"""Share of the launches' padded pattern slots that hold an instantiated
pattern (Counters.kernel_pat_used / Counters.kernel_pat_slots)."""


def read(rec):
    c = rec.counters
    if "kernel_pat_used" not in c or not c.get("kernel_pat_slots"):
        return None
    return 100.0 * c["kernel_pat_used"] / c["kernel_pat_slots"]
