"""Share of the window spent in the candidate range and Omega sub-range
lookups (Counters.range_ns, span brtpf.range)."""


def read(rec):
    if "range_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["range_ns"] / 1e9 / rec.seconds
