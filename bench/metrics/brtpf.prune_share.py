"""Share of the window spent in Omega pruning, the per-binding sub-range
lookups (Counters.prune_ns, span brtpf.prune, nested in brtpf.range)."""


def read(rec):
    if "prune_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["prune_ns"] / 1e9 / rec.seconds
