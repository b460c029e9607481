"""Share of the window spent in gathering candidate blocks and building the
padded launch inputs and pattern grids (Counters.gather_ns, span
brtpf.gather)."""


def read(rec):
    if "gather_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["gather_ns"] / 1e9 / rec.seconds
