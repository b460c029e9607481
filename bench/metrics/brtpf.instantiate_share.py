"""Share of the window spent turning requests' Omega into instantiated
patterns (Counters.instantiate_ns, span brtpf.instantiate)."""


def read(rec):
    if "instantiate_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["instantiate_ns"] / 1e9 / rec.seconds
