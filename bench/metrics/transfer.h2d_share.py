"""Share of the window spent in host-to-device copies of launch inputs
(Counters.h2d_ns, span brtpf.h2d)."""


def read(rec):
    if "h2d_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["h2d_ns"] / 1e9 / rec.seconds
