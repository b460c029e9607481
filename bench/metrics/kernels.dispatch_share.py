"""Share of the window spent in calling the kernels and their epilogues
until the calls return (enqueueing, and any compile)
(Counters.dispatch_ns, span brtpf.dispatch)."""


def read(rec):
    if "dispatch_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["dispatch_ns"] / 1e9 / rec.seconds
