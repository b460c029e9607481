"""Share of the window spent in waiting for launch outputs and copying them
back to the host (Counters.pull_ns, span brtpf.pull)."""


def read(rec):
    if "pull_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["pull_ns"] / 1e9 / rec.seconds
