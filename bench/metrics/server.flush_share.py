"""Share of the window spent in handle_batch as the front end dispatched
it (BatchStats.flush_ns, span brtpf.flush). The flush phases pages,
range, gather, h2d, dispatch, pull and order partition it; the rest is
host work no phase times."""


def read(rec):
    if "flush_ns" not in rec.batch:
        return None
    return 100.0 * rec.batch["flush_ns"] / 1e9 / rec.seconds
