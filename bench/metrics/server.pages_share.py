"""Share of the window spent in handle_batch's page loop: memo reads,
paging, the HTTP cache and transfer accounting (Counters.pages_ns, span
brtpf.pages). Self time: a solo request's selection inside the loop is
charged to its own phases."""


def read(rec):
    if "pages_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["pages_ns"] / 1e9 / rec.seconds
