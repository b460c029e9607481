"""Share of the window spent in the host ordering epilogue (kept-row
gather, stream_order) and the numpy small-work selections
(Counters.order_ns, span brtpf.order)."""


def read(rec):
    if "order_ns" not in rec.counters:
        return None
    return 100.0 * rec.counters["order_ns"] / 1e9 / rec.seconds
