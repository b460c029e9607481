"""Bytes sent to the device as launch inputs, per request served
(Counters.h2d_bytes)."""


def read(rec):
    c = rec.counters
    if "h2d_bytes" not in c or not c.get("num_requests"):
        return None
    return c["h2d_bytes"] / c["num_requests"]
