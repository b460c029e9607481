"""Bytes brought back from the device as launch outputs, per request
served (Counters.d2h_bytes)."""


def read(rec):
    c = rec.counters
    if "d2h_bytes" not in c or not c.get("num_requests"):
        return None
    return c["d2h_bytes"] / c["num_requests"]
