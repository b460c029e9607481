"""Mean server-side wire time per request: the ASGI app's request decode
and response encode (BatchStats.wire_ns, span brtpf.wire)."""


def read(rec):
    if "wire_ns" not in rec.batch or not rec.counters.get("num_requests"):
        return None
    return rec.batch["wire_ns"] / rec.counters["num_requests"] / 1e6
