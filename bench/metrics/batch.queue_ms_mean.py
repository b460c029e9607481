"""Mean time a batched request waited in the front end's queue, from
enqueue to its batch's dispatch (BatchStats.queue_wait_ns, on the event
loop's clock)."""


def read(rec):
    b = rec.batch
    if "queue_wait_ns" not in b or not b.get("requests"):
        return None
    return b["queue_wait_ns"] / b["requests"] / 1e6
