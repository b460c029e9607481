"""Mean lateness of the batching window's timer: how long after its due
time the event loop ran it (BatchStats.timer_late_ns per timer flush)."""


def read(rec):
    b = rec.batch
    if "timer_late_ns" not in b or not b.get("timer_flushes"):
        return None
    return b["timer_late_ns"] / b["timer_flushes"] / 1e6
