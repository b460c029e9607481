"""Share of the window the front end spent serving resident pages on the
event loop (BatchStats.resident_ns, span brtpf.resident)."""


def read(rec):
    if "resident_ns" not in rec.batch:
        return None
    return 100.0 * rec.batch["resident_ns"] / 1e9 / rec.seconds
