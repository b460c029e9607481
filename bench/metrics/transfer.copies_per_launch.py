"""Host-device transfers per kernel launch, both ways
((Counters.h2d_copies + Counters.d2h_copies) / Counters.kernel_launches)."""


def read(rec):
    c = rec.counters
    if "h2d_copies" not in c or not c.get("kernel_launches"):
        return None
    return (c["h2d_copies"] + c["d2h_copies"]) / c["kernel_launches"]
