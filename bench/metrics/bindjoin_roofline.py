"""Share of the memory roofline that the bind-join launch programs
reached in the traced window.

The least bytes are what the join needs whatever implements it: each
raw candidate row read once and each instantiated pattern slot read
once, three int32 ids (12 bytes) apiece,

    least_bytes = 12 * (kernel_cand_rows + kernel_pat_used).

The least time is those bytes over the chip's HBM bandwidth
(bench/peaks.json); the share is that time over the device time of the
launch programs ``_grouped_launch`` and ``_fused_launch`` (each the
kernel, its base-pattern mask and its epilogue). Padding, the second
read of the candidates by the mask, and the epilogue lower it."""

PROGRAMS = ("_grouped_launch", "_fused_launch")


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    c = rec.counters
    if "kernel_pat_used" not in c or not c.get("kernel_cand_rows"):
        return None
    busy = sum(t for name, t in rec.trace.get("device_ops", [])
               if name in PROGRAMS)
    if not busy:
        return None
    least_bytes = 12 * (c["kernel_cand_rows"] + c["kernel_pat_used"])
    return 100.0 * least_bytes / rec.peaks["hbm_bytes_per_s"] / busy
