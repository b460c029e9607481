"""Readers of the served path's phase counters (``GET /metrics``
``counters`` and ``batch`` over the window)."""
import numpy as np
import pytest

from bench import harness
from bench.run import Record

SECONDS = 2.0
COUNTERS = {"num_requests": 400, "pages_ns": 100_000_000,
            "range_ns": 20_000_000, "gather_ns": 300_000_000,
            "h2d_ns": 40_000_000, "h2d_bytes": 4_000_000,
            "dispatch_ns": 60_000_000, "pull_ns": 500_000_000,
            "d2h_bytes": 800_000, "order_ns": 70_000_000}
BATCH = {"requests": 200, "timer_flushes": 10, "flushes": 12,
         "flush_ns": 1_200_000_000, "queue_wait_ns": 1_000_000_000,
         "timer_late_ns": 30_000_000, "resident_ns": 160_000_000,
         "wire_ns": 80_000_000}

# metric -> value read from COUNTERS and BATCH over SECONDS
EXPECTED = {
    "batch.queue_ms_mean": 5.0,          # 1 s over 200 requests
    "batch.timer_late_ms_mean": 3.0,     # 30 ms over 10 timer flushes
    "batch.resident_share": 8.0,         # 0.16 s of 2 s
    "wire.server_ms_mean": 0.2,          # 80 ms over 400 requests
    "server.flush_share": 60.0,
    "server.pages_share": 5.0,
    "select.range_share": 1.0,
    "select.gather_share": 15.0,
    "select.order_share": 3.5,
    "transfer.h2d_share": 2.0,
    "transfer.pull_share": 25.0,
    "transfer.h2d_bytes_per_req": 10_000.0,
    "transfer.d2h_bytes_per_req": 2_000.0,
    "kernels.dispatch_share": 3.0,
}


def _record(counters, batch):
    return Record(seconds=SECONDS, setup_s=1.0,
                  latency_s=np.array([0.01]), answered_in_window=400,
                  counters=counters, batch=batch, compiles_in_window=0)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value(name):
    rec = _record(dict(COUNTERS), dict(BATCH))
    assert harness.reader(name)(rec) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_phases(name):
    # a program that exports no phase counters: the reader returns
    # nothing, and does not raise
    old_counters = {"num_requests": 400, "kernel_launches": 9}
    old_batch = {"requests": 200, "flushes": 12, "timer_flushes": 10}
    assert harness.reader(name)(_record(old_counters, old_batch)) is None


def test_flush_phases_fit_in_the_flush_share():
    rec = _record(dict(COUNTERS), dict(BATCH))
    parts = ("server.pages_share", "select.range_share",
             "select.gather_share", "transfer.h2d_share",
             "kernels.dispatch_share", "transfer.pull_share",
             "select.order_share")
    total = sum(harness.reader(n)(rec) for n in parts)
    assert total == pytest.approx(54.5)
    assert total <= harness.reader("server.flush_share")(rec)


def test_every_phase_metric_is_declared_for_the_tpf_cell():
    entries = {m["name"]: m for m in harness.spec()["per_layer"]}
    for name in EXPECTED:
        entry = entries[name]
        assert entry["source"] == "program_counter"
        assert entry["moves"] == "request_p95_ms"
        assert entry["workloads"] == ["lubm-tpf-c16"]
    assert set(EXPECTED) <= set(harness.metrics_of("lubm-tpf-c16", True))
