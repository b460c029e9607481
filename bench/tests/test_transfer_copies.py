"""The reader of ``transfer.copies_per_launch``: host-device transfers,
both ways, per kernel launch (``Counters.h2d_copies``, ``d2h_copies``
and ``kernel_launches`` over the window)."""
import numpy as np
import pytest

from bench import harness
from bench.run import Record

NAME = "transfer.copies_per_launch"


def _record(counters):
    return Record(seconds=2.0, setup_s=1.0, latency_s=np.array([0.01]),
                  answered_in_window=400, counters=counters,
                  batch={"requests": 200, "flushes": 12},
                  compiles_in_window=0)


@pytest.mark.parametrize("h2d,d2h,value", [
    (40, 40, 2.0),        # one packed buffer each way
    (240, 160, 10.0),     # six uploads and four pulls a launch
])
def test_reader_value(h2d, d2h, value):
    rec = _record({"num_requests": 400, "kernel_launches": 40,
                   "h2d_copies": h2d, "d2h_copies": d2h})
    assert harness.reader(NAME)(rec) == pytest.approx(value)


@pytest.mark.parametrize("counters", [
    {"num_requests": 400, "kernel_launches": 9},
    {"num_requests": 400, "kernel_launches": 0, "h2d_copies": 0,
     "d2h_copies": 0},
], ids=["program_without_the_counters", "no_launch"])
def test_reader_reads_nothing(counters):
    assert harness.reader(NAME)(_record(counters)) is None


def test_declared_for_the_tpf_cell():
    entry = {m["name"]: m for m in harness.spec()["per_layer"]}[NAME]
    assert (entry["layer"], entry["source"], entry["moves"],
            entry["better"]) == ("host-device transfer", "program_counter",
                                 "request_p95_ms", "lower")
    assert NAME in harness.metrics_of("lubm-tpf-c16", True)
