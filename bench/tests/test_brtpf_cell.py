"""The brTPF cell ``lubm-brtpf-c1`` run whole at LUBM(1) on the CPU (the
chip check skipped): a sound run is correct with Omega-bound pages among
those checked, and the control is not; and its readers, on hand-made
records."""
import time

import numpy as np
import pytest

from bench import faults, harness, run
from bench.run import Record

CELL = "lubm-brtpf-c1"
SECONDS = 4.0
NEW_METRICS = ("brtpf.instantiate_share", "brtpf.prune_share",
               "omega.mappings_per_req", "select.slot_fill_share",
               "bindjoin_roofline")


def _run(monkeypatch, seed, fault=None):
    """One run at LUBM(1); returns (result, the sampled page items)."""
    seen = []

    def check(triples, queries, completed, sample_items, page_size):
        seen.extend(sample_items)
        return checked(triples, queries, completed, sample_items,
                       page_size)

    checked = run.check
    monkeypatch.setattr(run, "check", check)
    dataset = dict(harness.config("lubm50-brtpf")["dataset"],
                   universities=1)
    result = run.run_cell(CELL, seed, SECONDS, False, dataset=dataset,
                          warmup=(0.5, 0.0, 0.5), cache_dir=None,
                          fault=fault, t_start=time.perf_counter())
    return result, seen


def test_sound_run_is_correct_with_omega_pages(monkeypatch):
    result, items = _run(monkeypatch, 2**31 + 11)
    checks = result["check"]
    assert result["correct"], checks
    assert checks["queries_checked"]["value"] >= 1
    assert checks["requests_failed"]["value"] == 0
    assert any(omega is not None and len(omega) for _p, omega, *_ in items)
    assert result["notes"]["triples"] == 98_511       # LUBM(1)
    assert set(result["metrics"]) == set(harness.metrics_of(CELL, False))


def test_control_is_not_correct(monkeypatch):
    result, _ = _run(monkeypatch, 2**31 + 12, faults.incomplete_pages)
    assert not result["correct"]
    assert result["check"]["pages_wrong"]["value"] > 0


def test_the_cell_reads_its_new_metrics_when_traced():
    cell = harness.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lubm50-brtpf", "lubm14-c1", 1)
    assert set(harness.metrics_of(CELL, True)) == set(NEW_METRICS)
    cfg = harness.config(cell["config"])
    assert (cfg["interface"], cfg["max_mpr"], cfg["page_size"]) == (
        "brtpf", 30, 100)
    mix = harness.mix(cell["traffic"])
    assert mix["clients"] == 1 and "lead_in" not in mix
    assert mix["warmup_quiet_s"] == 30
    assert mix["warmup_s"] <= mix["warmup_max_s"] <= 200


# ---------------------------------------------------------------------------
# The readers
# ---------------------------------------------------------------------------

COUNTERS = {"num_requests": 500, "instantiate_ns": 40_000_000,
            "prune_ns": 100_000_000, "mappings_sent": 6_000,
            "omega_requests": 250, "kernel_pat_used": 3_000,
            "kernel_pat_slots": 12_000, "kernel_cand_rows": 997_000}
# 12 B x (997,000 rows + 3,000 slots) = 12 MB, 1 ms at 12 GB/s
PEAKS = {"hbm_bytes_per_s": 12e9}
TRACE = {"busy_s": 0.01, "idle_share": 0.995,
         "device_ops": [["_grouped_launch", 0.003], ["_fused_launch", 0.001],
                        ["copy", 0.006]]}


def _record(counters, trace=None, peaks=None):
    return Record(seconds=2.0, setup_s=1.0, latency_s=np.array([0.01]),
                  answered_in_window=500, counters=counters,
                  batch={"requests": 500}, compiles_in_window=0,
                  trace=trace, peaks=peaks)


@pytest.mark.parametrize("name,value", [
    ("brtpf.instantiate_share", 2.0),     # 40 ms of 2 s
    ("brtpf.prune_share", 5.0),
    ("omega.mappings_per_req", 24.0),
    ("select.slot_fill_share", 25.0),
    ("bindjoin_roofline", 25.0),          # 1 ms of the programs' 4 ms
])
def test_reader_value(name, value):
    rec = _record(dict(COUNTERS), TRACE, PEAKS)
    assert harness.reader(name)(rec) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reads_nothing_from_a_program_without_the_counters(name):
    # the parent program exports none of the four counters
    old = {"num_requests": 500, "mappings_sent": 6_000,
           "kernel_pat_slots": 12_000, "kernel_cand_rows": 997_000}
    assert harness.reader(name)(_record(old, TRACE, PEAKS)) is None


def test_mappings_per_req_reads_nothing_without_omega():
    rec = _record(dict(COUNTERS, mappings_sent=0, omega_requests=0))
    assert harness.reader("omega.mappings_per_req")(rec) is None


def test_bindjoin_roofline_reads_nothing_without_a_trace():
    read = harness.reader("bindjoin_roofline")
    assert read(_record(dict(COUNTERS))) is None
    assert read(_record(dict(COUNTERS), None, PEAKS)) is None
    # a trace in which neither launch program ran
    idle = dict(TRACE, device_ops=[["copy", 0.006]])
    assert read(_record(dict(COUNTERS), idle, PEAKS)) is None


@pytest.mark.parametrize("busy", [0.004, 0.001, 0.0010001])
def test_bindjoin_roofline_never_passes_100(busy):
    # the programs' time never falls below the least time, 1 ms here,
    # so the share never passes 100
    trace = dict(TRACE, device_ops=[["_grouped_launch", busy]])
    share = harness.reader("bindjoin_roofline")(
        _record(dict(COUNTERS), trace, PEAKS))
    assert 0 < share <= 100 + 1e-9
