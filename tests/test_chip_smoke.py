"""CPU rehearsal of the chip smoke: the served-path check at a tiny scale
(kernels in interpret mode), and the refusal to run off the TPU -- by the
script, and by the kernels' interpret-mode switch.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import ServerConfig
from repro.data.watdiv import WatDivScale, generate, generate_workload
from repro.serving import smoke

REPO = Path(__file__).resolve().parents[1]
# the benchmarks' CI scale: ~25K triples
SCALE = WatDivScale(users=1500, products=600, reviews=2500, retailers=24,
                    genres=30, cities=40, tags=80)
BUDGET = 500


@pytest.fixture(scope="module")
def served():
    data = generate(SCALE, seed=0)
    workload = generate_workload(data, 145, seed=1)
    queries, oracle, _skipped = smoke.within_budget(
        data.store, workload, 8, page_size=100, max_mpr=30,
        request_budget=BUDGET)
    config = ServerConfig(page_size=100, max_mpr=30,
                          selector_backend="kernel")
    problems, run = smoke.check_served_path(
        data.store, queries, config, clients=4, request_budget=BUDGET,
        oracle=oracle)
    return queries, oracle, problems, run


def test_served_path_matches_oracle(served):
    queries, _oracle, problems, run = served
    assert len(queries) == 8
    assert problems == []
    counters = run.metrics["counters"]
    assert counters["kernel_launches"] > 0
    assert counters["fused_launches"] > 0
    assert counters["fast_path_selects"] == 0
    assert run.statuses == {200: run.requests}


def test_check_catches_a_wrong_answer(served):
    queries, oracle, _problems, run = served
    wrong = list(oracle)
    qi = next(i for i, r in enumerate(oracle) if r.solutions.shape[0])
    wrong[qi] = dataclasses.replace(oracle[qi],
                                    solutions=oracle[qi].solutions[1:])
    found = smoke.problems(run, wrong, queries)
    assert len(found) == 1 and f"query {qi}" in found[0]


def test_check_catches_a_missing_device_path(served):
    queries, oracle, _problems, run = served
    counters = dict(run.metrics["counters"])
    counters.update(kernel_launches=0, fused_launches=0,
                    fast_path_selects=3)
    starved = dataclasses.replace(
        run, metrics={**run.metrics, "counters": counters})
    found = smoke.problems(starved, oracle, queries)
    assert len(found) == 3, found


def test_chip_smoke_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_only_on_the_cpu(monkeypatch, backend, interpret):
    from repro.kernels import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._use_interpret()
    else:
        assert ops._use_interpret() is interpret
