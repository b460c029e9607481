"""Chaos benchmark: closed-loop load under seeded fault injection.

The brTPF line of work argues about *availability under load*; this is
the benchmark that measures it. A 4-replica
:class:`~repro.serving.router.ReplicaRouter` (shared WatDiv store,
kernel backend) runs under a deterministic
:class:`~repro.serving.faults.FaultPlan`:

* one replica (index 1) STALLS: after a handful of served requests,
  every subsequent request hangs far longer than any client deadline;
* every replica injects 5% transient transport errors (retryable 503s).

Sixteen closed-loop :class:`~repro.core.client.AsyncBrTPFClient`s drive
the WatDiv workload through a
:class:`~repro.serving.resilience.ResilientTransport` (per-request
deadline + per-attempt timeout, exponential backoff with full jitter,
hedging) over the loopback wire. The run asserts the whole resilience
story at once:

* **availability**: success rate over client-visible requests
  (``chaos_c16:success_rate`` budget, >= 0.999 -- retries + breaker
  failover must absorb the plan);
* **correctness**: every query that completes under faults returns
  byte-identical solutions to a fault-free sequential oracle
  (``chaos_c16:parity``) -- resilience must never change results;
* **tail latency**: p99 over the same requests
  (``chaos_c16:p99_latency_ms``) -- detouring around a stalled replica
  must cost bounded time, not hang;
* **regression-proofing (A/B)**: the SAME plan with resilience
  disabled (bare transport, deadlines only, no retries/failover) must
  demonstrably fail (``chaos_ab_c16:failed_queries`` >= 1) -- proving
  the fault plan has teeth and the pass above is earned.

Counters surface through ``GET /metrics``-schema snapshots read over
the transport itself (``resilience`` section: retries, hedges, shed,
breaker transitions/opens/failovers).
"""
from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core import AsyncBrTPFClient, BrTPFClient, BrTPFServer
from repro.core.config import ServerConfig
from repro.core.metrics import chaos_summary
from repro.core.sim import split_workload
from repro.serving.faults import FaultPlan, FaultSpec
from repro.serving.resilience import ResilientTransport, RetryPolicy
from repro.serving.router import ReplicaRouter
from repro.serving.transport import LoopbackTransport

from .common import BenchConfig, FAST_PATH_ROWS, dataset, emit, persist, \
    use_compile_cache, workload
from .throughput import BUDGETS_PATH, SHARD_WINDOW, check_budgets

REPLICAS = 4
STALLED_REPLICA = 1
ERROR_RATE = 0.05
PLAN_SEED = 1608            # arXiv:1608.08148

# Client resilience tuning: a stalled attempt is cut at
# ATTEMPT_TIMEOUT_MS (feeding the breaker), leaving most of DEADLINE_MS
# for the retry that lands on a healthy replica.
DEADLINE_MS = 8000.0
ATTEMPT_TIMEOUT_MS = 300.0
MAX_ATTEMPTS = 10
# The bare A/B arm gets deadlines only (no retries): tight enough that
# a stalled request fails fast instead of padding the wall clock.
AB_DEADLINE_MS = 2000.0


def fault_plan(seed: int = PLAN_SEED) -> FaultPlan:
    """The canonical acceptance plan: stall 1 of 4 replicas, 5%
    injected transport errors everywhere."""
    return FaultPlan(
        seed=seed,
        default=FaultSpec(error_rate=ERROR_RATE),
        per_replica={STALLED_REPLICA: FaultSpec(
            error_rate=ERROR_RATE, stall_after=2, stall_s=30.0)})


class _OutcomeTransport:
    """Counts client-visible request outcomes (after whatever
    resilience sits below) and times them -- the success-rate and
    latency surface the budgets gate."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.ok = 0
        self.failed = 0
        self.samples_s: List[float] = []

    @property
    def max_mpr(self) -> int:
        return self.inner.max_mpr

    async def handle(self, req):
        t0 = time.perf_counter()
        try:
            frag = await self.inner.handle(req)
        except Exception:
            self.failed += 1
            raise
        self.ok += 1
        self.samples_s.append(time.perf_counter() - t0)
        return frag

    async def metrics(self) -> dict:
        return await self.inner.metrics()

    async def aclose(self) -> None:
        await self.inner.aclose()


def _canon(solutions) -> np.ndarray:
    arr = np.asarray(solutions)
    if arr.size == 0:
        return arr.reshape(0, arr.shape[1] if arr.ndim == 2 else 0)
    return arr[np.lexsort(arr.T[::-1])]


def _server_config() -> ServerConfig:
    return ServerConfig(selector_backend="kernel",
                        fast_path_rows=FAST_PATH_ROWS,
                        shard_window=SHARD_WINDOW)


def _oracle(wl) -> Dict[int, np.ndarray]:
    """Fault-free ground truth: one sequential client over one plain
    server, no batching, no faults -- the byte-parity reference."""
    server = BrTPFServer(dataset().store, _server_config())
    client = BrTPFClient(server)
    return {i: _canon(client.execute(bgp).solutions)
            for i, (_name, bgp) in enumerate(wl)}


def run_chaos(clients: int = 16, resilient: bool = True,
              seed: int = PLAN_SEED, smoke: bool = False,
              oracle: Optional[Dict[int, np.ndarray]] = None) -> Dict:
    """One chaos arm. ``resilient=False`` is the A/B control: same
    plan, same deadlines, but a bare transport -- no retries, no
    hedging (the router's breaker still runs; it is part of the server,
    not the client)."""
    wl = list(workload())[:4 if smoke else 12]
    if oracle is None:
        oracle = _oracle(wl)
    router = ReplicaRouter(dataset().store, _server_config(),
                           replicas=REPLICAS,
                           fault_plan=fault_plan(seed),
                           failure_threshold=2, reset_after_s=0.5)
    base = LoopbackTransport(router)
    if resilient:
        inner = ResilientTransport(base, RetryPolicy(
            max_attempts=MAX_ATTEMPTS, base_backoff_s=2e-3,
            max_backoff_s=0.05, deadline_ms=DEADLINE_MS,
            attempt_timeout_ms=ATTEMPT_TIMEOUT_MS,
            hedge=True), seed=seed)
    else:
        inner = base
    probe = _OutcomeTransport(inner)
    indexed = list(enumerate(wl))
    per_client = split_workload(indexed, clients)
    failed_queries = 0
    mismatches = 0
    solved = 0

    async def one(client, queries) -> None:
        nonlocal failed_queries, mismatches, solved
        for i, (_name, bgp) in queries:
            try:
                res = await client.execute(bgp)
            except Exception:
                # client-visible query failure -- the A/B arm's whole
                # point; counted, never retried here (the resilient arm
                # already retried below, consulting is_retryable)
                failed_queries += 1
                continue
            solved += 1
            if not np.array_equal(_canon(res.solutions), oracle[i]):
                mismatches += 1

    async def main() -> dict:
        cs = [AsyncBrTPFClient(
            probe,
            deadline_ms=None if resilient else AB_DEADLINE_MS)
            for _ in range(clients)]
        try:
            await asyncio.gather(*[
                one(c, w) for c, w in zip(cs, per_client, strict=True)])
            return await probe.metrics()
        finally:
            await probe.aclose()

    t0 = time.perf_counter()
    snap = asyncio.run(main())
    wall = time.perf_counter() - t0
    out = chaos_summary(probe.ok, probe.failed, failed_queries,
                        probe.samples_s, wall_s=wall,
                        parity=1.0 if mismatches == 0 else 0.0)
    res = snap.get("resilience", {})
    breaker = res.get("breaker", {})
    out.update({
        "clients": clients,
        "resilient": 1.0 if resilient else 0.0,
        "queries": len(wl),
        "solved_queries": solved,
        "wall_s": wall,
        "retries": res.get("retries", 0),
        "hedges": res.get("hedges", 0),
        "shed": res.get("shed", 0),
        "breaker_opens": breaker.get("opens", 0),
        "breaker_transitions": breaker.get("transitions", 0),
        "failovers": breaker.get("failovers", 0),
    })
    return out


def run_sweep(smoke: bool = False, clients: int = 16) -> Dict:
    wl = list(workload())[:4 if smoke else 12]
    oracle = _oracle(wl)
    out: Dict = {}
    r = run_chaos(clients=clients, resilient=True, smoke=smoke,
                  oracle=oracle)
    out[("chaos", clients)] = r
    emit(f"chaos/resilient_c{clients}", 0.0,
         f"success_rate={r['success_rate']:.4f};"
         f"parity={r['parity']:.0f};"
         f"failed_queries={r['failed_queries']};"
         f"retries={r['retries']};hedges={r['hedges']};"
         f"shed={r['shed']};breaker_opens={r['breaker_opens']};"
         f"failovers={r['failovers']};"
         f"p99={r['p99_latency_ms']:.1f}ms;wall={r['wall_s']:.1f}s")
    ab = run_chaos(clients=clients, resilient=False, smoke=smoke,
                   oracle=oracle)
    # tuple key: check_budgets resolves "chaos_ab_c16" by splitting at
    # the first "_c", which lands on the concurrency suffix
    out[("chaos_ab", clients)] = ab
    emit(f"chaos/ab_bare_c{clients}", 0.0,
         f"success_rate={ab['success_rate']:.4f};"
         f"failed_queries={ab['failed_queries']};"
         f"solved={ab['solved_queries']}/{ab['queries']};"
         f"wall={ab['wall_s']:.1f}s")
    return out


def headline_metrics(out: Dict, clients: int = 16) -> Dict:
    r = out.get(("chaos", clients))
    ab = out.get(("chaos_ab", clients))
    h: Dict = {}
    if r:
        h.update({
            "chaos_c16_success_rate": r["success_rate"],
            "chaos_c16_p99_latency_ms": r["p99_latency_ms"],
            "chaos_c16_retries": r["retries"],
            "chaos_c16_breaker_opens": r["breaker_opens"],
            "chaos_c16_failovers": r["failovers"],
        })
    if ab:
        h["chaos_ab_c16_failed_queries"] = ab["failed_queries"]
    return h


def run(full: bool = False) -> Dict:
    """benchmarks.run entry point (CSV rows via ``emit``)."""
    return run_sweep(smoke=not full)


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="chaos: closed-loop load under seeded fault plans")
    parser.add_argument("--smoke", action="store_true",
                        help="small workload + budget gate (CI job)")
    parser.add_argument("--clients", type=int, default=16)
    args = parser.parse_args(argv)
    use_compile_cache()
    cfg = BenchConfig.default()
    assert cfg is not None  # env-validated scales
    out = run_sweep(smoke=args.smoke, clients=args.clients)
    failures = check_budgets(out, path=BUDGETS_PATH)
    # Both paths persist a trajectory entry (the smoke run is what CI
    # executes per PR, and every PR must land one); smoke keys carry a
    # ``smoke_`` prefix so they never masquerade as full-run numbers.
    headline = headline_metrics(out, clients=args.clients)
    if args.smoke:
        headline = {f"smoke_{k}": v for k, v in headline.items()}
    path = persist("throughput", out, headline=headline,
                   section="chaos_smoke" if args.smoke else "chaos")
    print(f"# persisted -> {path}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
