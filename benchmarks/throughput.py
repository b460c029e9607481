"""Paper Figure 3 (+ Appendix B): throughput under concurrent load.

Replays real engine traces through the calibrated discrete-event cluster
model (core/sim.py): one 4-worker server, N in {4, 16, 64} concurrent
clients, 5-minute query timeout, one simulated hour -- with and without
the shared HTTP cache (Figure 3 right column / section 7.2).

Validation targets: (C3) brTPF completes more queries than TPF at every
client count, TPF times out more, both scale with clients; (C4) the
cache raises both, TPF gains more (higher hit rate) but does not
overtake brTPF in completed queries; average QET grows slower for brTPF.

Selector-backend axis (beyond-paper): the brTPF workload is also traced
through the *kernel* selector backend (Pallas bind-join over the store's
candidate ranges) and the *sharded* windowed backend (mesh-partitioned
store, fixed per-shard window launches) and replayed under the TPU
launch cost model, with and without cross-request batching
(``SimParams.batch_window_s``), so the server-side speedup of the
accelerated paths is a measured comparison on the same request streams,
not an assertion. ``run_hetero_mix`` A/Bs cross-pattern kernel fusion
(docs/fusion.md) on identical heterogeneous request streams -- fused vs
unfused launches-per-request, CI-gated via ``hetero_c16:*``;
``run_sharded_axis`` sweeps the sharded geometry
(per-shard window); ``run_warm_cache`` measures the unified fragment
store (a warm pass must skip every launch -- CI-gated via
``budgets.json`` ``warm_cache:*``); ``run_cache_axis`` reproduces the
section-7.1 TPF-vs-brTPF HTTP hit-rate comparison under an LRU
capacity sweep. The whole run persists to ``BENCH_throughput.json`` at
the repo root for cross-PR tracking.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import time
from typing import Dict

from repro.core import (AsyncBrTPFClient, AsyncBrTPFServer, BrTPFClient,
                        LRUCache, layer_metrics)
from repro.core.sim import (calibrate, collect_traces, simulate,
                            split_workload)

from .common import (BenchConfig, emit, make_server, persist,
                     run_sequence, use_compile_cache, workload)

BUDGETS_PATH = os.path.join(os.path.dirname(__file__), "budgets.json")

# Per-shard window used by every sharded-backend measurement below (and
# by the budget gate): large enough that WatDiv CI-scale ranges take a
# handful of window launches, small enough that per-launch streaming
# stays an order of magnitude under the store size.
SHARD_WINDOW = 2048


def run(full: bool = False) -> Dict:
    cfg = BenchConfig.default()
    wl = list(workload())
    client_counts = [4, 16, 64]
    out: Dict = {}

    # one trace collection per (client kind, selector backend) -- server
    # state is stateless across requests, so traces are reusable across
    # client counts
    server = make_server()
    params = calibrate(server, wl)
    if not full:
        # 10 simulated minutes keeps the event-granular replay fast; the
        # TPF-vs-brTPF comparison is horizon-independent
        params.duration_s = 600.0
    traces = {}
    for kind, backend, mpr in [("tpf", "numpy", None),
                               ("brtpf", "numpy", 30),
                               ("brtpf-kernel", "kernel", 30),
                               ("brtpf-sharded", "sharded", 30)]:
        server = make_server(max_mpr=mpr or 30, selector_backend=backend,
                             shard_window=SHARD_WINDOW)
        traces[kind] = collect_traces(
            server, wl, kind.split("-")[0], max_mpr=mpr,
            request_budget=cfg.request_budget)

    for use_cache in (False, True):
        for n in client_counts:
            for kind in ("tpf", "brtpf"):
                per_client = split_workload(traces[kind], n)
                res = simulate(per_client, params,
                               cache_size=None, use_cache=use_cache,
                               wrap=True)
                key = (kind, n, use_cache)
                out[key] = res
                emit(
                    f"throughput/{kind}_c{n}"
                    f"{'_cache' if use_cache else ''}",
                    0.0,
                    f"completed_per_hr={res.throughput_per_hour:.0f};"
                    f"timeouts={res.timeouts};"
                    f"attempted_per_hr={res.attempts_per_hour:.0f};"
                    f"avg_qet={res.avg_qet:.2f}s;"
                    f"horizon={res.simulated_s:.0f}s")

    # selector-backend axis: same brTPF request streams, kernel launch
    # cost model (single-host kernel vs mesh-sharded windowed), batching
    # off vs on
    for kind in ("brtpf-kernel", "brtpf-sharded"):
        for n in client_counts:
            for label, window in [("batch0", 0.0), ("batch2ms", 2e-3)]:
                kp = dataclasses.replace(params, batch_window_s=window)
                per_client = split_workload(traces[kind], n)
                res = simulate(per_client, kp, cache_size=None,
                               use_cache=False, wrap=True)
                out[(kind, n, label)] = res
                emit(
                    f"throughput/{kind.replace('-', '_')}_c{n}_{label}",
                    0.0,
                    f"completed_per_hr={res.throughput_per_hour:.0f};"
                    f"timeouts={res.timeouts};"
                    f"launches_per_request="
                    f"{res.launches_per_request:.3f};"
                    f"avg_qet={res.avg_qet:.2f}s;"
                    f"horizon={res.simulated_s:.0f}s")
    return out


# ---------------------------------------------------------------------------
# Concurrency axis: REAL in-flight clients over the async front end
# ---------------------------------------------------------------------------


def _run_concurrent(backend: str, n: int, wl, request_budget: int,
                    batch_window_s: float = 2e-3,
                    max_batch: int = 64,
                    shard_window: int = SHARD_WINDOW,
                    fuse: bool = True,
                    per_client=None) -> Dict:
    """Run ``n`` concurrent AsyncBrTPFClients over one front end;
    returns wall-clock + launch accounting. ``per_client`` overrides
    the default round-robin partition with an explicit per-client
    workload assignment (the hetero-mix axis rotates overlapping
    subsets so every client stays busy with a different query)."""
    server = make_server(selector_backend=backend,
                         shard_window=shard_window,
                         fuse_patterns=fuse)
    front = AsyncBrTPFServer(server, batch_window_s=batch_window_s,
                             max_batch=max_batch)
    if per_client is None:
        per_client = split_workload(wl, n)

    async def main():
        clients = [AsyncBrTPFClient(front, request_budget=request_budget)
                   for _ in range(n)]
        try:
            return await asyncio.gather(
                *[c.run_workload(w)
                  for c, w in zip(clients, per_client, strict=True)])
        finally:
            await front.aclose()

    t0 = time.perf_counter()
    results = asyncio.run(main())
    wall = time.perf_counter() - t0
    c = server.counters
    reqs = max(c.num_requests, 1)
    return {
        "wall_s": wall,
        "requests": c.num_requests,
        "req_per_s": c.num_requests / max(wall, 1e-9),
        "launches": c.kernel_launches,
        "launches_per_request": c.kernel_launches / reqs,
        # per-device candidate rows streamed (window * launches on the
        # sharded backend, padded range buckets on the kernel backend)
        "cand_streamed": c.kernel_cand_streamed,
        "cand_streamed_per_request": c.kernel_cand_streamed / reqs,
        # Omega-restricted pruning + small-work fast path accounting
        "cand_pruned_away": c.cand_pruned_away,
        "fast_path_selects": c.fast_path_selects,
        "shard_window": shard_window if backend == "sharded" else 0,
        "shards": (server.federated.shards
                   if backend == "sharded" else 0),
        "batched_requests": c.kernel_batched_requests,
        # cross-pattern fusion accounting (docs/fusion.md): launches
        # that carried >= 2 pattern segments, and how many segments
        # each such launch amortised
        "fused_launches": c.fused_launches,
        "fused_launches_per_request": c.fused_launches / reqs,
        "fused_segments": c.fused_segments,
        "fused_segments_per_launch": (
            c.fused_segments / c.fused_launches
            if c.fused_launches else 0.0),
        # unified fragment store: launches avoided by residency + the
        # per-layer hit rates of the server's metrics snapshot
        "launches_skipped": c.launches_skipped,
        "launches_skipped_per_request": c.launches_skipped / reqs,
        "memo_hit_rate": server.fragments.hit_rate,
        "layers": layer_metrics(server),
        "fast_path": front.stats.fast_path,
        "flushes": front.stats.flushes,
        "mean_batch": front.stats.mean_batch,
        "completed": sum(sum(1 for r in rs if not r.timed_out)
                         for rs in results),
    }


def run_async(full: bool = False, smoke: bool = False) -> Dict:
    """Wall-clock concurrency axis: 1/4/16/64 in-flight clients on the
    real async batching front end, numpy vs kernel vs sharded backend."""
    cfg = BenchConfig.default()
    wl = list(workload())
    if smoke:
        wl = wl[:6]
        grid = [("kernel", 1), ("kernel", 8), ("sharded", 8)]
    else:
        if not full:
            wl = wl[:12]
        counts = [1, 4, 16, 64]
        grid = [(b, n) for b in ("numpy", "kernel", "sharded")
                for n in counts]
    out: Dict = {}
    for backend, n in grid:
        r = _run_concurrent(backend, n, wl, cfg.request_budget)
        out[(backend, n)] = r
        emit(
            f"throughput/async_{backend}_c{n}", 0.0,
            f"req_per_s={r['req_per_s']:.0f};"
            f"requests={r['requests']};"
            f"launches_per_request={r['launches_per_request']:.3f};"
            f"skipped_per_request="
            f"{r['launches_skipped_per_request']:.3f};"
            f"memo_hit_rate={r['memo_hit_rate']:.3f};"
            f"cand_per_request={r['cand_streamed_per_request']:.0f};"
            f"pruned_away={r['cand_pruned_away']};"
            f"fast_path_selects={r['fast_path_selects']};"
            f"batched={r['batched_requests']};"
            f"fast_path={r['fast_path']};"
            f"mean_batch={r['mean_batch']:.1f};"
            f"completed={r['completed']};"
            f"wall={r['wall_s']:.1f}s")
    return out


# ---------------------------------------------------------------------------
# Heterogeneous-mix axis: cross-pattern fusion under concurrent load
# ---------------------------------------------------------------------------


def run_hetero_mix(full: bool = False, smoke: bool = False) -> Dict:
    """Cross-pattern fusion axis (docs/fusion.md): N concurrent clients
    each working a *different* query subset, so every batching window
    holds a heterogeneous pattern mix (>= 4 distinct patterns in flight
    at N >= 4). Each client count runs twice on the kernel backend --
    fused and unfused -- on identical request streams, so the
    launches-per-request drop is a same-stream A/B, not a model
    estimate. ``launch_drop`` is the unfused/fused ratio; the CI gate
    (``budgets.json`` ``hetero_c16:*`` + ``hetero_unfused_c16:*``)
    bounds the fused side from above and the unfused side from below,
    which pins the drop at smoke scale.

    Client i works queries ``wl[i], wl[i+1], ... (mod len)`` -- rotated
    *overlapping* subsets rather than a disjoint partition, so no
    client finishes early and drains the mix into homogeneous
    single-pattern windows (a disjoint split at 16 clients leaves the
    straggler flushing alone, which is exactly the unfused regime)."""
    cfg = BenchConfig.default()
    wl = list(workload())
    if smoke:
        wl = wl[:8]
        counts = [16]
    else:
        if not full:
            wl = wl[:12]
        counts = [1, 4, 16, 64]
    per = min(4, len(wl))
    out: Dict = {}
    for n in counts:
        per_client = [[wl[(i + j) % len(wl)] for j in range(per)]
                      for i in range(n)]
        fused = _run_concurrent("kernel", n, wl, cfg.request_budget,
                                fuse=True, per_client=per_client)
        unfused = _run_concurrent("kernel", n, wl, cfg.request_budget,
                                  fuse=False, per_client=per_client)
        r = dict(fused)
        r["launches_unfused"] = unfused["launches"]
        r["launches_per_request_unfused"] = \
            unfused["launches_per_request"]
        r["launch_drop"] = (
            unfused["launches_per_request"]
            / max(fused["launches_per_request"], 1e-12))
        out[("hetero", n)] = r
        out[("hetero_unfused", n)] = unfused
        emit(
            f"throughput/hetero_c{n}", 0.0,
            f"launches_per_request={r['launches_per_request']:.3f};"
            f"unfused={r['launches_per_request_unfused']:.3f};"
            f"launch_drop={r['launch_drop']:.2f}x;"
            f"fused_launches_per_request="
            f"{r['fused_launches_per_request']:.3f};"
            f"fused_segments_per_launch="
            f"{r['fused_segments_per_launch']:.2f};"
            f"cand_per_request={r['cand_streamed_per_request']:.0f};"
            f"completed={r['completed']};"
            f"wall={r['wall_s']:.1f}s")
    return out


# ---------------------------------------------------------------------------
# Sharded axis: shards x window (the tentpole's perf claim)
# ---------------------------------------------------------------------------


def run_sharded_axis(full: bool = False) -> Dict:
    """Sweep the sharded backend's geometry: per-shard window size (and
    every shard the host exposes -- on a multi-device host the store is
    mesh-partitioned across all of them).

    The claim this axis demonstrates: candidates streamed per request
    are bounded by the *window* (one device's per-launch stream),
    independent of range/store/shard size -- versus the kernel backend,
    whose per-request stream is the pattern's padded range bucket.
    """
    cfg = BenchConfig.default()
    wl = list(workload())
    if not full:
        wl = wl[:12]
    windows = [256, 1024, 2048, 8192] if full else [512, 2048]
    out: Dict = {}
    for window in windows:
        r = _run_concurrent("sharded", 8, wl, cfg.request_budget,
                            shard_window=window)
        out[("sharded", 8, window)] = r
        emit(
            f"throughput/sharded_c8_w{window}", 0.0,
            f"shards={r['shards']};"
            f"req_per_s={r['req_per_s']:.0f};"
            f"launches_per_request={r['launches_per_request']:.3f};"
            f"cand_per_request={r['cand_streamed_per_request']:.0f};"
            f"completed={r['completed']};"
            f"wall={r['wall_s']:.1f}s")
    return out


# ---------------------------------------------------------------------------
# Unified-fragment-store axes: warm-cache skips + section-7.1 capacity sweep
# ---------------------------------------------------------------------------


def run_warm_cache(smoke: bool = False, backend: str = "kernel",
                   queries: int = 6) -> Dict:
    """Warm-cache measurement for the unified fragment store.

    Runs the same brTPF query sequence twice against one server with an
    unlimited HTTP cache; the second (warm) pass must be served from
    the unified store -- near-zero kernel launches, one skipped launch
    per request, HTTP hit rate ~1. The two warm-pass ratios are gated
    in CI (``budgets.json``: ``warm_cache:*``).
    """
    cfg = BenchConfig.default()
    wl = list(workload())[:queries if smoke else 2 * queries]
    server = make_server(cache=LRUCache(None), selector_backend=backend,
                         shard_window=SHARD_WINDOW)

    def one_pass():
        for _name, bgp in wl:
            BrTPFClient(server,
                        request_budget=cfg.request_budget).execute(bgp)

    one_pass()                    # cold: populate every layer
    server.reset_counters()
    one_pass()                    # warm: must skip every launch
    c = server.counters
    reqs = max(c.num_requests, 1)
    r = {
        "requests": c.num_requests,
        "launches": c.kernel_launches,
        "launches_per_request": c.kernel_launches / reqs,
        "launches_skipped": c.launches_skipped,
        "launches_skipped_per_request": c.launches_skipped / reqs,
        "hit_rate": server.cache.hit_rate,
        "layers": layer_metrics(server),
    }
    emit(
        f"throughput/warm_cache_{backend}", 0.0,
        f"requests={r['requests']};"
        f"launches={r['launches']};"
        f"skipped_per_request={r['launches_skipped_per_request']:.3f};"
        f"hit_rate={r['hit_rate']:.3f}")
    return r


def run_cache_axis(full: bool = False) -> Dict:
    """Section 7.1 (paper Figure 4a as *rates*): TPF-vs-brTPF HTTP
    cache hit rates under an LRU capacity sweep (unlimited / 1k / 100
    entries), persisted with the throughput results.

    Validation targets: TPF's hit rate >> brTPF's at every capacity
    (distinct Omega attachments make distinct URLs), maxMpR=15 beats
    maxMpR=30 on hits, and shrinking capacity only lowers hit rates.
    The servers run the numpy oracle backend: these are the paper's
    HTTP-layer numbers, deliberately free of memo/kernel effects.
    """
    capacities = [None, 1000, 100]
    out: Dict = {}
    for label, kind, mpr in [("tpf", "tpf", 30),
                             ("brtpf15", "brtpf", 15),
                             ("brtpf30", "brtpf", 30)]:
        for cap in capacities:
            cache = LRUCache(cap)
            server, _results = run_sequence(kind, max_mpr=mpr,
                                            cache=cache)
            key = (label, "inf" if cap is None else cap)
            out[key] = {
                "capacity": cap,
                "hits": cache.hits,
                "misses": cache.misses,
                "hit_rate": cache.hit_rate,
                "requests": server.counters.num_requests,
            }
            emit(
                f"throughput/cache_{label}_cap{cap or 'inf'}", 0.0,
                f"hits={cache.hits};"
                f"hit_rate={cache.hit_rate:.3f};"
                f"requests={server.counters.num_requests}")
    return out


def check_budgets(results: Dict, path: str = BUDGETS_PATH) -> int:
    """Gate kernel-backend launch coalescing (and warm-cache reuse)
    against checked-in budgets.

    Budgets are *counts/rates*, not wall-clock times, so the gate is
    stable across CI machine speeds. A plain number is an upper bound;
    a ``{"min": x}`` / ``{"max": y}`` object bounds either side (the
    warm-cache gates are lower bounds: hit rates must not regress).
    Returns the number of violations.
    """
    with open(path) as fh:
        budgets = json.load(fh)
    failures = 0
    for key, limit in budgets.items():
        name, metric = key.rsplit(":", 1)
        backend, _, cn = name.partition("_c")
        if cn.isdigit():
            r = results.get((backend, int(cn)))
        else:
            r = results.get(name)
        if r is None:
            print(f"budget SKIP {key}: combination not measured")
            continue
        value = r[metric]
        if isinstance(limit, dict):
            lo, hi = limit.get("min"), limit.get("max")
            ok = ((lo is None or value >= lo)
                  and (hi is None or value <= hi))
            bound = " and ".join(
                s for s in ([f">= {lo}"] if lo is not None else [])
                + ([f"<= {hi}"] if hi is not None else []))
        else:
            ok = value <= limit
            bound = f"<= {limit}"
        print(f"budget {'OK  ' if ok else 'FAIL'} {key}: "
              f"{value:.3f} {bound}")
        failures += 0 if ok else 1
    return failures


def headline_metrics(out: Dict) -> Dict:
    """One flat dict of the run's headline numbers -- the per-PR
    trajectory entry appended to ``BENCH_throughput.json`` (PR id is
    attached by ``common.persist``), so the perf history is a diffable
    series instead of a single overwritten snapshot."""
    h: Dict = {}
    k1 = out.get("async", {}).get(("kernel", 1))
    if k1:
        h.update({
            "kernel_c1_req_per_s": k1["req_per_s"],
            "kernel_c1_launches_per_request": k1["launches_per_request"],
            "kernel_c1_cand_per_request":
                k1["cand_streamed_per_request"],
            "kernel_c1_fast_path_selects": k1["fast_path_selects"],
            "kernel_c1_cand_pruned_away": k1["cand_pruned_away"],
        })
    sharded = out.get("sharded_axis", {}).get(("sharded", 8, SHARD_WINDOW))
    if sharded:
        h.update({
            "sharded_c8_launches_per_request":
                sharded["launches_per_request"],
            "sharded_c8_cand_per_request":
                sharded["cand_streamed_per_request"],
        })
    hetero = out.get("hetero", {}).get(("hetero", 16))
    if hetero:
        h.update({
            "hetero_c16_launches_per_request":
                hetero["launches_per_request"],
            "hetero_c16_launches_per_request_unfused":
                hetero["launches_per_request_unfused"],
            "hetero_c16_launch_drop": hetero["launch_drop"],
            "hetero_c16_fused_launches_per_request":
                hetero["fused_launches_per_request"],
            "hetero_c16_fused_segments_per_launch":
                hetero["fused_segments_per_launch"],
        })
    warm = out.get("warm_cache")
    if warm:
        h["warm_cache_hit_rate"] = warm["hit_rate"]
    return h


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--full", action="store_true")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny concurrency run + budget gate (CI job 3)")
    parser.add_argument("--async-only", action="store_true",
                        help="skip the trace-replay simulation section")
    args = parser.parse_args(argv)
    use_compile_cache()
    if args.smoke:
        results = run_async(smoke=True)
        results.update(run_hetero_mix(smoke=True))
        results["warm_cache"] = run_warm_cache(smoke=True)
        failures = check_budgets(results)
        # The smoke run is what CI executes per PR, so it must land the
        # PR's trajectory entry too (full runs previously were the only
        # writers, leaving PRs that only ran smoke absent from the
        # series). Smoke keys are ``smoke_``-prefixed so the reduced
        # concurrency sweep never masquerades as full-run numbers.
        headline = {f"smoke_{k}": v for k, v in
                    headline_metrics({"async": results,
                                      "hetero": results,
                                      "warm_cache":
                                          results["warm_cache"]}).items()}
        headline["smoke_budget_failures"] = failures
        path = persist("throughput", results, headline=headline,
                       section="smoke")
        print(f"# persisted -> {path}")
        return 1 if failures else 0
    out: Dict = {}
    if not args.async_only:
        out["replay"] = run(full=args.full)
    out["async"] = run_async(full=args.full)
    out["hetero"] = run_hetero_mix(full=args.full)
    out["sharded_axis"] = run_sharded_axis(full=args.full)
    out["warm_cache"] = run_warm_cache()
    out["cache_axis"] = run_cache_axis(full=args.full)
    path = persist("throughput", out, headline=headline_metrics(out))
    print(f"# persisted -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
