"""Bring-up smoke test: the served brTPF path on a TPU at WatDiv-10M scale.

    python chip_smoke.py [--seed N]        # one chip, kernel backend
    python chip_smoke.py --chips 4         # four chips, sharded backend

Generates a WatDiv-like graph at the paper's deployment size (section
5.2: about 10M triples, page size 100, maxMpR 30), serves queries of the
paper's seed-1 selection to four concurrent clients through the ASGI app
(``app_from_config`` -> ``AsgiTransport``) and holds every answer to the
numpy oracle (``repro.serving.smoke``). The queries are the first eight
that the oracle completes within the per-query request budget, the
harness's stand-in for the paper's query timeout.

One chip: ``selector_backend="kernel"``; the run must launch the grouped
and the fused bind-join kernels, take no numpy fast path, and answer
every request with HTTP 200. ``--chips 4``: ``selector_backend="sharded"``
on a 4-device mesh, served once on the initial equal split (the static
placement) and again after one heat-driven ``repartition()``; each pass
is held to the oracle, and every index array must hold one shard on each
of the four chips.

Refuses to run anywhere but on a TPU. The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; times printed
above it are cold set-up times (compilation included), not speed.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from benchmarks.common import use_compile_cache  # noqa: E402
from repro.core import ServerConfig  # noqa: E402
from repro.core.store import _MAX_ID  # noqa: E402
from repro.data.watdiv import (WatDivScale, generate,  # noqa: E402
                               generate_workload)
from repro.serving import smoke  # noqa: E402
from repro.serving.http import app_from_config  # noqa: E402

# 32x the benchmarks' --full scale: ~9.5M triples, ~1.9M terms.
SCALE = dict(users=640_000, products=256_000, reviews=960_000,
             retailers=100, genres=60, cities=120, tags=300)
PAGE_SIZE = 100
MAX_MPR = 30
QUERIES = 8
SELECTION = 145           # the paper's query selection size
REQUEST_BUDGET = 2_000    # per query
CLIENTS = 4
# Per-shard window of the four-chip path: a 2.56M-row range takes ~40
# window launches per shard, and up to eight segments still fuse under
# MAX_FUSED_STREAM.
SHARD_WINDOW = 16_384


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _shard_problems(fed, devices) -> list:
    """Every index array must be split one shard per chip."""
    want = sorted(d.id for d in devices)
    out = []
    for name, idx in fed.indexes.items():
        for label, arr in (("triples", idx.triples), ("keys", idx.keys),
                           ("valid", idx.valid)):
            shards = arr.addressable_shards
            on = sorted(s.device.id for s in shards)
            starts = {s.index[0].start or 0 for s in shards}
            if on != want or len(starts) != len(want):
                out.append(f"{name}.{label}: shards on devices {on}, "
                           f"row starts {sorted(starts)}")
    return out


def _report(label: str, run, before: dict) -> None:
    c = {k: v - before.get(k, 0) for k, v in run.metrics["counters"].items()}
    _say(f"[{label}] requests: {run.requests} "
         f"(HTTP statuses {run.statuses}, no retry layer)")
    _say(f"[{label}] kernel launches {c['kernel_launches']}, fused "
         f"launches {c['fused_launches']} ({c['fused_segments']} "
         f"segments), candidates streamed {c['kernel_cand_streamed']}, "
         f"fast-path selects {c['fast_path_selects']}, solutions "
         f"{sum(r.solutions.shape[0] for r in run.results)}")
    _say(f"[{label}] cold set-up seconds, compilation included, not "
         f"a speed measurement: first query {run.first_query_s}, "
         f"whole served run {run.wall_s}")


def _one_chip(store, queries, oracle):
    config = ServerConfig(page_size=PAGE_SIZE, max_mpr=MAX_MPR,
                          selector_backend="kernel")
    problems, run = smoke.check_served_path(
        store, queries, config, clients=CLIENTS,
        request_budget=REQUEST_BUDGET, oracle=oracle)
    _report("kernel", run, {})
    return problems


def _four_chips(store, queries, oracle, devices):
    mesh = Mesh(np.array(devices[:4]), ("data",))
    config = ServerConfig(page_size=PAGE_SIZE, max_mpr=MAX_MPR,
                          selector_backend="sharded", mesh=mesh,
                          shard_window=SHARD_WINDOW,
                          placement_policy="heat")
    app = app_from_config(store, config)
    front = app.backend
    problems = _shard_problems(front.server.federated, devices[:4])

    async def passes():
        # each pass is checked and reported as soon as it ends
        try:
            static = await smoke.serve_queries(
                app, queries, clients=CLIENTS,
                request_budget=REQUEST_BUDGET)
            label = "sharded, static placement"
            # the sharded fused path is not taken under a heat placement,
            # so neither pass is required to fuse
            found = [f"{label}: {p}" for p in smoke.problems(
                static, oracle, queries, expect_fused=False)]
            _report(label, static, {})
            await front.repartition()
            heat = await smoke.serve_queries(
                app, queries, clients=CLIENTS,
                request_budget=REQUEST_BUDGET)
            label = "sharded, after repartition"
            before = static.metrics["counters"]
            found += [f"{label}: {p}" for p in smoke.problems(
                heat, oracle, queries, expect_fused=False,
                counters_before=before)]
            _report(label, heat, before)
            return found
        finally:
            await app.aclose()

    problems += asyncio.run(passes())
    problems += _shard_problems(front.server.federated, devices[:4])
    return problems


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="data generation seed")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the sharded backend on a 4-chip mesh")
    args = parser.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    use_compile_cache()
    _say(f"device {devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    data = generate(WatDivScale(**SCALE), seed=args.seed)
    gen_s = time.perf_counter() - t0
    terms = len(data.dictionary)
    if terms - 1 > _MAX_ID:
        print(f"chip_smoke: {terms} terms exceed the 21-bit key layout",
              file=sys.stderr)
        return 1
    _say(f"data: {data.num_triples} triples, {terms} terms "
         f"(WatDiv-like, seed {args.seed})")

    workload = generate_workload(data, SELECTION, seed=1)
    queries, oracle, skipped = smoke.within_budget(
        data.store, workload, QUERIES, page_size=PAGE_SIZE,
        max_mpr=MAX_MPR, request_budget=REQUEST_BUDGET)
    if len(queries) < QUERIES:
        print(f"chip_smoke: only {len(queries)} of {SELECTION} queries "
              f"finish within {REQUEST_BUDGET} requests", file=sys.stderr)
        return 1
    _say(f"queries: {' '.join(n for n, _ in queries)} "
         f"({skipped} earlier seed-1 queries passed over: the oracle "
         f"needs more than {REQUEST_BUDGET} requests)")

    if args.chips == 4:
        problems = _four_chips(data.store, queries, oracle, devices)
    else:
        problems = _one_chip(data.store, queries, oracle)
    _say(f"cold set-up seconds: data generation {gen_s}")
    if problems:
        for p in problems:
            print(f"chip_smoke: FAIL {p}", file=sys.stderr)
        return 1
    _say(f"answers identical to the numpy oracle for all {len(queries)} "
         "queries")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
