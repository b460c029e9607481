"""Shared benchmark fixtures: dataset, workload, engine runners.

Scales default small enough for one CPU core; pass ``--full`` to
``benchmarks.run`` (or use the env var ``REPRO_BENCH_FULL=1``) for the
EXPERIMENTS.md configuration.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Dict, Optional


from repro.core import (BrTPFClient, BrTPFServer, LRUCache, ServerConfig,
                        TPFClient)
from repro.data.watdiv import (WatDivData, WatDivScale, generate,
                               generate_workload)

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class BenchConfig:
    scale: WatDivScale
    num_queries: int
    request_budget: int
    seed: int = 0

    @classmethod
    def default(cls) -> "BenchConfig":
        if FULL:
            # ~0.5M triples, the paper's 145-query selection
            return cls(WatDivScale(users=20000, products=8000,
                                   reviews=30000, retailers=100,
                                   genres=60, cities=120, tags=300),
                       num_queries=145, request_budget=100_000)
        # ~25K triples, 48 queries: CI-friendly
        return cls(WatDivScale(users=1500, products=600, reviews=2500,
                               retailers=24, genres=30, cities=40,
                               tags=80),
                   num_queries=48, request_budget=15_000)


@functools.lru_cache(maxsize=2)
def dataset(seed: int = 0, full: Optional[bool] = None) -> WatDivData:
    cfg = BenchConfig.default()
    return generate(cfg.scale, seed=cfg.seed + seed)


@functools.lru_cache(maxsize=4)
def workload(seed: int = 1):
    cfg = BenchConfig.default()
    return tuple(generate_workload(dataset(), cfg.num_queries, seed=seed))


# Small-work fast path for the accelerated benchmark servers: below
# this many (post-pruning) candidate rows the selector routes to the
# numpy block evaluation instead of a kernel/window launch
# (BENCH_kernels.json shows the interpret-mode kernel losing to numpy
# outright at small work; on TPU the dispatch overhead dominates there).
FAST_PATH_ROWS = 256


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at one fixed path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``
    (listed in ``.gitignore``): the directory is part of the cache key, so
    a path that moved from run to run would never hit.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(REPO_ROOT, ".jax_cache"))


def make_server(page_size: int = 100, max_mpr: int = 30,
                cache: Optional[LRUCache] = None,
                selector_backend: str = "numpy",
                shard_window: Optional[int] = None,
                fast_path_rows: int = FAST_PATH_ROWS,
                fuse_patterns: bool = True) -> BrTPFServer:
    config = ServerConfig(page_size=page_size, max_mpr=max_mpr,
                          selector_backend=selector_backend,
                          shard_window=shard_window,
                          fast_path_rows=fast_path_rows,
                          fuse_patterns=fuse_patterns)
    return BrTPFServer(dataset().store, config, cache=cache)


def run_sequence(client_kind: str, page_size: int = 100,
                 max_mpr: int = 30, cache: Optional[LRUCache] = None,
                 per_query: bool = False):
    """Execute the workload; returns (server, per-query results list)."""
    cfg = BenchConfig.default()
    server = make_server(page_size, max_mpr, cache)
    results = []
    for name, bgp in workload():
        if client_kind == "tpf":
            client = TPFClient(server, request_budget=cfg.request_budget)
        else:
            client = BrTPFClient(server, max_mpr=max_mpr,
                                 request_budget=cfg.request_budget)
        res = client.execute(bgp)
        results.append((name, res))
    return server, results


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0)


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def _jsonable(obj):
    """Best-effort conversion of benchmark results to JSON values."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)
                # per-query latency lists blow up the tracked file
                if f.name != "qets"}
    if isinstance(obj, dict):
        return {k if isinstance(k, str) else repr(k): _jsonable(v)
                for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item"):          # numpy scalar
        return obj.item()
    if isinstance(obj, float):
        return round(obj, 6)
    return obj


def pr_id() -> str:
    """Identifier for the current PR in the benchmark trajectory:
    ``REPRO_PR`` if set, else the repo's commit count (each PR is one
    commit in this repo's history), else 'unversioned'."""
    env = os.environ.get("REPRO_PR")
    if env:
        return env
    try:
        import subprocess
        count = subprocess.run(
            ["git", "rev-list", "--count", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10)
        if count.returncode == 0 and count.stdout.strip():
            return f"r{count.stdout.strip()}"
    except Exception:
        pass
    return "unversioned"


def persist(kind: str, results: Dict,
            headline: Optional[Dict] = None,
            section: Optional[str] = None) -> str:
    """Write results to ``BENCH_<kind>.json`` at the repo root.

    The file is committed per PR, so the current snapshot is diffable
    across the PR history; ``headline`` additionally APPENDS one
    trajectory entry (PR id + headline metrics) to the file's
    ``trajectory`` list, so the perf history (req/s,
    launches-per-request, candidates-streamed, ...) reads as a series
    instead of a single overwritten snapshot. Multiple benchmarks share
    one trajectory file (throughput + the latency load generator): a
    same-PR entry is MERGED key-wise, never replaced, so whichever runs
    second adds its metrics alongside the first's.

    ``section`` scopes the results write: instead of replacing the whole
    ``results`` payload, only ``results[section]`` is replaced (the
    latency run must not wipe the throughput snapshot it shares a file
    with).
    """
    path = os.path.join(REPO_ROOT, f"BENCH_{kind}.json")
    trajectory = []
    existing_results: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                existing = json.load(fh)
            trajectory = existing.get("trajectory", [])
            existing_results = existing.get("results", {})
        except Exception:
            trajectory = []
    if headline is not None:
        entry = {"pr": pr_id(), **_jsonable(headline)}
        # one merged entry per PR id: a re-run within a PR updates its
        # own keys in place and keeps sibling benchmarks' keys
        for prev in trajectory:
            if prev.get("pr") == entry["pr"]:
                entry = {**prev, **entry}
        trajectory = [e for e in trajectory if e.get("pr") != entry["pr"]]
        trajectory.append(entry)
    if section is not None:
        if not isinstance(existing_results, dict):
            existing_results = {}
        existing_results[section] = _jsonable(results)
        results_payload = existing_results
    else:
        results_payload = _jsonable(results)
    payload = {
        "config": _jsonable(dataclasses.asdict(BenchConfig.default())),
        "full": FULL,
        "results": results_payload,
    }
    if trajectory:
        payload["trajectory"] = trajectory
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path
