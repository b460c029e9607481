"""Workload-skew placement A/B: heat-based boundaries vs equal split.

The tentpole claim of docs/federation.md ("Placement"): under a
Zipf-skewed request mix, the legacy equal contiguous split concentrates
nearly every window launch on the shard that happens to own the hot key
band, while heat-based boundaries (plus hot-range replication) spread
the same traffic across the mesh. This module measures that claim on a
synthetic hot-band dataset:

1. build a sharded server (``placement_policy="heat"``) over a store
   whose subjects are contiguous in the SPO key space;
2. pass A: replay 16 Zipf-skewed brTPF request streams through the
   async front end against the *equal* split and snapshot the
   per-shard balance (``metrics_snapshot()["shards"]``);
3. ``server.repartition()`` -- cut new boundaries from the heat log
   recorded during pass A (and replicate the hottest sub-range);
4. pass B: replay the same streams against the placed store and
   snapshot the balance again;
5. assert fragment byte-parity: a sample of requests is answered by the
   numpy oracle, the kernel backend and the repartitioned sharded
   backend, and all three must return identical pages.

Run it as its own command, ``python -m benchmarks.skew``: it gates
``skew_c16:imbalance_uniform`` / ``imbalance_heat`` / ``imbalance_drop``
(``budgets.json``) on the row it computes and persists that row to
``BENCH_throughput.json``. The last stdout line is the row as one JSON
object (:func:`repro.core.metrics.rebalance_report` plus run metadata).
The mesh is every device JAX finds: the chips of a TPU host, or, with
``JAX_PLATFORMS=cpu``, four virtual CPU devices (the A/B is meaningless
on a one-device mesh).
"""
from __future__ import annotations

import os

# The host-platform device count is fixed when jax initializes, so this
# runs before the first jax import (transitively, via repro.core). It
# touches only the CPU platform; an externally set count wins.
if (__name__ == "__main__"
        and os.environ.get("JAX_PLATFORMS") == "cpu"
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + os.environ.get("XLA_FLAGS", ""))

import json
import sys
from typing import Dict, List, Tuple

import numpy as np

from repro.core import LRUCache, ServerConfig  # noqa: F401  (jax init)
from repro.core.batching import serve_concurrent
from repro.core.metrics import rebalance_report
from repro.core.rdf import UNBOUND, TriplePattern, encode_var
from repro.core.server import BrTPFServer, Request
from repro.core.store import TripleStore

# Dataset geometry: subjects are contiguous blocks in the SPO key space,
# so "hot subjects" == "hot key band" and the equal split's imbalance is
# structural, not accidental.
N_SUBJECTS = 512
N_PREDICATES = 16
TRIPLES_PER_SUBJECT = 96          # 6 objects per (subject, predicate)
SUBJ_BASE = 1_000
PRED_BASE = 1
OBJ_BASE = 100_000

N_STREAMS = 16
REQUESTS_PER_CLIENT = 48
# Zipf exponent 2.0: the top subject alone draws ~60% of the traffic,
# which no boundary cut can split -- so the A/B exercises BOTH placement
# mechanisms (weighted boundaries for the splittable tail, hot-range
# replication + routed dedup for the un-splittable head).
ZIPF_A = 2.0

SHARD_WINDOW = 64


def build_triples() -> np.ndarray:
    """Synthetic hot-band dataset: unique (s, p, o) rows, subjects (and
    their per-predicate blocks) contiguous under the SPO sort."""
    s = np.repeat(np.arange(N_SUBJECTS), TRIPLES_PER_SUBJECT) + SUBJ_BASE
    j = np.tile(np.arange(TRIPLES_PER_SUBJECT), N_SUBJECTS)
    p = (j % N_PREDICATES) + PRED_BASE
    o = np.arange(s.size) + OBJ_BASE    # unique per row
    return np.stack([s, p, o], axis=1).astype(np.int32)


def build_streams(seed: int = 0) -> List[List[Request]]:
    """16 Zipf-skewed brTPF streams. Each request restricts the pattern
    ``(subject, ?p, ?o)`` with a 2-mapping Omega binding ``?p`` -- the
    mapping pair varies per request, so repeats of a hot subject are
    distinct fragments (they launch instead of riding the memo) exactly
    like distinct downstream join states would be in a real bind-join."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, N_SUBJECTS + 1, dtype=np.float64)
    weights = ranks ** -ZIPF_A
    weights /= weights.sum()
    streams: List[List[Request]] = []
    for _ in range(N_STREAMS):
        reqs: List[Request] = []
        for _ in range(REQUESTS_PER_CLIENT):
            subj = int(rng.choice(N_SUBJECTS, p=weights)) + SUBJ_BASE
            preds = rng.choice(N_PREDICATES, size=2, replace=False)
            omega = np.asarray(
                [[int(p) + PRED_BASE, UNBOUND] for p in preds],
                dtype=np.int32)
            tp = TriplePattern(subj, encode_var(0), encode_var(1))
            reqs.append(Request(tp, omega, page=0))
        streams.append(reqs)
    return streams


def _replay(server: BrTPFServer,
            streams: List[List[Request]]) -> Dict:
    """Replay the streams through the real async front end (immediate
    dispatch: the balance measurement wants one launch plan per request
    on both sides of the A/B) and return the per-shard balance."""
    serve_concurrent(server, streams, batch_window_s=0.0)
    return server.metrics_snapshot()["shards"]


def _parity_sample(streams: List[List[Request]],
                   rng: np.random.Generator,
                   k: int = 12) -> List[Request]:
    flat = [r for s in streams for r in s]
    idx = rng.choice(len(flat), size=min(k, len(flat)), replace=False)
    return [flat[i] for i in idx]


def check_parity(store: TripleStore, sharded: BrTPFServer,
                 sample: List[Request]) -> Tuple[bool, int]:
    """Every sampled request must come back byte-identical from the
    numpy oracle, the kernel backend, and the (repartitioned, replica-
    holding) sharded backend."""
    oracle = BrTPFServer(store, ServerConfig(selector_backend="numpy"))
    kernel = BrTPFServer(store, ServerConfig(selector_backend="kernel"))
    mismatches = 0
    for req in sample:
        frags = [srv.handle(req) for srv in (oracle, kernel, sharded)]
        base = frags[0]
        for frag in frags[1:]:
            if (not np.array_equal(np.asarray(base.data),
                                   np.asarray(frag.data))
                    or base.cnt != frag.cnt
                    or base.has_next != frag.has_next):
                mismatches += 1
    return mismatches == 0, mismatches


def run(seed: int = 0) -> Dict:
    triples = build_triples()
    store = TripleStore(triples)
    streams = build_streams(seed)

    config = ServerConfig(selector_backend="sharded",
                          shard_window=SHARD_WINDOW,
                          placement_policy="heat")
    server = BrTPFServer(store, config)
    shards = server.federated.shards

    uniform = _replay(server, streams)       # pass A: equal split
    server.repartition()                     # heat -> boundaries + replicas
    server.reset_counters()
    heat = _replay(server, streams)          # pass B: placed store

    placement = server.federated.placement
    n_replicas = sum(len(v) for v in placement.replicas.values())
    parity_ok, mismatches = check_parity(
        store, server, _parity_sample(streams, np.random.default_rng(seed)))

    row = rebalance_report(uniform, heat)
    row.update({
        "shards": shards,
        "requests": N_STREAMS * REQUESTS_PER_CLIENT,
        "replica_ranges": n_replicas,
        "parity_ok": parity_ok,
        "parity_mismatches": mismatches,
    })
    return row


def main(argv=None) -> int:
    import argparse

    from .common import persist, use_compile_cache
    from .throughput import check_budgets
    parser = argparse.ArgumentParser(
        description="placement A/B under Zipf-skewed load")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    use_compile_cache()
    row = run(seed=args.seed)
    for k, v in row.items():
        if not isinstance(v, list):
            print(f"# skew/{k} = {v}", file=sys.stderr)
    failures = check_budgets({("skew", 16): row})
    persist("throughput", {("skew", 16): row}, section="skew", headline={
        f"skew_c16_{k}": row[k]
        for k in ("imbalance_uniform", "imbalance_heat", "imbalance_drop")})
    print(json.dumps(row))
    return 0 if row["parity_ok"] and not failures else 1


if __name__ == "__main__":
    raise SystemExit(main())
