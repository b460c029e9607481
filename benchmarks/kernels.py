"""Kernel microbenchmarks: Pallas (interpret on CPU) vs jnp oracle.

On this CPU container, interpret-mode timings measure Python dispatch,
not TPU performance -- the derived column therefore also reports the
*work geometry* (compare-grid cells per launch) that the roofline model
uses for the TPU projection in EXPERIMENTS.md.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bindjoin, ops, tpf_match
from repro.kernels import ref

from .common import emit, persist


def _time(fn, *args, reps=5, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(full: bool = False) -> Dict:
    rng = np.random.default_rng(0)
    out: Dict = {}
    shapes = [(4096, 30), (16384, 50)] if not full else [
        (4096, 30), (16384, 50), (65536, 128), (262144, 50)]
    for t, m in shapes:
        cand = jnp.asarray(rng.integers(0, 1000, (t, 3)), jnp.int32)
        pats = jnp.asarray(rng.integers(-1, 1000, (m, 3)), jnp.int32)
        valid = jnp.ones((m,), jnp.int32)

        dt_ref = _time(lambda: jax.block_until_ready(
            bindjoin(cand, pats, valid, use_pallas=False)))
        dt_pal = _time(lambda: jax.block_until_ready(
            bindjoin(cand, pats, valid, use_pallas=True)))
        cells = t * m
        out[(t, m)] = (dt_ref, dt_pal)
        emit(f"kernels/bindjoin_T{t}_M{m}_ref", dt_ref * 1e6,
             f"cells={cells}")
        emit(f"kernels/bindjoin_T{t}_M{m}_pallas_interp", dt_pal * 1e6,
             f"cells={cells}")

        vec = jnp.asarray(ops.pattern_vec_from((3, -1, -1)))
        dt_m = _time(lambda: jax.block_until_ready(
            tpf_match(cand, vec, use_pallas=False)))
        emit(f"kernels/tpf_match_T{t}_ref", dt_m * 1e6, f"rows={t}")

    out["selector"] = run_selector_backends(full=full)
    path = persist("kernels", out)
    print(f"# persisted -> {path}")
    return out


def run_selector_backends(full: bool = False) -> Dict:
    """Selector-backend axis: the server-side brTPF selector evaluated
    by the numpy per-pattern backend loop vs the Pallas bind-join kernel
    path (solo and cross-request-batched grouped launches).

    On CPU the kernel runs in interpret mode, so its wall-clock column
    measures dispatch, not TPU speed; the geometry columns (candidates
    streamed per HBM pass, compare-grid cells, passes saved by batching)
    are the quantities the TPU cost model in ``core/sim.py`` charges.
    """
    import jax
    from jax.sharding import Mesh
    from repro.core.federation import FederatedStore, ShardedSelector
    from repro.core.kernel_selectors import KernelSelector
    from repro.core.rdf import UNBOUND, TriplePattern, encode_var
    from repro.core.selectors import brtpf_select_with_cnt
    from repro.core.store import TripleStore

    rng = np.random.default_rng(7)
    n_triples = 200_000 if full else 20_000
    triples = np.unique(
        rng.integers(0, 500, (n_triples, 3)).astype(np.int32), axis=0)
    store = TripleStore(triples)
    v = encode_var
    out: Dict = {}

    fed = FederatedStore.build(
        store.triples, Mesh(np.array(jax.devices()), ("data",)))

    def full_stream_omega(m, width):
        """Random mappings with one all-UNBOUND row: the base-shaped
        instantiation defeats sub-range pruning, so these rows measure
        the classic full-prefix-range stream (the pre-pruning geometry
        the cost model projects)."""
        om = rng.integers(0, 500, (m, width)).astype(np.int32)
        om[0] = UNBOUND
        return om

    def pruned_omega(m, positions):
        """Mappings sampled from real store rows (so sub-ranges are
        non-empty) binding exactly ``positions`` -> the Omega-restricted
        pruned stream."""
        picks = store.triples[rng.integers(0, len(store), (m,))]
        width = max(positions) + 1
        om = np.full((m, width), UNBOUND, np.int32)
        for var, pos in enumerate(positions):
            om[:, var] = picks[:, pos]
        return om

    cases = [
        ("bound_p", TriplePattern(v(0), 7, v(1)),
         full_stream_omega(30, 2)),
        ("wildcard", TriplePattern(v(0), v(1), v(2)),
         full_stream_omega(30, 3)),
        ("bound_p_small_omega", TriplePattern(v(0), 7, v(1)),
         full_stream_omega(5, 2)),
        # Omega-restricted pruning rows (docs/pruning.md): identical
        # patterns, mappings that instantiate more-bound shapes -- the
        # candidate stream shrinks to the sub-range union
        ("bound_p_pruned", TriplePattern(v(0), 7, v(1)),
         pruned_omega(30, (0, 2))),
        ("wildcard_pruned", TriplePattern(v(0), v(1), v(2)),
         pruned_omega(30, (0, 1))),
    ]
    for name, tp, omega in cases:
        omegas = [omega] + [
            np.stack([rng.integers(0, 500, (omega.shape[1],))
                      .astype(np.int32)
                      for _ in range(omega.shape[0])])
            for _ in range(7)
        ]
        sel = KernelSelector(store)

        dt_np = _time(lambda tp=tp, omega=omega:
                      brtpf_select_with_cnt(store, tp, omega))
        dt_k = _time(lambda tp=tp, omega=omega:
                     sel.select_with_cnt(tp, omega))
        sel.launches.clear()
        dt_b = _time(lambda tp=tp, omegas=omegas:
                     sel.select_same_pattern(tp, omegas))
        rec = sel.launches[-1] if sel.launches else None
        out[name] = (dt_np, dt_k, dt_b, rec)
        emit(f"kernels/selector_{name}_numpy", dt_np * 1e6,
             f"per_request")
        if rec is None:
            emit(f"kernels/selector_{name}_kernel_interp", dt_k * 1e6,
                 "cand=0;pruned_to_empty")
            continue
        solo_cells = rec.cand_streamed * (rec.pat_slots
                                          // max(rec.groups, 1))
        emit(f"kernels/selector_{name}_kernel_interp", dt_k * 1e6,
             f"cand={rec.cand_streamed};cells={solo_cells};"
             f"pruned={int(rec.pruned)};cand_full={rec.cand_full}")
        emit(f"kernels/selector_{name}_kernel_batch{len(omegas)}",
             dt_b * 1e6 / len(omegas),
             f"per_request;cand_shared={rec.cand_streamed};"
             f"cells={rec.cells};hbm_passes_saved={rec.groups - 1}")

        # sharded windowed backend: same selection, per-shard window
        # launches -- per-launch streaming is the window, not the range
        ssel = ShardedSelector(fed, window=2048)
        dt_s = _time(lambda tp=tp, omega=omega:
                     ssel.select_with_cnt(tp, omega), reps=2)
        ssel.launches.clear()
        ssel.select_with_cnt(tp, omega)  # launch count of ONE select
        n_launch = len(ssel.launches)
        per_launch = ssel.launches[-1] if ssel.launches else None
        out[name + "_sharded"] = (dt_s, n_launch, per_launch)
        window_rows = per_launch.cand_streamed if per_launch else 0
        emit(f"kernels/selector_{name}_sharded_interp", dt_s * 1e6,
             f"window={window_rows};"
             f"launches={n_launch};shards={fed.shards};"
             f"cand_total={window_rows * n_launch}")
    return out


if __name__ == "__main__":
    import sys

    from .common import use_compile_cache
    use_compile_cache()
    run(full="--full" in sys.argv)
