"""Public wrappers around the Pallas kernels.

Handles padding to tile multiples, the choice between compiling a
kernel for the TPU and running it in Pallas interpret mode on the CPU
(``_use_interpret``), and the jnp-side epilogues (mask -> compacted
indices). The wrappers are meant to be traced inside their caller's
program (the selectors' launch programs, the sharded path's
``shard_map`` steps): called eagerly, each of their ``jnp`` ops would
be a host dispatch and a device program of its own.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bindjoin import (DEFAULT_BM, DEFAULT_BT, DEFAULT_FUSED_BT,
                       bindjoin_fused_pallas, bindjoin_grouped_pallas,
                       bindjoin_pallas)
from .tpf_match import DEFAULT_BR, LANES, tpf_match_pallas


def _use_interpret() -> bool:
    """Interpret mode on the CPU (tests, rehearsals), compiled kernels on
    the TPU. Any other platform is an error: a silent fallback to the
    interpreter would let a run that lost its accelerator pass."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (or 'cpu' in interpret mode), "
        f"not on {backend!r}")


def _kernel_dtypes():
    """Trace a kernel with 64-bit types off. The kernels are int32
    throughout, but the sharded path calls them under ``enable_x64`` (its
    packed keys are int64), where index arithmetic would promote to
    int64, which the TPU kernel compiler does not lower."""
    return jax.enable_x64(False)


def _pad_to(x: jnp.ndarray, mult: int, fill) -> jnp.ndarray:
    n = x.shape[0]
    rem = (-n) % mult
    if rem == 0 and n > 0:
        return x
    pad = max(rem, mult if n == 0 else rem)
    return jnp.concatenate(
        [x, jnp.full((pad,), fill, dtype=x.dtype)], axis=0)


def bindjoin(cand: jnp.ndarray, patterns: jnp.ndarray,
             pat_valid: jnp.ndarray, *, bt: int = DEFAULT_BT,
             bm: int = DEFAULT_BM,
             use_pallas: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bind-join filter over candidate triples.

    Args:
      cand: int32 [T, 3] candidate data triples.
      patterns: int32 [M, 3] instantiated patterns (component < 0 = wild).
      pat_valid: int32 [M] (0 marks padding rows).

    Returns:
      keep: bool [T]  -- triple joins with >= 1 attached mapping.
      idx:  int32 [T] -- first matching pattern index (= padded M if none).
    """
    t = cand.shape[0]
    cs = _pad_to(cand[:, 0], bt, 0)
    cp = _pad_to(cand[:, 1], bt, 0)
    co = _pad_to(cand[:, 2], bt, 0)
    ps = _pad_to(patterns[:, 0], bm, 0)
    pp = _pad_to(patterns[:, 1], bm, 0)
    po = _pad_to(patterns[:, 2], bm, 0)
    pv = _pad_to(pat_valid.astype(jnp.int32), bm, 0)
    if use_pallas:
        with _kernel_dtypes():
            keep, idx = bindjoin_pallas(cs, cp, co, ps, pp, po, pv,
                                        bt=bt, bm=bm,
                                        interpret=_use_interpret())
    else:
        keep, idx = ref.bindjoin_ref(cs, cp, co, ps, pp, po, pv)
        keep = keep.astype(jnp.int32)
    return keep[:t].astype(bool), idx[:t]


def padded_pattern_slots(m: int, bm: int = DEFAULT_BM) -> int:
    """Per-group pattern-slot count after padding to the m-tile size --
    the single source of truth for the launch geometry that
    ``bindjoin_grouped`` uses and the selector/sim cost models charge."""
    return max(m + (-m) % bm, bm)


def bindjoin_grouped(cand: jnp.ndarray, patterns: jnp.ndarray,
                     pat_valid: jnp.ndarray, *, bt: int = DEFAULT_BT,
                     bm: int = DEFAULT_BM, use_pallas: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Grouped bind-join filter: G pattern sets, one candidate pass.

    Args:
      cand: int32 [T, 3] candidate data triples (shared by all groups).
      patterns: int32 [G, M, 3] per-group instantiated patterns
        (component < 0 = wild).
      pat_valid: int32 [G, M] (0 marks padding rows).

    Returns:
      keep:   bool  [T, G] -- triple joins with >= 1 of group g's patterns.
      idx:    int32 [T, G] -- first matching within-group pattern index
        (= padded M if none).
      nmatch: int32 [T, G] -- matching-pattern count (cnt contribution).
    """
    t = cand.shape[0]
    g, m = patterns.shape[0], patterns.shape[1]
    cs = _pad_to(cand[:, 0], bt, 0)
    cp = _pad_to(cand[:, 1], bt, 0)
    co = _pad_to(cand[:, 2], bt, 0)
    mp = padded_pattern_slots(m, bm)

    def pad_flat(x, fill):
        out = jnp.full((g, mp), fill, dtype=x.dtype)
        return out.at[:, :m].set(x).reshape(g * mp)

    ps = pad_flat(patterns[:, :, 0], 0)
    pp = pad_flat(patterns[:, :, 1], 0)
    po = pad_flat(patterns[:, :, 2], 0)
    pv = pad_flat(pat_valid.astype(jnp.int32), 0)
    if use_pallas:
        with _kernel_dtypes():
            keep, idx, nmatch = bindjoin_grouped_pallas(
                cs, cp, co, ps, pp, po, pv, groups=g, bt=bt, bm=bm,
                interpret=_use_interpret())
    else:
        keep, idx, nmatch = ref.bindjoin_grouped_ref(
            cs, cp, co, ps.reshape(g, mp), pp.reshape(g, mp),
            po.reshape(g, mp), pv.reshape(g, mp))
        keep = keep.astype(jnp.int32)
    return keep[:t].astype(bool), idx[:t], nmatch[:t]


def bindjoin_fused(cand: jnp.ndarray, seg_of_tile: jnp.ndarray,
                   patterns: jnp.ndarray, pat_valid: jnp.ndarray, *,
                   bt: int = DEFAULT_FUSED_BT, bm: int = DEFAULT_BM,
                   use_pallas: bool = True
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cross-pattern fused bind-join: S segments share one candidate pass.

    Args:
      cand: int32 [T, 3] concatenated candidate stream, T % bt == 0;
        each bt-tile's rows belong to one segment (callers tile-align
        every segment's block -- ``kernel_selectors.select_fused``).
      seg_of_tile: int32 [T // bt] per-tile segment id (-1 = dead tile).
      patterns: int32 [S, G, M, 3] per-segment per-group instantiated
        patterns (component < 0 = wild).
      pat_valid: int32 [S, G, M] (0 marks padding rows).

    Returns:
      keep:   bool  [T, G] -- row matches its own segment's group g.
      idx:    int32 [T, G] -- first matching within-group pattern index
        (= padded M if none).
      nmatch: int32 [T, G] -- matching-pattern count (cnt contribution).
    """
    t = cand.shape[0]
    s, g, m = patterns.shape[0], patterns.shape[1], patterns.shape[2]
    assert t % bt == 0, (t, bt)
    mp = padded_pattern_slots(m, bm)

    def pad_flat(x, fill):
        out = jnp.full((s, g, mp), fill, dtype=x.dtype)
        return out.at[:, :, :m].set(x).reshape(s * g * mp)

    ps = pad_flat(patterns[:, :, :, 0], 0)
    pp = pad_flat(patterns[:, :, :, 1], 0)
    po = pad_flat(patterns[:, :, :, 2], 0)
    pv = pad_flat(pat_valid.astype(jnp.int32), 0)
    if use_pallas:
        with _kernel_dtypes():
            keep, idx, nmatch = bindjoin_fused_pallas(
                seg_of_tile.astype(jnp.int32), cand[:, 0], cand[:, 1],
                cand[:, 2], ps, pp, po, pv, segments=s, groups=g, bt=bt,
                bm=bm, interpret=_use_interpret())
    else:
        seg_of_row = jnp.repeat(seg_of_tile.astype(jnp.int32), bt)
        keep, idx, nmatch = ref.bindjoin_fused_ref(
            cand[:, 0], cand[:, 1], cand[:, 2], seg_of_row,
            ps.reshape(s, g, mp), pp.reshape(s, g, mp),
            po.reshape(s, g, mp), pv.reshape(s, g, mp))
        keep = keep.astype(jnp.int32)
    return keep.astype(bool), idx, nmatch


def tpf_match(cand: jnp.ndarray, pattern_vec: jnp.ndarray, *,
              br: int = DEFAULT_BR,
              use_pallas: bool = True) -> jnp.ndarray:
    """Single-pattern match mask over candidate triples.

    Args:
      cand: int32 [T, 3]; pattern_vec: int32 [8]
        = [s, p, o, eq_sp, eq_so, eq_po, 0, 0], components < 0 wild.
    Returns: bool [T].
    """
    t = cand.shape[0]
    tile = br * LANES
    cs = _pad_to(cand[:, 0], tile, -1)
    cp = _pad_to(cand[:, 1], tile, -2)   # s != p for padding rows ->
    co = _pad_to(cand[:, 2], tile, -3)   # eq_* constraints reject them
    if use_pallas:
        with _kernel_dtypes():
            mask = tpf_match_pallas(cs, cp, co, pattern_vec, br=br,
                                    interpret=_use_interpret())
    else:
        mask = ref.tpf_match_ref(cs, cp, co, pattern_vec).astype(jnp.int32)
    return mask[:t].astype(bool)


@functools.partial(jax.jit, static_argnames=("capacity",))
def compact_mask(mask: jnp.ndarray, capacity: int):
    """Turn a bool mask into (indices[capacity], count) with -1 padding --
    the fixed-shape 'page' epilogue used by the federation path."""
    count = jnp.sum(mask.astype(jnp.int32))
    order = jnp.argsort(~mask, stable=True)        # True rows first
    n = order.shape[0]
    if n < capacity:
        order = jnp.concatenate(
            [order, jnp.full((capacity - n,), -1, order.dtype)])
    idx = order[:capacity]
    valid = jnp.arange(capacity) < count
    return jnp.where(valid, idx, -1), count


def pattern_vec_from(tp_tuple, eq_sp=0, eq_so=0, eq_po=0) -> np.ndarray:
    """Host helper: build the int32[8] pattern vector for tpf_match."""
    s, p, o = tp_tuple
    return np.array([s, p, o, eq_sp, eq_so, eq_po, 0, 0], dtype=np.int32)
