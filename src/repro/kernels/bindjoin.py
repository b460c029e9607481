"""Pallas TPU kernel: the server-side bind-join filter.

This is the compute hot-spot of a brTPF server: for every candidate
triple in the fragment's prefix range, decide whether it matches at least
one of the (instantiated, deduped) patterns derived from the attached
solution mappings -- an OR-reduction over an outer-product compare grid.

TPU adaptation (vs. the paper's per-pattern HDT lookups): the Java
servlet loops over the instantiated patterns and queries the backend per
pattern. On TPU we invert the loop: stream candidate triples through VMEM
once and compare each tile against *all* patterns resident in VMEM --
one HBM pass over the candidates instead of M passes, and the (BT x BM)
compare grid maps onto the VPU's (8 x 128) vector lanes.

Tiling of the single-pattern kernel:
  grid = (ceil(T / BT), ceil(M / BM));  m is the inner (reduction) axis.
  candidate components: three (BT, 1)-blocks replicated across the m axis
  pattern components:   three (1, BM)-blocks replicated across the t axis
  outputs keep/idx:     (BT, 1)-blocks accumulated across m steps
    (output revisiting across the inner grid axis is the standard Pallas
     reduction idiom: initialize at m == 0, combine otherwise).

VMEM per step at (BT, BM) = (1024, 128): compare grid 1024*128*4 B
= 512 KiB for the int32 index grid plus 3 * 4 KiB pattern/candidate
vectors -- comfortably inside the ~16 MiB VMEM budget, and the minor
dimension is a full 128-lane multiple.

The *grouped* variant serves the server's cross-request batching: G
concurrent brTPF requests for the same triple pattern share one HBM pass
over the (identical) candidate range. It is laid out for streams of
millions of rows (an unbound TPF probe streams its whole range):

  candidates: lane-dense (BT/128, 128) tiles, one per leading-axis slot
    of a (T/BT, BT/128, 128) array -- 4 B per row in HBM;
  patterns:   a (4, BM) block of the (s, p, o, valid) slot table in
    SMEM; the body reads each slot as four scalars and compares it
    against the whole tile, so keep/first/count reduce elementwise;
  outputs:    (G, T/BT, BT/128, 128) arrays, one tile-shaped block per
    (group, tile), accumulated across that group's m-tiles.

The G pattern sets are padded to a common Mp and laid out side by side
on the m axis, so the m-tile -> group mapping is static (tiles_per_group
= Mp // BM), and the per-row match *count* output gives each request its
Definition-2 ``cnt`` estimate from the same launch. The *fused* variant
has the same layout; its per-tile segment ids ride in scalar prefetch
and select each tile's slot block in the block ``index_map``.

The kernel is agnostic to what the candidate block contains and in what
order: since the Omega-restricted pruning PR (docs/pruning.md) callers
stream the merged union of per-binding sub-ranges -- a subset of the
prefix range in mixed physical order -- whenever the attached mappings
allow it. Everything here only requires that each candidate triple
appear exactly once (the hosts' span-merge/dedup contract); the
first-match/ordering semantics are restored by the host epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 1024
LANES = 128          # TPU vector lanes (minor dim of a vreg)
DEFAULT_BM = 128

# Fused launches tile the candidate stream finer than the same-pattern
# grouped kernel: each segment's block is tile-aligned independently, so
# a smaller tile bounds the per-segment alignment waste.
DEFAULT_FUSED_BT = 256


def _bindjoin_kernel(cs_ref, cp_ref, co_ref, ps_ref, pp_ref, po_ref,
                     pv_ref, keep_ref, idx_ref, *, bm: int, m_total: int):
    m_step = pl.program_id(1)

    cs = cs_ref[...]          # (BT, 1) int32
    cp = cp_ref[...]
    co = co_ref[...]
    ps = ps_ref[...]          # (1, BM) int32
    pp = pp_ref[...]
    po = po_ref[...]
    pv = pv_ref[...]          # (1, BM) int32 validity

    comp = (
        ((ps < 0) | (cs == ps))
        & ((pp < 0) | (cp == pp))
        & ((po < 0) | (co == po))
        & (pv != 0)
    )                          # (BT, BM) bool

    any_m = jnp.any(comp, axis=1, keepdims=True)              # (BT, 1)
    # Global pattern index of each column in this m-tile.
    col = jax.lax.broadcasted_iota(jnp.int32, comp.shape, 1)
    col = col + m_step * bm
    big = jnp.int32(m_total)
    first = jnp.min(jnp.where(comp, col, big), axis=1,
                    keepdims=True).astype(jnp.int32)          # (BT, 1)

    @pl.when(m_step == 0)
    def _init():
        keep_ref[...] = any_m.astype(jnp.int32)
        idx_ref[...] = first

    @pl.when(m_step != 0)
    def _accum():
        keep_ref[...] = jnp.maximum(keep_ref[...], any_m.astype(jnp.int32))
        idx_ref[...] = jnp.minimum(idx_ref[...], first)


@functools.partial(jax.jit, static_argnames=("bt", "bm", "interpret"))
def bindjoin_pallas(cand_s, cand_p, cand_o, pat_s, pat_p, pat_o, pat_valid,
                    *, bt: int = DEFAULT_BT, bm: int = DEFAULT_BM,
                    interpret: bool = False):
    """Tiled bind-join filter. Inputs must be padded: T % bt == 0 and
    M % bm == 0 (``ops.bindjoin`` handles padding). Returns
    (keep int32[T], idx int32[T]) with idx == M_padded when no match."""
    t = cand_s.shape[0]
    m = pat_s.shape[0]
    assert t % bt == 0 and m % bm == 0, (t, m, bt, bm)

    cand2 = lambda x: x.reshape(t, 1)
    pat2 = lambda x: x.reshape(1, m)

    grid = (t // bt, m // bm)
    kernel = functools.partial(_bindjoin_kernel, bm=bm, m_total=m)
    keep, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),   # cand s
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),   # cand p
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),   # cand o
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),   # pat s
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),   # pat p
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),   # pat o
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),   # pat valid
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, 1), jnp.int32),
            jax.ShapeDtypeStruct((t, 1), jnp.int32),
        ],
        interpret=interpret,
    )(cand2(cand_s), cand2(cand_p), cand2(cand_o),
      pat2(pat_s), pat2(pat_p), pat2(pat_o), pat2(pat_valid))
    return keep.reshape(t), idx.reshape(t)


def _to_tiles(x, sub: int, lanes: int):
    """[T] -> [T // (sub * lanes), sub, lanes]: one candidate tile per
    leading-axis slot.

    A tile is stored lane-dense -- rows fill the 128 lanes first -- so a
    candidate stream costs 4 B per row in HBM; a ``(T, 1)`` column would
    be padded to 128 lanes (512 B per row), and a 2**22-row stream would
    no longer fit the chip. Tiles narrower than a lane row (``bt < 128``,
    the sharded path's small windows) keep one partial row. The block's
    last two dims equal the array's, which the TPU lowering accepts for
    any tile size.
    """
    return x.reshape(-1, sub, lanes)


def _from_tiles(x, t: int):
    """[G, T // bt, sublanes, lanes] kernel output -> [T, G]."""
    return x.reshape(x.shape[0], t).T


def _match_tile(cs, cp, co, pat_ref, *, bm: int, col0, big, live):
    """First-match index and match count of every row of one candidate
    tile against the ``bm`` pattern slots in ``pat_ref``.

    ``pat_ref`` is the (4, bm) SMEM block of the slot table (rows s, p,
    o, valid); each slot is read as four scalars and compared against
    the whole tile, so the reductions over patterns stay elementwise.
    ``first`` holds ``big`` where no slot matched; ``live`` (a scalar)
    masks out a dead tile. The loop runs on int32 carries: the chip's
    compiler does not carry boolean vectors through a loop, and keep is
    just ``cnt > 0``.
    """
    def body(m, carry):
        first, cnt = carry
        s, p, o, v = (pat_ref[0, m], pat_ref[1, m], pat_ref[2, m],
                      pat_ref[3, m])
        hit = (((s < 0) | (cs == s))
               & ((p < 0) | (cp == p))
               & ((o < 0) | (co == o))
               & ((v != 0) & live))
        first = jnp.where(hit & (first == big), col0 + m, first)
        return first, cnt + hit.astype(jnp.int32)

    init = (jnp.full(cs.shape, big, jnp.int32),
            jnp.zeros(cs.shape, jnp.int32))
    return jax.lax.fori_loop(0, bm, body, init)


def _accumulate(keep_ref, idx_ref, nmatch_ref, first, cnt, m_step):
    """Fold one m-tile's results into the group's output block (the
    standard Pallas reduction idiom: initialize at the group's first
    m-tile, combine on the later ones)."""
    keep = (cnt > 0).astype(jnp.int32)

    @pl.when(m_step == 0)
    def _init():
        keep_ref[...] = keep
        idx_ref[...] = first
        nmatch_ref[...] = cnt

    @pl.when(m_step != 0)
    def _accum():
        keep_ref[...] = jnp.maximum(keep_ref[...], keep)
        idx_ref[...] = jnp.minimum(idx_ref[...], first)
        nmatch_ref[...] = nmatch_ref[...] + cnt


def _bindjoin_grouped_kernel(cs_ref, cp_ref, co_ref, pat_ref, keep_ref,
                             idx_ref, nmatch_ref, *, bm: int,
                             m_per_group: int):
    tiles_per_group = m_per_group // bm
    m_step = pl.program_id(1) % tiles_per_group   # m-tile within the group
    first, cnt = _match_tile(cs_ref[...], cp_ref[...], co_ref[...], pat_ref,
                             bm=bm, col0=m_step * bm,
                             big=jnp.int32(m_per_group), live=True)
    _accumulate(keep_ref, idx_ref, nmatch_ref, first, cnt, m_step)


@functools.partial(jax.jit,
                   static_argnames=("groups", "bt", "bm", "interpret"))
def bindjoin_grouped_pallas(cand_s, cand_p, cand_o, pat_s, pat_p, pat_o,
                            pat_valid, *, groups: int,
                            bt: int = DEFAULT_BT, bm: int = DEFAULT_BM,
                            interpret: bool = False):
    """Grouped bind-join filter: one candidate pass, G pattern sets.

    Pattern inputs are flat ``int32 [G * Mp]`` with ``Mp`` (= per-group
    padded pattern count) a multiple of ``bm``; candidates ``int32 [T]``
    with ``T`` a multiple of ``bt`` (``ops.bindjoin_grouped`` pads).
    Returns (keep int32[T, G], idx int32[T, G], nmatch int32[T, G]) where
    ``idx == Mp`` when a row matches none of group g's patterns and
    ``nmatch`` counts group g's matching patterns per row.
    """
    t = cand_s.shape[0]
    gm = pat_s.shape[0]
    assert gm % groups == 0, (gm, groups)
    mp = gm // groups
    assert t % bt == 0 and mp % bm == 0, (t, mp, bt, bm)
    tiles_per_group = mp // bm
    assert bt < LANES or bt % LANES == 0, bt
    sub = max(bt // LANES, 1)          # tile = (sub, lanes), lane-dense
    lanes = min(bt, LANES)

    kernel = functools.partial(_bindjoin_grouped_kernel, bm=bm,
                               m_per_group=mp)
    cand_spec = pl.BlockSpec((None, sub, lanes), lambda i, j: (i, 0, 0))
    out_spec = pl.BlockSpec((None, None, sub, lanes),
                            lambda i, j: (j // tiles_per_group, i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(t // bt, gm // bm),
        in_specs=[
            cand_spec, cand_spec, cand_spec,              # cand s, p, o
            pl.BlockSpec((4, bm), lambda i, j: (0, j),    # slot table
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((groups, t // bt, sub, lanes),
                                        jnp.int32)] * 3,
        interpret=interpret,
    )(_to_tiles(cand_s, sub, lanes), _to_tiles(cand_p, sub, lanes),
      _to_tiles(cand_o, sub, lanes),
      jnp.stack([pat_s, pat_p, pat_o, pat_valid]))
    keep, idx, nmatch = (_from_tiles(x, t) for x in out)
    return keep, idx, nmatch


def _bindjoin_fused_kernel(seg_ref, cs_ref, cp_ref, co_ref, pat_ref,
                           keep_ref, idx_ref, nmatch_ref, *, bm: int,
                           m_per_group: int):
    """Heterogeneous-batch bind-join: each tile resolves its segment.

    ``seg_ref`` is the scalar-prefetched per-tile segment id; the
    pattern block's ``index_map`` already used it to fetch this
    segment's slot tile, so the body only masks dead padding tiles
    (segment id -1), which match nothing.
    """
    tiles_per_group = m_per_group // bm
    m_step = pl.program_id(1) % tiles_per_group   # m-tile within the group
    live = seg_ref[pl.program_id(0)] >= 0
    first, cnt = _match_tile(cs_ref[...], cp_ref[...], co_ref[...], pat_ref,
                             bm=bm, col0=m_step * bm,
                             big=jnp.int32(m_per_group), live=live)
    _accumulate(keep_ref, idx_ref, nmatch_ref, first, cnt, m_step)


@functools.partial(jax.jit,
                   static_argnames=("segments", "groups", "bt", "bm",
                                    "interpret"))
def bindjoin_fused_pallas(seg_of_tile, cand_s, cand_p, cand_o, pat_s, pat_p,
                          pat_o, pat_valid, *, segments: int, groups: int,
                          bt: int = DEFAULT_FUSED_BT, bm: int = DEFAULT_BM,
                          interpret: bool = False):
    """Cross-pattern fused bind-join: S segments, one launch.

    ``seg_of_tile`` is int32 ``[T // bt]`` mapping each candidate tile to
    its segment (-1 = dead padding tile). Pattern inputs are flat int32
    ``[segments * groups * Mp]`` slot tables -- per segment, ``groups``
    pattern sets of ``Mp`` (multiple of ``bm``) slots. Candidates are
    int32 ``[T]`` with ``T`` a multiple of ``bt``; every tile's rows
    belong to one segment (``ops.bindjoin_fused`` marshals/pads).

    The segment ids ride in scalar prefetch, so the slot-table block
    each grid step fetches is chosen by its ``index_map``: segment
    ``seg`` owns slot tiles ``[seg * tiles_per_seg, (seg + 1) *
    tiles_per_seg)``.

    Returns (keep, idx, nmatch) int32 ``[T, groups]`` where column g of a
    row is that row's result against *its own segment's* group-g pattern
    set (``idx == Mp`` when no match).
    """
    t = cand_s.shape[0]
    sgm = pat_s.shape[0]
    assert sgm % (segments * groups) == 0, (sgm, segments, groups)
    mp = sgm // (segments * groups)
    assert t % bt == 0 and mp % bm == 0, (t, mp, bt, bm)
    assert seg_of_tile.shape[0] == t // bt, (seg_of_tile.shape, t, bt)
    tiles_per_group = mp // bm
    tiles_per_seg = groups * tiles_per_group
    assert bt < LANES or bt % LANES == 0, bt
    sub = max(bt // LANES, 1)          # tile = (sub, lanes), lane-dense
    lanes = min(bt, LANES)

    def pat_index(i, j, seg):
        return 0, jnp.maximum(seg[i], 0) * tiles_per_seg + j

    kernel = functools.partial(_bindjoin_fused_kernel, bm=bm,
                               m_per_group=mp)
    cand_spec = pl.BlockSpec((None, sub, lanes),
                             lambda i, j, seg: (i, 0, 0))
    out_spec = pl.BlockSpec((None, None, sub, lanes),
                            lambda i, j, seg: (j // tiles_per_group, i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // bt, tiles_per_seg),
            in_specs=[
                cand_spec, cand_spec, cand_spec,          # cand s, p, o
                pl.BlockSpec((4, bm), pat_index,          # slot table
                             memory_space=pltpu.SMEM),
            ],
            out_specs=[out_spec, out_spec, out_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((groups, t // bt, sub, lanes),
                                        jnp.int32)] * 3,
        interpret=interpret,
    )(seg_of_tile.astype(jnp.int32),
      _to_tiles(cand_s, sub, lanes), _to_tiles(cand_p, sub, lanes),
      _to_tiles(cand_o, sub, lanes),
      jnp.stack([pat_s, pat_p, pat_o, pat_valid]))
    keep, idx, nmatch = (_from_tiles(x, t) for x in out)
    return keep, idx, nmatch
