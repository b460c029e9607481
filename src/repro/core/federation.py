"""Distributed brTPF: the triple store sharded over the mesh.

The paper (section 2.2) notes that TPF-style interfaces compose into
federations of servers. Here the federation *is* the mesh: the dataset is
partitioned across the ``data`` axis (one shard per device = one "brTPF
server"), a request -- (triple pattern, attached mappings) -- is broadcast
to every shard, each shard evaluates the bindings-restricted selector
locally with the Pallas ``bindjoin`` kernel, and the fixed-capacity local
pages are all-gathered back to the requesting client.

This is the paper's thesis expressed in mesh terms: the bindings (a few
KB) travel to the data, instead of the data (the full TPF fragment)
traveling to the client. The dry-run rooflines in EXPERIMENTS.md quantify
exactly this collective-byte saving.

Since PR 3 the *windowed* request step is the default: each shard
binary-searches its sorted keys for the pattern's bound-prefix range and
streams only a fixed ``window`` of it per launch, so per-request device
work scales with the window -- never with the range or the shard size.
:class:`ShardedSelector` packages this as a first-class selector backend
for :class:`~repro.core.server.BrTPFServer` (``selector_backend=
"sharded"``), byte-identical to ``selectors.brtpf_select_with_cnt`` and
sharing the grouped multi-request geometry (G same-pattern requests =
one sharded launch) and :class:`~repro.core.kernel_selectors.LaunchRecord`
accounting surface with the single-host kernel path.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from .fragments import FragmentStore, fragment_key
from .metrics import Counters, phase
from .placement import HeatLog, Placement
from .kernel_selectors import (_EMPTY, FUSED_BT, FusedSegment,
                               LaunchRecord, _fused_base_mask,
                               consult_fragments, consult_segment,
                               finish_segment, fusion_legality,
                               marshal_pattern_grid, record_fragments,
                               select_block_numpy, stream_order)
from .rdf import TriplePattern, is_var
from .selectors import instantiate_patterns

# Default per-shard window: one launch streams this many candidate rows
# per device. 8 * 128 VPU sublane*lane tiles; small enough that a page-0
# probe of a selective pattern costs a fraction of a shard pass, large
# enough that WatDiv-scale ranges need a handful of windows.
DEFAULT_SHARD_WINDOW = 1024

# Smallest kernel tile the sharded steps launch: one 8-row sublane group,
# however small a shard window or compacted sub-window gets.
_MIN_TILE = 8


def _local_brtpf(cand: jnp.ndarray, patterns: jnp.ndarray,
                 pat_valid: jnp.ndarray, base_vec: jnp.ndarray,
                 cand_valid: jnp.ndarray, capacity: int):
    """Per-shard selector: Definition 1 on the local partition.

    ``base_vec`` carries the original pattern's repeated-variable equality
    flags (the instantiated-pattern grid alone cannot express them).
    Returns a fixed-shape local page (capacity, 3) padded with -1 + count.
    """
    keep, _ = kops.bindjoin(cand, patterns, pat_valid)
    keep &= kops.tpf_match(cand, base_vec)
    keep &= cand_valid
    idx, count = kops.compact_mask(keep, capacity)
    page = jnp.take(cand, jnp.maximum(idx, 0), axis=0)
    page = jnp.where((idx >= 0)[:, None], page, -1)
    return page, count


@dataclasses.dataclass
class ShardIndex:
    """One component order's per-shard sorted mirror of the partition.

    ``host_keys`` keeps a host-side copy of the per-shard sorted keys:
    the request planner (:meth:`FederatedStore.plan_windows`) uses it to
    binary-search shard-local ranges and Omega sub-ranges *before*
    launching, so windows provably disjoint from every sub-range are
    never dispatched. (The device step re-derives the same bounds with
    an on-device searchsorted -- the host copy only steers which pages
    launch, it never feeds result data.)
    """

    name: str                # "spo" | "pos" | "osp"
    triples: jax.Array       # int32 [shards * shard_n, 3], per-shard sorted
    valid: jax.Array         # bool  [shards * shard_n]
    keys: jax.Array          # int64 [shards * shard_n]
    host_keys: np.ndarray    # int64 [shards, shard_n] (same values)


@dataclasses.dataclass
class WindowPlan:
    """Host-side launch plan for one (grouped) windowed request.

    ``pages`` lists the window indexes that can contain join-relevant
    rows on at least one shard; everything else is skipped. Unpruned
    plans list every page of the pattern's bound-prefix range under
    ``order``; pruned plans keep only pages intersecting some
    per-binding sub-range. ``candidate_rows`` is the total (cross-shard)
    row count inside the relevant sub-ranges -- the small-work fast
    path's decision quantity.
    """

    order: str
    lo_key: int
    hi_key: int
    pages: List[int]
    range_rows: int          # sum over shards of the base range length
    candidate_rows: int      # rows inside relevant sub-ranges (<= above)
    pruned: bool
    pages_total: int         # pages an unpruned plan would launch
    # Per shard the base range bounds [start, end) -- absolute
    # shard-local positions. Set on every plan (per-shard attribution
    # and replica routing need it); ``shard_spans`` additionally carries
    # the merged live sub-range spans that sub-window compaction needs,
    # and stays None when unpruned.
    shard_bounds: Optional[List[Tuple[int, int]]] = None
    shard_spans: Optional[List[np.ndarray]] = None


@dataclasses.dataclass
class FederatedStore:
    """Triple store sharded over one mesh axis (one shard = one server).

    Each shard keeps its partition sorted with packed int64 keys in all
    three component orders -- SPO plus the POS/OSP mirrors (every
    federation member is an HDT-style server with HDT's three indexes).
    The mirrors are what let unbound-subject patterns (``(?s, p, ?o)``,
    ``(?s, ?p, o)``) binary-search a narrow shard-local range instead of
    scanning the whole shard, and the *windowed* request path (the
    default since PR 3) streams only a fixed window of the chosen
    order's range per launch.
    """

    mesh: Mesh
    axis: str
    triples: jax.Array       # SPO mirror (compat alias of indexes["spo"])
    valid: jax.Array
    keys: jax.Array
    shard_n: int
    indexes: Dict[str, ShardIndex] = dataclasses.field(
        default_factory=dict, repr=False)
    # jit-cache for the windowed request steps, keyed on the static
    # launch geometry (window, groups, pattern slots, projection).
    _steps: Dict[tuple, object] = dataclasses.field(
        default_factory=dict, repr=False)
    # Workload-aware placement (docs/federation.md, "Placement"): when
    # set, shard boundaries follow the heat-weighted quantiles instead
    # of the equal split, and ``placement.replicas`` ranges are held by
    # several shards (the routed launch path dedups them to one owner).
    placement: Optional[Placement] = None
    # Host copy of the unsharded dataset, kept so ``repartition`` can
    # rebuild under new boundaries without a device gather.
    host_triples: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)

    @property
    def shards(self) -> int:
        return self.mesh.shape[self.axis]

    @classmethod
    def build(cls, triples_np: np.ndarray, mesh: Mesh,
              axis: str = "data",
              placement: Optional[Placement] = None) -> "FederatedStore":
        from .store import _ORDERS, _pack
        shards = mesh.shape[axis]
        n = triples_np.shape[0]
        if placement is not None:
            return cls._build_placed(triples_np, mesh, axis, placement)
        shard_n = max(1, -(-n // shards))
        total = shard_n * shards
        base = np.full((total, 3), -1, dtype=np.int32)
        base[:n] = triples_np
        base_valid = np.zeros((total,), dtype=bool)
        base_valid[:n] = True
        sharding = NamedSharding(mesh, P(axis, None))
        vsharding = NamedSharding(mesh, P(axis))
        indexes: Dict[str, ShardIndex] = {}
        for name, comp_order in _ORDERS.items():
            padded = base.copy()
            valid = base_valid.copy()
            # per-shard sort under this order's packed key (padding rows
            # key to +inf -> sort last). int64 keys need the x64 context
            # (off by default in jax).
            keys = np.where(
                valid,
                _pack(padded[:, comp_order[0]], padded[:, comp_order[1]],
                      padded[:, comp_order[2]]),
                np.iinfo(np.int64).max)
            for s in range(shards):
                sl = slice(s * shard_n, (s + 1) * shard_n)
                order = np.argsort(keys[sl], kind="stable")
                padded[sl] = padded[sl][order]
                valid[sl] = valid[sl][order]
                keys[sl] = keys[sl][order]
            with enable_x64(True):
                keys_dev = jax.device_put(keys, vsharding)
            indexes[name] = ShardIndex(
                name=name,
                triples=jax.device_put(padded, sharding),
                valid=jax.device_put(valid, vsharding),
                keys=keys_dev,
                host_keys=keys.reshape(shards, shard_n))
        spo = indexes["spo"]
        return cls(mesh=mesh, axis=axis,
                   triples=spo.triples, valid=spo.valid, keys=spo.keys,
                   shard_n=shard_n, indexes=indexes,
                   host_triples=np.asarray(triples_np))

    @classmethod
    def _build_placed(cls, triples_np: np.ndarray, mesh: Mesh,
                      axis: str, placement: Placement) -> "FederatedStore":
        """Build under workload-aware boundaries + replicated ranges.

        Per order, each triple's packed key is assigned to the shard
        whose boundary span owns it (``Placement.shard_of``; orders
        without boundaries fall back to an equal-count contiguous
        split), then every :class:`~repro.core.placement.ReplicaRange`'s
        rows are *additionally* copied onto its replica shards.  Each
        shard's partition stays a contiguous key range plus whole
        replicated sub-ranges, sorted -- which is what lets the routed
        launch path subtract a replica range from non-owners by a pair
        of binary searches.
        """
        from .store import _ORDERS, _pack
        shards = mesh.shape[axis]
        per_order_rows: Dict[str, List[np.ndarray]] = {}
        for name, comp_order in _ORDERS.items():
            keys = _pack(triples_np[:, comp_order[0]],
                         triples_np[:, comp_order[1]],
                         triples_np[:, comp_order[2]])
            bounds = placement.boundaries.get(name)
            if bounds is not None and len(bounds) == shards - 1:
                assign = np.searchsorted(
                    np.asarray(bounds, dtype=np.int64), keys, side="right")
            else:
                # equal-count contiguous fallback over this order's
                # sorted keys (still a contiguous key partition)
                order = np.argsort(keys, kind="stable")
                assign = np.empty(keys.shape, dtype=np.int64)
                cutpos = np.arange(1, shards) * keys.size // shards
                assign[order] = np.searchsorted(
                    cutpos, np.arange(keys.size), side="right")
            rows = [triples_np[assign == s] for s in range(shards)]
            for rr in placement.replicas.get(name, ()):
                sel = (keys >= rr.lo_key) & (keys <= rr.hi_key)
                block = triples_np[sel]
                if block.shape[0] == 0:
                    continue
                for rs in rr.replicas:
                    if rs != rr.home:
                        rows[rs] = np.concatenate([rows[rs], block],
                                                  axis=0)
            per_order_rows[name] = rows
        shard_n = max(1, max(r.shape[0] for rows in per_order_rows.values()
                             for r in rows))
        total = shard_n * shards
        sharding = NamedSharding(mesh, P(axis, None))
        vsharding = NamedSharding(mesh, P(axis))
        indexes: Dict[str, ShardIndex] = {}
        for name, comp_order in _ORDERS.items():
            padded = np.full((total, 3), -1, dtype=np.int32)
            valid = np.zeros((total,), dtype=bool)
            keys = np.full((total,), np.iinfo(np.int64).max,
                           dtype=np.int64)
            for s, block in enumerate(per_order_rows[name]):
                m = block.shape[0]
                k = _pack(block[:, comp_order[0]], block[:, comp_order[1]],
                          block[:, comp_order[2]])
                order = np.argsort(k, kind="stable")
                sl = slice(s * shard_n, s * shard_n + m)
                padded[sl] = block[order]
                valid[sl] = True
                keys[sl] = k[order]
            with enable_x64(True):
                keys_dev = jax.device_put(keys, vsharding)
            indexes[name] = ShardIndex(
                name=name,
                triples=jax.device_put(padded, sharding),
                valid=jax.device_put(valid, vsharding),
                keys=keys_dev,
                host_keys=keys.reshape(shards, shard_n))
        spo = indexes["spo"]
        return cls(mesh=mesh, axis=axis,
                   triples=spo.triples, valid=spo.valid, keys=spo.keys,
                   shard_n=shard_n, indexes=indexes,
                   placement=placement,
                   host_triples=np.asarray(triples_np))

    def repartition(self, heat: HeatLog, **plan_kwargs) -> "FederatedStore":
        """Rebuild with workload-aware boundaries planned from ``heat``.

        Returns a NEW store (rebuild-with-cutover: the caller swaps it in
        atomically and must invalidate any :class:`FragmentStore` pages
        planned against the old partitioning -- repro-lint CC003 enforces
        that every ``.federated`` swap site reaches an invalidation).
        """
        from .placement import dataset_keys, plan_placement
        if self.host_triples is None:
            raise ValueError(
                "host triples unavailable; the store was not built via "
                "FederatedStore.build")
        placement = plan_placement(
            heat, dataset_keys(self.host_triples), self.shards,
            **plan_kwargs)
        return FederatedStore.build(self.host_triples, self.mesh,
                                    axis=self.axis, placement=placement)

    # -- host-side request marshalling ---------------------------------------

    def request_arrays(self, tp: TriplePattern,
                       omega: Optional[np.ndarray],
                       max_mpr: int) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
        """Host-side request marshalling: instantiate + dedup (server
        algorithm steps 1-3) and pad to the interface's maxMpR."""
        insts = instantiate_patterns(tp, omega)
        if len(insts) > max_mpr:
            raise ValueError(f"{len(insts)} instantiations > maxMpR")
        pats = np.full((max_mpr, 3), -1, dtype=np.int32)
        valid = np.zeros((max_mpr,), dtype=np.int32)
        for i, p in enumerate(insts):
            pats[i] = [c if not is_var(c) else -1 for c in p.as_tuple()]
            valid[i] = 1
        comps = tp.as_tuple()
        base_vec = kops.pattern_vec_from(
            tuple(-1 if is_var(c) else c for c in comps),
            eq_sp=int(is_var(comps[0]) and comps[0] == comps[1]),
            eq_so=int(is_var(comps[0]) and comps[0] == comps[2]),
            eq_po=int(is_var(comps[1]) and comps[1] == comps[2]),
        )
        return pats, valid, base_vec

    @staticmethod
    def prefix_keys(tp: TriplePattern,
                    order_name: str = "spo") -> Tuple[int, int]:
        """(lo_key, hi_key) of the pattern's bound prefix under the
        given index order -- the host-computed range bounds every shard
        binary-searches (the client computing a page URL, in mesh
        terms). Defaults to the SPO mirror for compatibility with the
        single-request windowed path."""
        from .store import _MAX_ID, _ORDERS, _pack
        comp_order = _ORDERS[order_name]
        comps = tp.as_tuple()
        prefix = []
        for pos in comp_order:
            if is_var(comps[pos]):
                break
            prefix.append(comps[pos])
        lo_vals = prefix + [0] * (3 - len(prefix))
        hi_vals = prefix + [_MAX_ID] * (3 - len(prefix))
        lo = int(_pack(np.int64(lo_vals[0]), np.int64(lo_vals[1]),
                       np.int64(lo_vals[2])))
        hi = int(_pack(np.int64(hi_vals[0]), np.int64(hi_vals[1]),
                       np.int64(hi_vals[2])))
        return lo, hi

    # -- host-side launch planning (Omega-restricted window skip) ------------

    def plan_windows(self, tp: TriplePattern,
                     insts: Sequence[TriplePattern],
                     window: int) -> WindowPlan:
        """Plan the window launches for one (grouped) request.

        Index choice: when every instantiated pattern shares one shape
        whose best index binds a longer prefix than the base pattern
        does under that index, the launch streams THAT order and the
        per-binding sub-ranges become host-computable window filters;
        otherwise the base pattern's own best index is used (the
        POS/OSP mirrors are what make this a real choice -- an
        unbound-subject pattern no longer scans whole shards).

        Window skip: the per-binding ``(lo, hi)`` key intervals are
        batch-searchsorted against every shard's host key copy; a window
        page whose owned span intersects no sub-range on any shard is
        provably match-free (every triple matching instantiation ``p_j``
        has its key inside ``p_j``'s interval) and is dropped from
        ``pages``. Skipping whole pages never reorders or duplicates
        anything, so parity is untouched.
        """
        from .store import (TripleStore, _ORDERS, merge_spans,
                            prefix_interval_keys)
        window = max(1, min(int(window), self.shard_n))

        def base_plan(order_name: str) -> WindowPlan:
            lo, hi = self.prefix_keys(tp, order_name)
            hk = self.indexes[order_name].host_keys
            starts = np.array([np.searchsorted(hk[s], lo, side="left")
                               for s in range(hk.shape[0])])
            ends = np.array([np.searchsorted(hk[s], hi, side="right")
                             for s in range(hk.shape[0])])
            range_rows = int((ends - starts).sum())
            pages_total = int(max(
                (-(-int(e - s) // window)
                 for s, e in zip(starts, ends, strict=True)), default=0))
            return WindowPlan(order=order_name, lo_key=lo, hi_key=hi,
                              pages=list(range(pages_total)),
                              range_rows=range_rows,
                              candidate_rows=range_rows, pruned=False,
                              pages_total=pages_total,
                              shard_bounds=[
                                  (int(s), int(e)) for s, e in
                                  zip(starts, ends, strict=True)])

        bname, _ = TripleStore._choose_index(tp)
        unpruned = base_plan(bname)
        shapes = {tuple(is_var(c) for c in p.as_tuple()) for p in insts}
        if len(shapes) != 1 or not insts:
            return unpruned
        iname, iplen = TripleStore._choose_index(insts[0])
        # prefix the BASE pattern binds under the instantiations' best
        # index: pruning pays only if instantiations bind more
        comp_order = _ORDERS[iname]
        base_plen = 0
        for pos in comp_order:
            if is_var(tp.as_tuple()[pos]):
                break
            base_plen += 1
        if iplen <= base_plen:
            return unpruned
        comps = np.asarray([p.as_tuple() for p in insts], dtype=np.int64)
        lo_keys, hi_keys = prefix_interval_keys(comps, comp_order, iplen)
        # base range under the insts' index (already computed when the
        # instantiations' best order is the base pattern's own)
        shell = unpruned if iname == bname else base_plan(iname)
        hk = self.indexes[iname].host_keys
        pages: set = set()
        candidate_rows = 0
        shard_bounds: List[Tuple[int, int]] = []
        shard_spans: List[np.ndarray] = []
        for s in range(hk.shape[0]):
            start = int(np.searchsorted(hk[s], shell.lo_key,
                                        side="left"))
            end = int(np.searchsorted(hk[s], shell.hi_key,
                                      side="right"))
            shard_bounds.append((start, end))
            if end <= start:
                shard_spans.append(np.empty((0, 2), dtype=np.int64))
                continue
            a = np.searchsorted(hk[s], lo_keys, side="left")
            b = np.searchsorted(hk[s], hi_keys, side="right")
            spans = merge_spans(np.stack([a, b], axis=1))
            clipped: List[Tuple[int, int]] = []
            for slo, shi in spans:
                # instantiation intervals are sub-intervals of the base
                # range under the same order, but clip defensively
                slo = max(int(slo), start)
                shi = min(int(shi), end)
                if shi <= slo:
                    continue
                candidate_rows += shi - slo
                clipped.append((slo, shi))
                pages.update(range((slo - start) // window,
                                   (shi - 1 - start) // window + 1))
            shard_spans.append(
                np.asarray(clipped, dtype=np.int64).reshape(-1, 2))
        pruned = WindowPlan(order=iname, lo_key=shell.lo_key,
                            hi_key=shell.hi_key, pages=sorted(pages),
                            range_rows=shell.range_rows,
                            candidate_rows=candidate_rows, pruned=True,
                            pages_total=shell.pages_total,
                            shard_bounds=shard_bounds,
                            shard_spans=shard_spans)
        # the base pattern's own index may beat sub-range skipping under
        # the instantiations' index (fewer actual window dispatches win)
        return pruned if len(pruned.pages) <= len(unpruned.pages) \
            else unpruned

    # -- the request path ----------------------------------------------------

    def execute(self, tp: TriplePattern, omega: Optional[np.ndarray],
                max_mpr: int, capacity: int) -> np.ndarray:
        """Run one distributed brTPF request; returns matching triples.

        Routed through the windowed step (the default request path):
        per-shard device work is bounded by the window, and -- unlike
        :meth:`execute_full` -- the result can never be truncated by an
        undersized ``capacity`` (each window's page capacity is the
        window itself).
        """
        return self.execute_windowed(tp, omega, max_mpr, capacity,
                                     window=min(capacity, self.shard_n))

    def execute_full(self, tp: TriplePattern, omega: Optional[np.ndarray],
                     max_mpr: int, capacity: int) -> np.ndarray:
        """The paper-faithful baseline: every shard streams its whole
        partition through the bind-join kernel in one launch. Kept for
        the dry-run roofline comparison; ``capacity`` bounds the local
        page (matches beyond it are silently dropped)."""
        if self.placement is not None and self.placement.has_replicas:
            raise RuntimeError(
                "execute_full cannot serve a replicated placement: the "
                "full-shard stream would report replicated ranges once "
                "per holder -- use the windowed (routed) path")
        pats, valid, base_vec = self.request_arrays(tp, omega, max_mpr)
        pages, counts = self.lowerable(capacity)(
            self.triples, self.valid, jnp.asarray(pats),
            jnp.asarray(valid), jnp.asarray(base_vec))
        pages = np.asarray(pages).reshape(-1, 3)
        keep = pages[:, 0] >= 0  # -1-padded rows are invalid
        return pages[keep]

    def lowerable(self, capacity: int):
        """The jitted full-shard-stream request step (also used by the
        dry-run: ``.lower(...).compile()`` proves the collective
        schedule of the baseline variant)."""
        mesh, axis = self.mesh, self.axis

        def step(triples, valid, pats, pat_valid, base_vec):
            def shard_fn(cand, cand_valid, p, pv, bv):
                page, count = _local_brtpf(
                    cand, p, pv, bv, cand_valid, capacity)
                # Return per-shard pages; the all-gather back to the
                # client is the response wire transfer.
                page = jax.lax.all_gather(page, axis)
                count = jax.lax.all_gather(count, axis)
                return page, count

            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(), P(), P()),
                out_specs=(P(), P()),
                # pallas_call emits ShapeDtypeStructs without vma metadata
                check_vma=False,
            )
            return fn(triples, valid, pats, pat_valid, base_vec)

        return jax.jit(step)

    # -- the windowed request path (default) ---------------------------------

    def lowerable_windowed(self, capacity: int, window: int,
                           wild_cols: tuple = (0, 1, 2)):
        """Single-request windowed step (see EXPERIMENTS.md §Perf(D)):

        1. *windowed scan*: each shard binary-searches its sorted keys
           for the pattern's bound-prefix range and runs the bind-join
           kernel over a fixed ``window`` starting there, not the whole
           shard -- compute/memory per request drops shard_n/window x
           for selective patterns;
        2. *column projection*: only the pattern's unbound components
           (``wild_cols``) are all-gathered back -- the bound
           components are implied by the request, cutting response
           bytes by (3 - len(wild_cols))/3.

        Inputs add (lo_key, hi_key) int64 scalars (host-computed from
        the pattern prefix, identical on every shard). Page windows are
        *disjoint* spans of the range (a span near the shard edge is
        masked, not shifted), so paging never double-reports a triple.
        """
        mesh, axis = self.mesh, self.axis
        window = max(1, min(window, self.shard_n))

        def step(triples, valid, keys, pats, pat_valid, base_vec,
                 lo_key, hi_key, page_idx):
            def shard_fn(cand, cand_valid, k, p, pv, bv, lo, hi, pi):
                start = jnp.searchsorted(k, lo, side="left")
                end = jnp.searchsorted(k, hi, side="right")
                range_len = end - start                 # page metadata
                win, win_valid, in_span = _window_slice(
                    cand, cand_valid, start, end, pi, window)
                page, count = _local_brtpf(
                    win, p, pv, bv, win_valid & in_span, capacity)
                page = page[:, list(wild_cols)]
                page = jax.lax.all_gather(page, axis)
                count = jax.lax.all_gather(count, axis)
                range_len = jax.lax.all_gather(range_len, axis)
                return page, count, range_len

            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis), P(), P(),
                          P(), P(), P(), P()),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )
            return fn(triples, valid, keys, pats, pat_valid, base_vec,
                      lo_key, hi_key, page_idx)

        return jax.jit(step)

    def lowerable_windowed_grouped(self, window: int, groups: int,
                                   wild_cols: tuple = (0, 1, 2)):
        """Grouped windowed step: G same-pattern requests, one launch.

        The sharded twin of ``kops.bindjoin_grouped``'s geometry: every
        shard streams ONE window of its bound-prefix range and evaluates
        all G requests' instantiated-pattern sets against it, so
        coalesced batches (``BrTPFServer.handle_batch`` /
        ``AsyncBrTPFServer``) cost one sharded launch per window instead
        of G. Per (shard, group) the step emits a fixed-shape page of
        compacted kept rows (capacity = window, so a window's matches
        always fit), the first-matching-pattern index per kept row (the
        stream id the ordering epilogue needs), the kept-row count, and
        the group's Definition-2 ``cnt`` contribution (sum of per-row
        matching-pattern counts); plus the shard's range length for
        paging. Jitted steps are cached per static geometry on the
        store (``_steps``).

        Returns arrays shaped (shards, G, window[, C]) / (shards, G) /
        (shards,) after the all-gather.
        """
        # clamp before building the cache key, so raw windows that
        # clamp to the same effective value share one traced step
        window = max(1, min(window, self.shard_n))
        key = ("grouped", window, groups, wild_cols)
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        mesh, axis = self.mesh, self.axis

        def step(triples, valid, keys, pats, pat_valid, base_vec,
                 lo_key, hi_key, page_idx):
            def shard_fn(cand, cand_valid, k, p, pv, bv, lo, hi, pi):
                start = jnp.searchsorted(k, lo, side="left")
                end = jnp.searchsorted(k, hi, side="right")
                range_len = end - start
                win, win_valid, in_span = _window_slice(
                    cand, cand_valid, start, end, pi, window)
                keep, idx, nmatch = kops.bindjoin_grouped(win, p, pv)
                base = kops.tpf_match(win, bv)
                mask = (keep & base[:, None]
                        & (win_valid & in_span)[:, None])        # (W, G)
                cnts = jnp.sum(jnp.where(mask, nmatch, 0), axis=0)
                rows, counts = jax.vmap(
                    lambda m: kops.compact_mask(m, window),
                    in_axes=1, out_axes=0)(mask)          # (G, W), (G,)
                safe = jnp.maximum(rows, 0)
                page = jnp.take(win, safe, axis=0)        # (G, W, 3)
                first = jax.vmap(lambda r, col: col[r],
                                 in_axes=(0, 1))(safe, idx)   # (G, W)
                page = page[:, :, list(wild_cols)]
                page = jnp.where((rows >= 0)[:, :, None], page, -1)
                first = jnp.where(rows >= 0, first, -1)
                page = jax.lax.all_gather(page, axis)
                first = jax.lax.all_gather(first, axis)
                counts = jax.lax.all_gather(counts, axis)
                cnts = jax.lax.all_gather(cnts, axis)
                range_len = jax.lax.all_gather(range_len, axis)
                return page, first, counts, cnts, range_len

            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis), P(), P(),
                          P(), P(), P(), P()),
                out_specs=(P(), P(), P(), P(), P()),
                check_vma=False,
            )
            return fn(triples, valid, keys, pats, pat_valid, base_vec,
                      lo_key, hi_key, page_idx)

        fn = jax.jit(step)
        self._steps[key] = fn
        return fn

    def lowerable_windowed_routed(self, window: int, groups: int,
                                  wild_cols: tuple = (0, 1, 2)):
        """Routed grouped step (docs/federation.md, "Placement").

        Same grouped geometry as :meth:`lowerable_windowed_grouped`, but
        the shard-local span to stream arrives host-computed as explicit
        ``(span_lo, span_hi)`` int32 [shards] position vectors instead of
        being re-derived from ``(lo_key, hi_key)`` on device.  The host
        planner needs that control under a workload-aware placement: it
        has already chosen each replicated range's least-loaded owner and
        subtracted the range from every other holder's span, so a
        replicated triple is streamed by exactly one shard per request
        (dedup at merge) and per-shard spans can differ in length.  A
        shard with no work this round sends ``(0, 0)``.  Each round
        streams at most ``window`` rows per shard (the planner chops
        longer spans into window-sized chunks).
        """
        window = max(1, min(window, self.shard_n))
        key = ("routed", window, groups, wild_cols)
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        mesh, axis = self.mesh, self.axis

        def step(triples, valid, pats, pat_valid, base_vec,
                 span_lo, span_hi):
            def shard_fn(cand, cand_valid, p, pv, bv, lo, hi):
                lo = lo[0]
                hi = hi[0]
                shard_rows = cand.shape[0]
                slice_start = jnp.clip(lo, 0, max(shard_rows - window, 0))
                win = jax.lax.dynamic_slice_in_dim(
                    cand, slice_start, window, axis=0)
                win_valid = jax.lax.dynamic_slice_in_dim(
                    cand_valid, slice_start, window, axis=0)
                pos = jnp.arange(window, dtype=jnp.int32) + slice_start
                in_span = (pos >= lo) & (pos < jnp.minimum(
                    lo + window, hi))
                keep, idx, nmatch = kops.bindjoin_grouped(win, p, pv)
                base = kops.tpf_match(win, bv)
                mask = (keep & base[:, None]
                        & (win_valid & in_span)[:, None])        # (W, G)
                cnts = jnp.sum(jnp.where(mask, nmatch, 0), axis=0)
                rows, counts = jax.vmap(
                    lambda m: kops.compact_mask(m, window),
                    in_axes=1, out_axes=0)(mask)          # (G, W), (G,)
                safe = jnp.maximum(rows, 0)
                page = jnp.take(win, safe, axis=0)        # (G, W, 3)
                first = jax.vmap(lambda r, col: col[r],
                                 in_axes=(0, 1))(safe, idx)   # (G, W)
                page = page[:, :, list(wild_cols)]
                page = jnp.where((rows >= 0)[:, :, None], page, -1)
                first = jnp.where(rows >= 0, first, -1)
                page = jax.lax.all_gather(page, axis)
                first = jax.lax.all_gather(first, axis)
                counts = jax.lax.all_gather(counts, axis)
                cnts = jax.lax.all_gather(cnts, axis)
                return page, first, counts, cnts

            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(), P(), P(),
                          P(axis), P(axis)),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )
            return fn(triples, valid, pats, pat_valid, base_vec,
                      span_lo, span_hi)

        fn = jax.jit(step)
        self._steps[key] = fn
        return fn

    def lowerable_windowed_grouped_compact(self, wc: int, groups: int,
                                           wild_cols: tuple = (0, 1, 2)):
        """Sub-window compacted grouped step (docs/fusion.md).

        Instead of streaming a contiguous window, each shard gathers an
        explicit row-index vector of capacity ``wc`` (< window),
        host-computed from the ``merge_spans`` live spans inside the
        page's span -- the PR 5 leftover: when sub-ranges leave large
        dead gaps *inside* a window, the gather skips them at row
        granularity rather than only skipping whole disjoint pages.
        Rows outside every per-binding sub-range are provably match-free
        (each instantiation's matches lie inside its own key interval),
        so dropping them cannot change the response; the caller records
        the reclaimed rows on the :class:`LaunchRecord`.

        ``wc`` is a power of two (bounded jit cache; the caller only
        compacts when ``wc <= window // 2``, so the gather pays for
        itself). Index -1 marks padding slots.
        """
        key = ("compact", wc, groups, wild_cols)
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        mesh, axis = self.mesh, self.axis
        bt = max(min(kops.DEFAULT_BT, wc), _MIN_TILE)

        def step(triples, valid, pats, pat_valid, base_vec, row_sel):
            def shard_fn(cand, cand_valid, p, pv, bv, rs):
                rs = rs.reshape(wc)
                safe = jnp.maximum(rs, 0)
                win = jnp.take(cand, safe, axis=0)        # (wc, 3)
                wv = jnp.take(cand_valid, safe, axis=0) & (rs >= 0)
                keep, idx, nmatch = kops.bindjoin_grouped(win, p, pv,
                                                          bt=bt)
                base = kops.tpf_match(win, bv)
                mask = keep & base[:, None] & wv[:, None]      # (wc, G)
                cnts = jnp.sum(jnp.where(mask, nmatch, 0), axis=0)
                rows, counts = jax.vmap(
                    lambda m: kops.compact_mask(m, wc),
                    in_axes=1, out_axes=0)(mask)       # (G, wc), (G,)
                safe2 = jnp.maximum(rows, 0)
                page = jnp.take(win, safe2, axis=0)        # (G, wc, 3)
                first = jax.vmap(lambda r, col: col[r],
                                 in_axes=(0, 1))(safe2, idx)   # (G, wc)
                page = page[:, :, list(wild_cols)]
                page = jnp.where((rows >= 0)[:, :, None], page, -1)
                first = jnp.where(rows >= 0, first, -1)
                page = jax.lax.all_gather(page, axis)
                first = jax.lax.all_gather(first, axis)
                counts = jax.lax.all_gather(counts, axis)
                cnts = jax.lax.all_gather(cnts, axis)
                return page, first, counts, cnts

            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(), P(), P(),
                          P(axis, None)),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )
            return fn(triples, valid, pats, pat_valid, base_vec, row_sel)

        fn = jax.jit(step)
        self._steps[key] = fn
        return fn

    def lowerable_windowed_fused(self, window: int, segs: int,
                                 groups: int):
        """Cross-pattern fused windowed step: S segments, one launch.

        The sharded twin of ``kops.bindjoin_fused`` (docs/fusion.md):
        per round, every shard slices ONE window of *each* segment's
        bound-prefix range under this step's index order, concatenates
        the S windows into one tile-aligned stream, and the fused kernel
        resolves each tile's segment from its program id. Per-segment
        ``(lo, hi)`` keys and page indexes arrive as int64/int32 [S]
        vectors; a page index of -1 deactivates its segment for the
        round (its rows are masked out of every group), which is how
        segments with fewer planned pages ride along. Windows are padded
        to the next power of two so the fused tile evenly divides every
        segment's extent.

        Returns (page, first, counts, cnts) shaped
        (shards, S, G, Wp[, 3]) / (shards, S, G) after the all-gather --
        no column projection: segments bind different components, so the
        full triples travel back.
        """
        window = max(1, min(window, self.shard_n))
        wp = max(_pow2(window), _MIN_TILE)
        key = ("fused", window, segs, groups)
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        mesh, axis = self.mesh, self.axis
        bt = min(FUSED_BT, wp)
        tiles_per_seg = wp // bt

        def step(triples, valid, keys, pats, pat_valid, base_vecs,
                 lo_keys, hi_keys, page_idx):
            def shard_fn(cand, cand_valid, k, p, pv, bvs, lo, hi, pi):
                wins, valids = [], []
                for si in range(segs):
                    start = jnp.searchsorted(k, lo[si], side="left")
                    end = jnp.searchsorted(k, hi[si], side="right")
                    win, wv, ins = _window_slice(
                        cand, cand_valid, start, end, pi[si], window)
                    ok = wv & ins & (pi[si] >= 0)
                    if wp > window:
                        win = jnp.concatenate(
                            [win, jnp.zeros((wp - window, 3), win.dtype)])
                        ok = jnp.concatenate(
                            [ok, jnp.zeros((wp - window,), bool)])
                    wins.append(win)
                    valids.append(ok)
                stream = jnp.concatenate(wins, axis=0)   # (S * Wp, 3)
                svalid = jnp.concatenate(valids, axis=0)
                seg_of_tile = jnp.repeat(
                    jnp.arange(segs, dtype=jnp.int32), tiles_per_seg)
                keep, idx, nmatch = kops.bindjoin_fused(
                    stream, seg_of_tile, p, pv, bt=bt)
                seg_of_row = jnp.repeat(seg_of_tile, bt)
                base = _fused_base_mask(stream, seg_of_row, bvs)
                mask = keep & base[:, None] & svalid[:, None]
                mm = mask.reshape(segs, wp, groups)
                cnts = jnp.where(mask, nmatch, 0).reshape(
                    segs, wp, groups).sum(axis=1)        # (S, G)
                rows, counts = jax.vmap(jax.vmap(
                    lambda m: kops.compact_mask(m, wp),
                    in_axes=1, out_axes=0))(mm)   # (S, G, Wp), (S, G)
                safe = jnp.maximum(rows, 0)
                win_all = stream.reshape(segs, wp, 3)
                page = jax.vmap(
                    lambda w, r: jnp.take(w, r, axis=0))(win_all, safe)
                idxr = idx.reshape(segs, wp, groups)
                first = jax.vmap(
                    lambda ix, r: jax.vmap(lambda rg, col: col[rg],
                                           in_axes=(0, 1))(r, ix)
                )(idxr, safe)                            # (S, G, Wp)
                page = jnp.where((rows >= 0)[..., None], page, -1)
                first = jnp.where(rows >= 0, first, -1)
                page = jax.lax.all_gather(page, axis)
                first = jax.lax.all_gather(first, axis)
                counts = jax.lax.all_gather(counts, axis)
                cnts = jax.lax.all_gather(cnts, axis)
                return page, first, counts, cnts

            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis), P(), P(),
                          P(), P(), P(), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )
            return fn(triples, valid, keys, pats, pat_valid, base_vecs,
                      lo_keys, hi_keys, page_idx)

        fn = jax.jit(step)
        self._steps[key] = fn
        return fn

    def execute_windowed(self, tp: TriplePattern,
                         omega: Optional[np.ndarray], max_mpr: int,
                         capacity: int, window: int) -> np.ndarray:
        """Run the windowed path end-to-end: disjoint window pages until
        every shard's bound-prefix range is covered (the first response
        carries each shard's range length -- the cnt metadata of
        Definition 2), with client-side reconstruction of projected
        columns.

        Returns the fragment's data-triple sequence byte-identical
        (values AND order) to ``selectors.brtpf_select_with_cnt``.
        ``capacity`` is accepted for interface symmetry with
        :meth:`execute_full` but the per-window page capacity is the
        window itself, so results are never truncated.
        """
        del capacity  # windowed pages are capacity-safe by construction
        insts = instantiate_patterns(tp, omega)
        if len(insts) > max_mpr:
            raise ValueError(f"{len(insts)} instantiations > maxMpR")
        selector = ShardedSelector(self, window=window)
        data, _cnt = selector.select_with_cnt(tp, omega, insts)
        return data


def _window_slice(cand, cand_valid, start, end, pi, window: int):
    """Slice window ``pi`` of the shard-local range [start, end).

    The span ``[start + pi*window, min(start + (pi+1)*window, end))`` is
    what this page *owns*; the physical slice start is clamped into the
    array so ``dynamic_slice`` never clips, and ``in_span`` masks the
    slice back to the owned span -- spans are disjoint across pages and
    exactly tile the range, so no triple is reported twice and none is
    skipped.
    """
    shard_n = cand.shape[0]
    span_lo = start + pi.astype(start.dtype) * window
    slice_start = jnp.clip(span_lo, 0, max(shard_n - window, 0))
    win = jax.lax.dynamic_slice_in_dim(
        cand, slice_start.astype(jnp.int32), window, axis=0)
    win_valid = jax.lax.dynamic_slice_in_dim(
        cand_valid, slice_start.astype(jnp.int32), window, axis=0)
    pos = jnp.arange(window, dtype=jnp.int64) + slice_start
    in_span = (pos >= span_lo) & (pos < jnp.minimum(span_lo + window,
                                                    end))
    return win, win_valid, in_span


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _subtract_interval(spans: List[Tuple[int, int]], a: int,
                       b: int) -> List[Tuple[int, int]]:
    """Remove [a, b) from a sorted list of disjoint [lo, hi) spans."""
    out: List[Tuple[int, int]] = []
    for lo, hi in spans:
        if hi <= a or lo >= b:
            out.append((lo, hi))
            continue
        if lo < a:
            out.append((lo, a))
        if hi > b:
            out.append((b, hi))
    return out


def _chop_spans(spans: List[List[Tuple[int, int]]],
                window: int) -> Tuple[List[List[Tuple[int, int]]], int]:
    """Chop each shard's spans into window-sized chunks; returns the
    per-shard chunk lists and the number of launch rounds (the longest
    shard's chunk count -- shards with fewer chunks idle in later
    rounds)."""
    chunks: List[List[Tuple[int, int]]] = []
    for shard_spans in spans:
        cs: List[Tuple[int, int]] = []
        for lo, hi in shard_spans:
            p = lo
            while p < hi:
                q = min(p + window, hi)
                cs.append((p, q))
                p = q
        chunks.append(cs)
    rounds = max((len(c) for c in chunks), default=0)
    return chunks, rounds


class ShardedSelector:
    """Mesh-sharded windowed selector with the KernelSelector contract.

    Serves the bindings-restricted selector from a
    :class:`FederatedStore` without ever materializing a candidate
    range: each launch streams one ``window`` per shard, G same-pattern
    requests share the launch (grouped geometry), and the host epilogue
    (:func:`~repro.core.kernel_selectors.stream_order` over the
    all-gathered kept rows + first-match indices) makes the returned
    data-triple sequence and Definition-2 ``cnt`` byte-identical to
    ``selectors.brtpf_select_with_cnt``.

    Why parity holds across shards: the store partitions the triples,
    so every triple is evaluated on exactly one shard, and page spans
    are disjoint within a shard -- each matching triple is kept exactly
    once, with the same first-matching-pattern stream id the single-host
    kernel computes; the epilogue's (stream, packed-key) sort is a total
    order, so concatenation order across shards/windows is irrelevant.
    ``cnt`` sums the per-row matching-pattern counts over all shards,
    which equals the oracle's sum of per-instantiation stream sizes.

    ``launches`` records one :class:`LaunchRecord` per window launch
    with ``cand_streamed = window`` -- the rows ONE device streams --
    so the accounting surface (and the budgets gated on it) is shared
    with the single-host kernel path.

    Omega-restricted pruning (docs/pruning.md): every request is
    launched from a host-side :class:`WindowPlan` -- the POS/OSP
    mirrors let the plan pick the order with the longest bound prefix
    (unbound-subject patterns stop scanning whole shards), and window
    pages disjoint from every per-binding sub-range are skipped
    outright. With ``store`` connected and ``fast_path_rows`` > 0,
    plans whose relevant row count falls below the threshold are served
    by the numpy block evaluation instead of launching windows.

    Host phases are charged to ``counters`` only around the helpers
    shared with the single-host selector: ``gather`` (the pattern
    grids), ``order`` (the numpy block evaluation and ``stream_order``).
    Planning, the window launches and their read-back are not timed.
    """

    def __init__(self, fed: FederatedStore,
                 window: int = DEFAULT_SHARD_WINDOW,
                 fragments: Optional[FragmentStore] = None,
                 store=None, fast_path_rows: int = 0,
                 heat: Optional[HeatLog] = None,
                 counters: Optional[Counters] = None) -> None:
        self.fed = fed
        self.counters = counters if counters is not None else Counters()
        self.window = max(1, min(int(window), fed.shard_n))
        self.fragments = fragments
        self.store = store
        self.fast_path_rows = int(fast_path_rows)
        self.launches: List[LaunchRecord] = []
        # Placement surfaces (docs/federation.md, "Placement"): the
        # bounded heat log the re-partitioner consumes, and per-shard
        # attribution counters -- launches a shard had work in, candidate
        # rows it streamed, and planned window pages it owned.
        self.heat = heat
        self.shard_launches = np.zeros((fed.shards,), dtype=np.int64)
        self.shard_rows = np.zeros((fed.shards,), dtype=np.int64)
        self.shard_pages = np.zeros((fed.shards,), dtype=np.int64)

    # -- placement surfaces (docs/federation.md, "Placement") ---------------

    def shard_balance(self) -> dict:
        """JSON-safe per-shard balance snapshot (metrics ``shards``)."""
        from .metrics import shard_balance
        return shard_balance(self.shard_launches.tolist(),
                             self.shard_rows.tolist(),
                             self.shard_pages.tolist())

    def reset_shard_counters(self) -> None:
        self.shard_launches[:] = 0
        self.shard_rows[:] = 0
        self.shard_pages[:] = 0

    def rebind(self, fed: FederatedStore) -> None:
        """Cutover to a repartitioned store: swap the federation, clamp
        the window to the new shard size, and restart the per-shard
        attribution (old counts were measured against old boundaries).
        The heat log is kept -- it describes the workload, not the
        partitioning."""
        self.fed = fed
        self.window = max(1, min(self.window, fed.shard_n))
        self.shard_launches = np.zeros((fed.shards,), dtype=np.int64)
        self.shard_rows = np.zeros((fed.shards,), dtype=np.int64)
        self.shard_pages = np.zeros((fed.shards,), dtype=np.int64)

    def _charge_shard_page(self, plan: WindowPlan, window: int,
                           page_idx: int,
                           row_sel: Optional[np.ndarray] = None) -> None:
        """Attribute one window page to the shards that had work in it."""
        if plan.shard_bounds is None:
            return
        for s, (start, end) in enumerate(plan.shard_bounds):
            plo = start + page_idx * window
            phi = min(plo + window, end)
            if phi <= plo:
                continue
            if row_sel is not None:
                rows = int((row_sel[s] >= 0).sum())
                if rows == 0:
                    continue
            elif plan.pruned and plan.shard_spans is not None:
                rows = 0
                for lo, hi in np.asarray(
                        plan.shard_spans[s]).reshape(-1, 2):
                    rows += max(0, min(int(hi), phi) - max(int(lo), plo))
                if rows == 0:
                    continue
            else:
                rows = phi - plo
            self.shard_launches[s] += 1
            self.shard_pages[s] += 1
            self.shard_rows[s] += rows

    def _routed_spans(self, plan: WindowPlan) -> List[List[Tuple[int, int]]]:
        """Per-shard live [lo, hi) position spans for the routed path,
        with every overlapping replica range deduped to its least-loaded
        owner (the other holders get the range subtracted -- a pair of
        binary searches, since each holder's copy is sorted)."""
        fed = self.fed
        hk = fed.indexes[plan.order].host_keys
        shards = hk.shape[0]
        spans: List[List[Tuple[int, int]]] = []
        if plan.pruned and plan.shard_spans is not None:
            for sp in plan.shard_spans:
                spans.append([(int(a), int(b)) for a, b in
                              np.asarray(sp).reshape(-1, 2) if b > a])
        elif plan.shard_bounds is not None:
            spans = [[(int(a), int(b))] if b > a else []
                     for a, b in plan.shard_bounds]
        else:
            for s in range(shards):
                a = int(np.searchsorted(hk[s], plan.lo_key, side="left"))
                b = int(np.searchsorted(hk[s], plan.hi_key, side="right"))
                spans.append([(a, b)] if b > a else [])
        placement = fed.placement
        if placement is None:
            return spans
        for rr in placement.replicas.get(plan.order, ()):
            if rr.hi_key < plan.lo_key or rr.lo_key > plan.hi_key:
                continue
            holders = rr.holders
            owner = min(holders,
                        key=lambda s: (int(self.shard_pages[s]), s))
            for s in holders:
                if s == owner:
                    continue
                a = int(np.searchsorted(hk[s], rr.lo_key, side="left"))
                b = int(np.searchsorted(hk[s], rr.hi_key, side="right"))
                if b > a:
                    spans[s] = _subtract_interval(spans[s], a, b)
        return spans

    # -- public API (same contract as KernelSelector) ------------------------

    def select_with_cnt(
        self, tp: TriplePattern, omega: Optional[np.ndarray],
        insts: Optional[List[TriplePattern]] = None,
    ) -> Tuple[np.ndarray, int]:
        """Sharded ``brtpf_select_with_cnt`` (byte-identical)."""
        return self.select_same_pattern(
            tp, [omega], None if insts is None else [insts])[0]

    def select_same_pattern(
        self, tp: TriplePattern, omegas: Sequence[Optional[np.ndarray]],
        patterns: Optional[List[List[TriplePattern]]] = None,
    ) -> List[Tuple[np.ndarray, int]]:
        """Serve G same-pattern requests from one sharded launch per
        window page. Returns per-request (data sequence, cnt), each
        identical to ``brtpf_select_with_cnt(store, tp, omega_g)``.

        Groups resident in the connected fragment store never launch a
        window: their share is recorded as skipped (same contract as
        :class:`~repro.core.kernel_selectors.KernelSelector`)."""
        if patterns is None:
            patterns = [instantiate_patterns(tp, om) for om in omegas]
        results, live = consult_fragments(self.fragments, tp, omegas,
                                          self.launches)
        if live:
            live_omegas = [omegas[i] for i in live]
            fresh = self._launch_groups(tp, live_omegas,
                                        [patterns[i] for i in live])
            record_fragments(self.fragments, tp, live_omegas, fresh)
            for i, res in zip(live, fresh, strict=True):
                results[i] = res
        return results

    def select_count(self, tp: TriplePattern, omega: Optional[np.ndarray],
                     insts: Optional[List[TriplePattern]] = None) -> int:
        """Count-only sharded selection: Definition-2 ``cnt``, no row
        gather, no all-gathered pages consumed (docs/fusion.md)."""
        if self.fragments is not None:
            got = self.fragments.peek_data(
                fragment_key(tp.as_tuple(), omega), touch=True)
            if got is not None:
                self.fragments.note_skip()
                self.launches.append(LaunchRecord(
                    cand_streamed=0, pat_slots=0, groups=1, skipped=True))
                return int(got[1])
        patterns = [insts if insts is not None
                    else instantiate_patterns(tp, omega)]
        return self._launch_groups(tp, [omega], patterns,
                                   count_only=True)[0][1]

    def _launch_groups(
        self, tp: TriplePattern, omegas: Sequence[Optional[np.ndarray]],
        patterns: List[List[TriplePattern]],
        count_only: bool = False,
    ) -> List[Tuple[np.ndarray, int]]:
        """Windowed sharded launches over the store-miss groups."""
        all_insts = [p for group in patterns for p in group]
        plan = self.fed.plan_windows(tp, all_insts, self.window)
        return self._launch_plan(tp, patterns, plan,
                                 count_only=count_only)

    def _gather_fast_block(self, tp: TriplePattern,
                           all_insts: List[TriplePattern]) -> np.ndarray:
        """Host-side pruned candidate block for the small-work path."""
        sr = self.store.subranges(tp, insts=all_insts)
        if sr is not None and sr.rows < len(
                self.store.candidate_range(tp)):
            return self.store.gather_subranges(sr)
        return self.store.candidate_range(tp).triples

    def _page_row_sel(self, plan: WindowPlan, window: int,
                      page: int) -> Optional[np.ndarray]:
        """Sub-window compaction plan for one page (docs/fusion.md).

        Intersects each shard's live ``merge_spans`` sub-ranges with the
        page's owned span; if the widest shard's live row count, padded
        to a power of two, is at most half the window, returns the
        int32 [shards, wc] gather-index table (-1 padding) for
        ``lowerable_windowed_grouped_compact``. Otherwise (dead gaps too
        small to pay for the gather) returns None and the page streams
        contiguously as before.
        """
        if not plan.pruned or plan.shard_spans is None \
                or plan.shard_bounds is None:
            return None
        per_shard: List[np.ndarray] = []
        need = 0
        for (start, end), spans in zip(plan.shard_bounds,
                                       plan.shard_spans, strict=True):
            plo = start + page * window
            phi = min(plo + window, end)
            segs = [np.arange(max(int(lo), plo), min(int(hi), phi),
                              dtype=np.int64)
                    for lo, hi in spans]
            segs = [a for a in segs if a.size]
            live = np.concatenate(segs) if segs \
                else np.empty((0,), dtype=np.int64)
            per_shard.append(live)
            need = max(need, int(live.size))
        wc = _pow2(max(need, 1))
        if wc > window // 2:
            return None
        sel = np.full((len(per_shard), wc), -1, dtype=np.int32)
        for s, live in enumerate(per_shard):
            sel[s, :live.size] = live.astype(np.int32)
        return sel

    def _launch_plan(
        self, tp: TriplePattern, patterns: List[List[TriplePattern]],
        plan: WindowPlan, count_only: bool = False,
    ) -> List[Tuple[np.ndarray, int]]:
        """Execute one planned (grouped) request: fast path or windows."""
        g = len(patterns)
        m = max(len(p) for p in patterns)
        window = self.window
        if not plan.pages:
            # no window can contain a match on any shard (empty range,
            # or every sub-range empty): zero launches, cnt = 0
            return [(_EMPTY, 0)] * g

        # Small-work fast path: the plan's relevant rows cannot pay for
        # window dispatches -- evaluate the groups over the pruned block
        # gathered from the (host) oracle store instead.
        if (self.store is not None
                and 0 < plan.candidate_rows <= self.fast_path_rows):
            block = self._gather_fast_block(
                tp, [p for group in patterns for p in group])
            self.launches.append(LaunchRecord(
                cand_streamed=int(block.shape[0]), pat_slots=0, groups=g,
                pruned=plan.pruned, cand_full=plan.range_rows,
                fast_path=True))
            with phase(self.counters, "order"):
                return select_block_numpy(block, tp, patterns,
                                          count_only=count_only)

        # pad the grid to bucketed static shapes (bounded jit cache):
        # groups to a power of two, pattern slots to the kernel m-tile.
        gpad = _pow2(g)
        mp = kops.padded_pattern_slots(m)
        with phase(self.counters, "gather"):
            pats, valid, base_vec = marshal_pattern_grid(tp, patterns,
                                                         gpad, mp)
        comps = tp.as_tuple()
        wild = [i for i, c in enumerate(comps) if is_var(c)]
        wild_cols = tuple(wild) or (0,)  # dummy column when fully bound
        idx = self.fed.indexes[plan.order]
        routed = self.fed.placement is not None
        fn = None if routed else self.fed.lowerable_windowed_grouped(
            window, gpad, wild_cols=wild_cols)

        kept: List[List[np.ndarray]] = [[] for _ in range(g)]
        firsts: List[List[np.ndarray]] = [[] for _ in range(g)]
        cnt_total = np.zeros((g,), dtype=np.int64)
        n_launched = 0
        with enable_x64(True):
            lo_dev = jnp.asarray(plan.lo_key, jnp.int64)
            hi_dev = jnp.asarray(plan.hi_key, jnp.int64)
            pats_dev = jnp.asarray(pats)
            valid_dev = jnp.asarray(valid)
            bv_dev = jnp.asarray(base_vec)
            if routed:
                # workload-aware placement: explicit per-shard spans
                # with replica ranges routed to one owner each
                spans = self._routed_spans(plan)
                chunks, rounds = _chop_spans(spans, window)
                rfn = self.fed.lowerable_windowed_routed(
                    window, gpad, wild_cols=wild_cols)
                for r in range(rounds):
                    span_lo = np.zeros((len(chunks),), dtype=np.int32)
                    span_hi = np.zeros((len(chunks),), dtype=np.int32)
                    for s, cs in enumerate(chunks):
                        if r < len(cs):
                            span_lo[s], span_hi[s] = cs[r]
                    # read each round before the next one launches:
                    # holding every round's outputs on the device at
                    # once ran a v5e out of HBM on WatDiv-10M ranges
                    pages, first, counts, cnts = rfn(
                        idx.triples, idx.valid, pats_dev, valid_dev,
                        bv_dev, jnp.asarray(span_lo),
                        jnp.asarray(span_hi))
                    self.launches.append(LaunchRecord(
                        cand_streamed=window, pat_slots=gpad * mp,
                        groups=g, pruned=plan.pruned, cand_full=window))
                    n_launched += 1
                    for s, cs in enumerate(chunks):
                        if r < len(cs):
                            a, b = cs[r]
                            self.shard_launches[s] += 1
                            self.shard_pages[s] += 1
                            self.shard_rows[s] += b - a
                    counts = np.asarray(counts)
                    cnt_total += np.asarray(cnts)[:, :g].sum(axis=0)
                    if count_only:
                        continue
                    pages = np.asarray(pages)
                    first = np.asarray(first)
                    for s in range(pages.shape[0]):
                        for gi in range(g):
                            n = int(counts[s, gi])
                            if n:
                                kept[gi].append(pages[s, gi, :n])
                                firsts[gi].append(first[s, gi, :n])
            else:
                for page_idx in plan.pages:
                    row_sel = self._page_row_sel(plan, window, page_idx)
                    if row_sel is not None:
                        # sub-window compaction: gather only the live rows
                        wc = row_sel.shape[1]
                        cfn = self.fed.lowerable_windowed_grouped_compact(
                            wc, gpad, wild_cols=wild_cols)
                        pages, first, counts, cnts = cfn(
                            idx.triples, idx.valid, pats_dev, valid_dev,
                            bv_dev, jnp.asarray(row_sel))
                        self.launches.append(LaunchRecord(
                            cand_streamed=wc, pat_slots=gpad * mp, groups=g,
                            pruned=True, cand_full=window,
                            reclaimed_rows=window - wc))
                    else:
                        pages, first, counts, cnts, _range_len = fn(
                            idx.triples, idx.valid, idx.keys,
                            pats_dev, valid_dev, bv_dev, lo_dev, hi_dev,
                            jnp.asarray(page_idx, jnp.int32))
                        self.launches.append(LaunchRecord(
                            cand_streamed=window, pat_slots=gpad * mp,
                            groups=g, pruned=plan.pruned, cand_full=window))
                    n_launched += 1
                    self._charge_shard_page(plan, window, page_idx,
                                            row_sel=row_sel)
                    counts = np.asarray(counts)
                    cnt_total += np.asarray(cnts)[:, :g].sum(axis=0)
                    if count_only:
                        continue   # cnt-only: skip the gather epilogue
                    pages = np.asarray(pages)
                    first = np.asarray(first)
                    for s in range(pages.shape[0]):
                        for gi in range(g):
                            n = int(counts[s, gi])
                            if n:
                                kept[gi].append(pages[s, gi, :n])
                                firsts[gi].append(first[s, gi, :n])
        if self.heat is not None and n_launched:
            self.heat.record(plan.order, plan.lo_key, plan.hi_key,
                             launches=n_launched,
                             rows=plan.candidate_rows,
                             pages=len(plan.pages))

        out: List[Tuple[np.ndarray, int]] = []
        for gi in range(g):
            if count_only or not kept[gi]:
                out.append((_EMPTY, int(cnt_total[gi])))
                continue
            proj = np.concatenate(kept[gi], axis=0)
            first_g = np.concatenate(firsts[gi], axis=0)
            # reconstruct full triples from the request's bound
            # components (the wire carried only unbound columns)
            full = np.empty((proj.shape[0], 3), dtype=np.int32)
            for i, c in enumerate(comps):
                if is_var(c):
                    full[:, i] = proj[:, wild.index(i)]
                else:
                    full[:, i] = c
            with phase(self.counters, "order"):
                kept_g = stream_order(full, first_g, patterns[gi])
            out.append((kept_g, int(cnt_total[gi])))
        return out

    # -- cross-pattern fusion (docs/fusion.md) -------------------------------

    def select_fused(self, segments: Sequence[FusedSegment]
                     ) -> List[List[Tuple[np.ndarray, int]]]:
        """Serve S heterogeneous segments with fused windowed launches.

        The sharded twin of ``KernelSelector.select_fused``: segments
        are planned individually (residency skips, ``plan_windows``
        page skipping, and the small-work fast path behave exactly as
        unfused), then the launch-worthy segments are grouped BY INDEX
        ORDER -- only same-order segments can share a window slice pass
        -- and each order group runs ``lowerable_windowed_fused``: per
        round, one launch streams one window of every active segment.
        Segments with fewer planned pages go inactive (page index -1)
        in later rounds. ``fusion_legality`` refusals and singleton
        order groups fall back to per-segment ``_launch_plan`` on the
        already-computed plans.
        """
        results: List[List[Optional[Tuple[np.ndarray, int]]]] = [
            [None] * len(seg.omegas) for seg in segments]
        work: List[Tuple[int, List[List[TriplePattern]],
                         List[Optional[np.ndarray]], List[int],
                         WindowPlan]] = []
        for si, seg in enumerate(segments):
            patterns = seg.patterns
            if patterns is None:
                patterns = [instantiate_patterns(seg.tp, om)
                            for om in seg.omegas]
            live = consult_segment(self.fragments, seg, results[si],
                                   self.launches)
            if not live:
                continue
            omegas_live = [seg.omegas[i] for i in live]
            pats_live = [patterns[i] for i in live]
            all_insts = [p for group in pats_live for p in group]
            plan = self.fed.plan_windows(seg.tp, all_insts, self.window)
            if not plan.pages:
                finish_segment(self.fragments, seg, omegas_live,
                               [(_EMPTY, 0)] * len(live), results[si],
                               live)
                continue
            if (self.store is not None
                    and 0 < plan.candidate_rows <= self.fast_path_rows):
                block = self._gather_fast_block(seg.tp, all_insts)
                self.launches.append(LaunchRecord(
                    cand_streamed=int(block.shape[0]), pat_slots=0,
                    groups=len(live), pruned=plan.pruned,
                    cand_full=plan.range_rows, fast_path=True))
                with phase(self.counters, "order"):
                    fresh = select_block_numpy(block, seg.tp, pats_live,
                                               count_only=seg.count_only)
                finish_segment(self.fragments, seg, omegas_live, fresh,
                               results[si], live)
                continue
            work.append((si, pats_live, omegas_live, live, plan))
        if not work:
            return results

        # Legality: declared dependencies refuse the whole batch
        # (conservative -- DaCe-style fusion only for independent
        # states); geometry ceilings are checked per order group below.
        dep_reason = fusion_legality(
            [segments[w[0]] for w in work], stream_rows=0, slot_table=0)

        by_order: Dict[str, List] = {}
        for item in work:
            by_order.setdefault(item[4].order, []).append(item)
        wp = _pow2(self.window)
        for items in by_order.values():
            s_pad = _pow2(len(items))
            g_pad = _pow2(max(len(w[3]) for w in items))
            m_max = max(max(len(p) for p in w[1]) for w in items)
            mp = kops.padded_pattern_slots(m_max)
            reason = dep_reason or fusion_legality(
                [segments[w[0]] for w in items],
                stream_rows=s_pad * wp,
                slot_table=s_pad * g_pad * mp)
            if (len(items) == 1 or reason is not None
                    or self.fed.placement is not None):
                # documented fallback: per-segment grouped launches on
                # the plans already in hand (no re-probe, no re-plan).
                # A workload-aware placement always falls back: the
                # fused step derives spans on device from (lo, hi) keys
                # and cannot honor per-shard replica routing.
                for si, pats_live, omegas_live, live, plan in items:
                    seg = segments[si]
                    fresh = self._launch_plan(seg.tp, pats_live, plan,
                                              count_only=seg.count_only)
                    finish_segment(self.fragments, seg, omegas_live,
                                   fresh, results[si], live)
                continue
            self._launch_fused_order(items, segments, results,
                                     s_pad, g_pad, mp)
        return results

    def _launch_fused_order(self, items, segments, results,
                            s_pad: int, g_pad: int, mp: int) -> None:
        """Run one order group's fused windowed rounds + epilogue."""
        window = self.window
        wp = _pow2(window)
        s = len(items)
        order = items[0][4].order
        idx = self.fed.indexes[order]
        pats_all = np.full((s_pad, g_pad, mp, 3), -1, dtype=np.int32)
        valid_all = np.zeros((s_pad, g_pad, mp), dtype=np.int32)
        base_vecs = np.zeros((s_pad, 8), dtype=np.int32)
        lo = np.zeros((s_pad,), dtype=np.int64)
        hi = np.full((s_pad,), -1, dtype=np.int64)  # empty range for pads
        for wi, (si, pats_live, _om, _live, plan) in enumerate(items):
            with phase(self.counters, "gather"):
                p_grid, v_grid, bv = marshal_pattern_grid(
                    segments[si].tp, pats_live, g_pad, mp)
            pats_all[wi], valid_all[wi], base_vecs[wi] = p_grid, v_grid, bv
            lo[wi], hi[wi] = plan.lo_key, plan.hi_key
        fn = self.fed.lowerable_windowed_fused(window, s_pad, g_pad)
        rounds = max(len(w[4].pages) for w in items)
        for _si, _pl, _om, _live, plan in items:
            if self.heat is not None and plan.pages:
                self.heat.record(plan.order, plan.lo_key, plan.hi_key,
                                 launches=len(plan.pages),
                                 rows=plan.candidate_rows,
                                 pages=len(plan.pages))
            for page_idx in plan.pages:
                self._charge_shard_page(plan, window, page_idx)

        kept: Dict[Tuple[int, int], List[np.ndarray]] = {}
        firsts: Dict[Tuple[int, int], List[np.ndarray]] = {}
        cnt_total = np.zeros((s, g_pad), dtype=np.int64)
        with enable_x64(True):
            lo_dev = jnp.asarray(lo, jnp.int64)
            hi_dev = jnp.asarray(hi, jnp.int64)
            pats_dev = jnp.asarray(pats_all)
            valid_dev = jnp.asarray(valid_all)
            bvs_dev = jnp.asarray(base_vecs)
            for r in range(rounds):
                pi = np.full((s_pad,), -1, dtype=np.int32)
                for wi, item in enumerate(items):
                    pages = item[4].pages
                    if r < len(pages):
                        pi[wi] = pages[r]
                active = [wi for wi in range(s) if pi[wi] >= 0]
                page, first, counts, cnts = fn(
                    idx.triples, idx.valid, idx.keys, pats_dev,
                    valid_dev, bvs_dev, lo_dev, hi_dev, jnp.asarray(pi))
                counts = np.asarray(counts)
                cnt_total += np.asarray(cnts).sum(axis=0)[:s]
                self.launches.append(LaunchRecord(
                    cand_streamed=len(active) * wp,
                    pat_slots=g_pad * mp,
                    groups=sum(len(items[wi][3]) for wi in active),
                    pruned=any(items[wi][4].pruned for wi in active),
                    cand_full=len(active) * wp,
                    segments=len(active)))
                page = np.asarray(page)
                first = np.asarray(first)
                for wi in active:
                    if segments[items[wi][0]].count_only:
                        continue   # cnt-only segment: no row gather
                    for sh in range(page.shape[0]):
                        for gi in range(len(items[wi][3])):
                            n = int(counts[sh, wi, gi])
                            if n:
                                kept.setdefault((wi, gi), []).append(
                                    page[sh, wi, gi, :n])
                                firsts.setdefault((wi, gi), []).append(
                                    first[sh, wi, gi, :n])

        for wi, (si, pats_live, omegas_live, live, _plan) in \
                enumerate(items):
            seg = segments[si]
            fresh: List[Tuple[np.ndarray, int]] = []
            for gi in range(len(live)):
                cnt = int(cnt_total[wi, gi])
                rows = kept.get((wi, gi))
                if seg.count_only or not rows:
                    fresh.append((_EMPTY, cnt))
                    continue
                full = np.concatenate(rows, axis=0)
                first_g = np.concatenate(firsts[(wi, gi)], axis=0)
                with phase(self.counters, "order"):
                    kept_g = stream_order(full, first_g, pats_live[gi])
                fresh.append((kept_g, cnt))
            finish_segment(self.fragments, seg, omegas_live, fresh,
                           results[si], live)
