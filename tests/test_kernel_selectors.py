"""Kernel selector backend vs the numpy selector oracle.

The contract is *byte identity*: the Pallas bind-join selector path must
produce exactly the data-triple sequence (values AND order) and cnt
estimate of ``selectors.brtpf_select_with_cnt``, for every pattern/omega
shape, so that paging through ``BrTPFServer.handle`` is bit-for-bit
independent of the selector backend.
"""
import jax
import numpy as np
import pytest

from repro.core import (BrTPFServer, Request, ServerConfig, TriplePattern,
                        TripleStore, UNBOUND, brtpf_select_with_cnt,
                        encode_var)
from repro.core import kernel_selectors as ks
from repro.core import metrics
from repro.core.kernel_selectors import (FusedSegment, KernelSelector,
                                         select_block_numpy)
from repro.core.selectors import instantiate_patterns
from repro.kernels import ops as kops

V = encode_var

pytestmark = pytest.mark.tier1


def make_store(seed=0, n=500, terms=15):
    rng = np.random.default_rng(seed)
    return TripleStore(np.unique(
        rng.integers(0, terms, size=(n, 3)).astype(np.int32), axis=0))


def rand_omega(rng, m, v=2, terms=15, unbound_frac=0.3):
    om = rng.integers(0, terms, size=(m, v)).astype(np.int32)
    om[rng.random((m, v)) < unbound_frac] = UNBOUND
    return om


def assert_identical(store, tp, omega):
    got, gcnt = KernelSelector(store).select_with_cnt(tp, omega)
    want, wcnt = brtpf_select_with_cnt(store, tp, omega)
    assert got.dtype == want.dtype
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    assert gcnt == wcnt


class TestSelectorParity:
    def test_empty_omega_is_tpf_selector(self):
        assert_identical(make_store(), TriplePattern(V(0), 3, V(1)), None)
        assert_identical(make_store(), TriplePattern(V(0), 3, V(1)),
                         np.empty((0, 2), np.int32))

    def test_full_wildcard_pattern(self):
        rng = np.random.default_rng(1)
        assert_identical(make_store(1), TriplePattern(V(0), V(1), V(2)),
                         rand_omega(rng, 6, v=3))

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_typical_patterns(self, seed):
        rng = np.random.default_rng(seed)
        store = make_store(seed)
        for tp in [TriplePattern(V(0), 3, V(1)),
                   TriplePattern(5, V(0), V(1)),
                   TriplePattern(V(0), V(1), 7),
                   TriplePattern(5, 3, V(0))]:
            assert_identical(store, tp, rand_omega(rng, 6))

    def test_repeated_variable_patterns(self):
        rng = np.random.default_rng(4)
        store = make_store(4)
        assert_identical(store, TriplePattern(V(0), 2, V(0)),
                         rand_omega(rng, 5, v=1))
        assert_identical(store, TriplePattern(V(0), V(0), V(1)),
                         rand_omega(rng, 5))
        assert_identical(store, TriplePattern(V(0), V(0), V(0)),
                         rand_omega(rng, 5, v=1))

    def test_single_mapping_changes_stream_index(self):
        # One instantiated pattern whose chosen index differs from the
        # base pattern's: the stream order is the instantiation's index
        # order, which the kernel epilogue must reproduce.
        store = make_store(5)
        om = np.array([[5, UNBOUND]], np.int32)
        assert_identical(store, TriplePattern(V(0), 3, V(1)), om)

    def test_max_mpr_sized_omega(self):
        rng = np.random.default_rng(6)
        assert_identical(make_store(6, n=800),
                         TriplePattern(V(0), 3, V(1)), rand_omega(rng, 30))

    def test_no_matches_and_empty_store(self):
        rng = np.random.default_rng(7)
        assert_identical(make_store(7), TriplePattern(V(0), 14, 9999),
                         rand_omega(rng, 6))
        empty = TripleStore(np.empty((0, 3), np.int32))
        assert_identical(empty, TriplePattern(V(0), 3, V(1)),
                         rand_omega(rng, 6))

    def test_duplicate_mappings_dedup(self):
        store = make_store(8)
        om = np.array([[2, UNBOUND], [2, UNBOUND], [UNBOUND, 4],
                       [2, UNBOUND]], np.int32)
        assert_identical(store, TriplePattern(V(0), 3, V(1)), om)

    def test_cnt_counts_cross_stream_duplicates(self):
        # cnt sums per-stream sizes (Definition 2 over-count), while the
        # data sequence dedups -- both must match the oracle exactly.
        store = TripleStore(np.array(
            [[1, 2, 3], [1, 2, 4], [5, 2, 3]], np.int32))
        om = np.array([[1, UNBOUND], [UNBOUND, 3]], np.int32)
        tp = TriplePattern(V(0), 2, V(1))
        got, cnt = KernelSelector(store).select_with_cnt(tp, om)
        want, wcnt = brtpf_select_with_cnt(store, tp, om)
        np.testing.assert_array_equal(got, want)
        assert cnt == wcnt
        assert cnt > got.shape[0]  # (1,2,3) is in both streams


class TestBatchedSelector:
    def test_batch_matches_solo(self):
        rng = np.random.default_rng(9)
        store = make_store(9, n=700)
        tp = TriplePattern(V(0), 3, V(1))
        omegas = [None, rand_omega(rng, 6), rand_omega(rng, 30),
                  np.array([[5, UNBOUND]], np.int32)]
        sel = KernelSelector(store)
        results = sel.select_same_pattern(tp, omegas)
        assert len(sel.launches) == 1
        assert sel.launches[0].groups == len(omegas)
        for (data, cnt), om in zip(results, omegas, strict=True):
            want, wcnt = brtpf_select_with_cnt(store, tp, om)
            np.testing.assert_array_equal(data, want)
            assert cnt == wcnt


class TestServerBackendParity:
    def _servers(self, seed=10):
        store = make_store(seed, n=900)
        return (BrTPFServer(store, page_size=20,
                            selector_backend="numpy"),
                BrTPFServer(store, page_size=20,
                            selector_backend="kernel"))

    def test_paging_determinism_across_backends(self):
        rng = np.random.default_rng(11)
        s_np, s_k = self._servers()
        tp = TriplePattern(V(0), 3, V(1))
        om = rand_omega(rng, 8)
        om[0] = UNBOUND  # one unrestricted mapping -> full-match stream
        # (multi-page fragment, exercising paging determinism)
        page = 0
        while True:
            f_np = s_np.handle(Request(tp, om, page))
            f_k = s_k.handle(Request(tp, om, page))
            np.testing.assert_array_equal(f_np.data, f_k.data)
            assert f_np.cnt == f_k.cnt
            assert f_np.has_next == f_k.has_next
            assert f_np.triples_received == f_k.triples_received
            if not f_np.has_next:
                break
            page += 1
        assert page >= 1  # the fragment actually paged

    def test_tpf_requests_match_too(self):
        s_np, s_k = self._servers(12)
        tp = TriplePattern(V(0), 3, V(1))
        f_np = s_np.handle(Request(tp, None, 0))
        f_k = s_k.handle(Request(tp, None, 0))
        np.testing.assert_array_equal(f_np.data, f_k.data)
        assert f_np.cnt == f_k.cnt

    def test_handle_batch_parity_and_coalescing(self):
        rng = np.random.default_rng(13)
        store = make_store(13, n=900)
        tp_a = TriplePattern(V(0), 3, V(1))
        tp_b = TriplePattern(V(0), 5, V(1))
        reqs = [Request(tp_a, rand_omega(rng, 6), 0),
                Request(tp_a, rand_omega(rng, 6), 0),
                Request(tp_b, rand_omega(rng, 6), 0),
                Request(tp_a, None, 0)]

        solo = BrTPFServer(store, selector_backend="kernel")
        want = [solo.handle(r) for r in reqs]

        batched = BrTPFServer(store, selector_backend="kernel")
        got = batched.handle_batch(reqs)
        for f_w, f_g in zip(want, got, strict=True):
            np.testing.assert_array_equal(f_w.data, f_g.data)
            assert f_w.cnt == f_g.cnt
            assert f_w.has_next == f_g.has_next

        # cross-pattern fusion (docs/fusion.md): the tp_a group and the
        # tp_b solo segment share ONE fused launch vs 4 unbatched
        assert batched.counters.kernel_launches == 1
        assert batched.counters.fused_launches == 1
        assert batched.counters.fused_segments == 2
        assert solo.counters.kernel_launches == 4
        # every member rode the fused launch, tp_b solo included
        assert batched.counters.kernel_batched_requests == 4

        # with fusion off, handle_batch still coalesces same-pattern
        # requests: one grouped launch per pattern (the PR 1 contract)
        unfused = BrTPFServer(
            store, ServerConfig(selector_backend="kernel",
                                fuse_patterns=False))
        got_unfused = unfused.handle_batch(reqs)
        for f_w, f_g in zip(want, got_unfused, strict=True):
            np.testing.assert_array_equal(f_w.data, f_g.data)
        assert unfused.counters.kernel_launches == 2
        assert unfused.counters.fused_launches == 0
        assert unfused.counters.kernel_batched_requests == 3
        # identical transfer/request accounting either way
        assert (batched.counters.num_requests
                == solo.counters.num_requests)
        assert (batched.counters.data_received
                == solo.counters.data_received)
        assert (batched.counters.server_lookups
                == solo.counters.server_lookups)

    def test_handle_batch_numpy_backend_falls_through(self):
        rng = np.random.default_rng(14)
        store = make_store(14)
        server = BrTPFServer(store, selector_backend="numpy")
        tp = TriplePattern(V(0), 3, V(1))
        reqs = [Request(tp, rand_omega(rng, 4), 0),
                Request(tp, rand_omega(rng, 4), 0)]
        frags = server.handle_batch(reqs)
        for r, f in zip(reqs, frags, strict=True):
            want, wcnt = brtpf_select_with_cnt(store, tp, r.omega)
            np.testing.assert_array_equal(
                f.data, want[:server.page_size])
            assert f.cnt == wcnt
        assert server.counters.kernel_launches == 0


def bound_omega(m, offset=0):
    """m mappings binding ?0 to distinct terms: m instantiated patterns."""
    om = np.full((m, 2), UNBOUND, np.int32)
    om[:, 0] = np.arange(offset, offset + m)
    return om


TP_A = TriplePattern(V(0), 3, V(1))
TP_B = TriplePattern(V(0), 5, V(1))


def launch(sel, kind, m=3):
    """One launch of ``kind`` whose longest group has m patterns: G = 2
    (grouped), G = 1 (count-only), or S = 2 segments of G = 1 (fused)."""
    if kind == "grouped":
        return sel.select_same_pattern(TP_A, [bound_omega(m), None])
    if kind == "count_only":
        return sel.select_count(TP_A, bound_omega(m))
    return sel.select_fused([FusedSegment(TP_A, [bound_omega(m)]),
                             FusedSegment(TP_B, [None])])


class TestLaunchProgram:
    """Each selector launch is one compiled program, keyed by the
    launch's shape, with the pattern grid's M padded to a power of two."""

    store = make_store(21, n=3000, terms=40)

    @pytest.mark.parametrize("kind", ["grouped", "count_only", "fused"])
    def test_one_program_call_per_launch(self, kind, monkeypatch):
        name = "_fused_launch" if kind == "fused" else "_grouped_launch"
        program = getattr(ks, name)
        calls = []

        def spy(*args, **kwargs):
            # the host phase the call runs in
            calls.append(getattr(getattr(metrics._open, "phase", None),
                                 "field", None))
            return program(*args, **kwargs)

        monkeypatch.setattr(ks, name, spy)
        # the kernel wrappers run only while the program is traced
        eager = []
        for fn in ("bindjoin_grouped", "bindjoin_fused", "tpf_match"):
            def watch(cand, *a, _fn=fn, _orig=getattr(kops, fn), **k):
                if not isinstance(cand, jax.core.Tracer):
                    eager.append(_fn)
                return _orig(cand, *a, **k)
            monkeypatch.setattr(kops, fn, watch)
        sel = KernelSelector(self.store)
        launch(sel, kind)
        assert calls == ["dispatch_ns"]
        assert eager == []
        (rec,) = sel.launches
        assert rec.segments == (2 if kind == "fused" else 1)

    @pytest.mark.parametrize("kind", ["grouped", "count_only", "fused"])
    def test_program_is_keyed_by_power_of_two_m(self, kind):
        program = ks._fused_launch if kind == "fused" else ks._grouped_launch
        program.clear_cache()
        sel = KernelSelector(self.store)
        sizes = []
        for m in (3, 4, 5):
            launch(sel, kind, m)
            sizes.append(program._cache_size())
        # the same candidate bucket and G for all three
        assert len({(r.cand_streamed, r.groups, r.segments)
                    for r in sel.launches}) == 1
        assert sizes == [1, 1, 2]

    EDGES = [pytest.param(1, True, id="tpf"),
             *[pytest.param(m, False, id=f"m{m}") for m in (1, 3, 4, 5, 30)]]

    @pytest.mark.parametrize("m,tpf", EDGES)
    def test_grouped_matches_block_numpy(self, m, tpf):
        """Two groups, the longer with m patterns, against the numpy
        evaluation of the same block; count-only too."""
        omegas = ([None, None] if tpf
                  else [bound_omega(m), bound_omega(max(m // 2, 1), 7)])
        patterns = [instantiate_patterns(TP_A, om) for om in omegas]
        assert max(len(p) for p in patterns) == m
        want = select_block_numpy(self.store.candidate_range(TP_A).triples,
                                  TP_A, patterns)
        sel = KernelSelector(self.store)
        got = sel.select_same_pattern(TP_A, omegas)
        (rec,) = sel.launches
        assert not rec.fast_path
        for (data, cnt), (w_data, w_cnt) in zip(got, want, strict=True):
            assert data.dtype == w_data.dtype
            np.testing.assert_array_equal(data, w_data)
            assert cnt == w_cnt
        assert KernelSelector(self.store).select_count(
            TP_A, omegas[0]) == want[0][1]

    @pytest.mark.parametrize("m,tpf", EDGES)
    def test_fused_matches_block_numpy(self, m, tpf):
        """Two fused segments of different M and G against the numpy
        evaluation of each segment's block."""
        segs = [FusedSegment(TP_A, [None if tpf else bound_omega(m)]),
                FusedSegment(TP_B, [None, None] if tpf
                             else [bound_omega(3, 11), bound_omega(1, 20)])]
        sel = KernelSelector(self.store)
        got = sel.select_fused(segs)
        (rec,) = sel.launches
        assert rec.segments == 2
        for seg, res in zip(segs, got, strict=True):
            patterns = [instantiate_patterns(seg.tp, om) for om in seg.omegas]
            want = select_block_numpy(
                self.store.candidate_range(seg.tp).triples, seg.tp,
                patterns)
            for (data, cnt), (w_data, w_cnt) in zip(res, want, strict=True):
                assert data.dtype == w_data.dtype
                np.testing.assert_array_equal(data, w_data)
                assert cnt == w_cnt


def edge_store():
    """Ranges of known sizes: 1,024 rows of predicate 3 (one whole
    candidate bucket), 300 of 5 (two fused tiles, the last partly
    filled), 512 of 6 and 256 of 7 (whole tiles)."""
    parts = [np.stack([np.arange(n), np.full(n, p), 2000 + np.arange(n) % 7],
                      axis=1)
             for p, n in ((3, 1024), (5, 300), (6, 512), (7, 256))]
    return TripleStore(np.concatenate(parts).astype(np.int32))


def assert_matches_block_numpy(store, tp, omegas, got):
    patterns = [instantiate_patterns(tp, om) for om in omegas]
    want = select_block_numpy(store.candidate_range(tp).triples, tp,
                              patterns)
    for (data, cnt), (w_data, w_cnt) in zip(got, want, strict=True):
        assert data.dtype == w_data.dtype
        np.testing.assert_array_equal(data, w_data)
        assert cnt == w_cnt


class TestPackedLayout:
    """Each launch's inputs and outputs cross as one buffer each way;
    the edges of that layout, against the numpy evaluation."""

    store = edge_store()

    def test_group_with_no_kept_row(self):
        # the full wildcard: the bucket's padding rows would match it
        # but for the row count sent with the candidates
        tp = TriplePattern(V(0), V(1), V(2))
        absent = np.array([[5000, UNBOUND, UNBOUND]], np.int32)
        omegas = [bound_omega(4), absent, None]
        sel = KernelSelector(self.store)
        got = sel.select_same_pattern(tp, omegas)
        (rec,) = sel.launches
        assert rec.groups == 3 and not rec.fast_path
        assert rec.cand_rows == 2092 < rec.cand_streamed
        assert got[1] == (got[1][0], 0) and got[1][0].shape == (0, 3)
        assert_matches_block_numpy(self.store, tp, omegas, got)

    def test_group_whose_kept_count_equals_the_capacity(self):
        tp = TriplePattern(V(0), 3, V(1))
        omegas = [None, bound_omega(3)]
        sel = KernelSelector(self.store)
        got = sel.select_same_pattern(tp, omegas)
        (rec,) = sel.launches
        assert len(got[0][0]) == rec.cand_streamed == rec.cand_rows == 1024
        assert_matches_block_numpy(self.store, tp, omegas, got)

    @pytest.mark.parametrize("preds,rows", [
        # 300 rows in 2 tiles, 4 tiles in all
        pytest.param((5, 6), (300, 512), id="partly_filled_last_tile"),
        # the full wildcard, which padding rows would match: 2,092 rows
        # in 9 tiles, the last partly filled
        pytest.param((V(1), 6), (2092, 512), id="wildcard_last_tile"),
        # 3 whole tiles padded to 4: one tile of no segment
        pytest.param((7, 6), (256, 512), id="dead_tile"),
    ])
    def test_fused_tiles(self, preds, rows):
        segs = [FusedSegment(TriplePattern(V(0), p, V(2)),
                             [None, bound_omega(2, 100 * i)])
                for i, p in enumerate(preds)]
        sel = KernelSelector(self.store)
        got = sel.select_fused(segs)
        (rec,) = sel.launches
        assert rec.segments == 2 and rec.cand_rows == sum(rows)
        tiles = sum(-(-r // ks.FUSED_BT) for r in rows)
        assert rec.cand_streamed == ks._pow2_at_least(tiles) * ks.FUSED_BT
        assert (rows[0] % ks.FUSED_BT != 0) or tiles & (tiles - 1)
        for seg, res in zip(segs, got, strict=True):
            assert_matches_block_numpy(self.store, seg.tp, seg.omegas, res)
