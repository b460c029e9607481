"""Phase spans and counters of the served path (``core/metrics.py``
``phase``).

Every host phase of a flush is a ``brtpf.<name>`` profiler span and a
``<name>_ns`` counter on the server's ``Counters`` or the front end's
``BatchStats``, exported at ``GET /metrics``. The tests drive the
kernel backend on the CPU (Pallas in interpret mode) and check that the
counters advance, that the flush phases never sum past the flush, that
the byte counters equal what one launch of known shape moves, that the
front end's queue and timer clocks read what the loop did, and that the
spans reach a profiler trace nested as the code nests them.
"""
import asyncio
import json
import threading
import time

import numpy as np
import pytest

from repro.core import (AsyncBrTPFServer, BrTPFServer, Counters, Request,
                        ServerConfig, TriplePattern, TripleStore, UNBOUND,
                        encode_var)
from repro.core.batching import BatchStats
from repro.core.kernel_selectors import (FusedSegment, KernelSelector,
                                         _pow2_at_least)
from repro.core.metrics import phase
from repro.core.selectors import instantiate_patterns
from repro.core.wire import dumps
from repro.kernels import ops as kops
from repro.serving.http import TestClient, app_from_config, request_asgi

V = encode_var

pytestmark = pytest.mark.tier1

# the phases that partition a flush's host time, on Counters
FLUSH_PHASES = ("pages_ns", "instantiate_ns", "range_ns", "prune_ns",
                "gather_ns", "h2d_ns", "dispatch_ns", "pull_ns", "order_ns")
COUNTER_FIELDS = FLUSH_PHASES + ("h2d_bytes", "d2h_bytes", "h2d_copies",
                                 "d2h_copies", "omega_requests",
                                 "kernel_pat_used")
BATCH_FIELDS = ("flush_ns", "queue_wait_ns", "timer_late_ns",
                "resident_ns", "wire_ns")


def make_store(seed=0, n=600, terms=15):
    rng = np.random.default_rng(seed)
    return TripleStore(np.unique(
        rng.integers(0, terms, size=(n, 3)).astype(np.int32), axis=0))


def mixed_requests(seed=0):
    """A heterogeneous batch: TPF requests over three patterns and brTPF
    requests (Omega attached) over two, so one window fuses segments of
    both kinds."""
    rng = np.random.default_rng(seed)
    reqs = [Request(TriplePattern(V(0), p, V(1))) for p in (2, 3, 4)]
    for p in (3, 5):
        for _ in range(3):
            om = rng.integers(0, 15, size=(4, 2)).astype(np.int32)
            om[rng.random((4, 2)) < 0.3] = UNBOUND
            reqs.append(Request(TriplePattern(V(0), p, V(1)), om))
    return reqs


async def _post_all(app, reqs):
    return await asyncio.gather(*[
        request_asgi(app, "POST", "/fragment", body=dumps(r.to_wire()))
        for r in reqs])


def serve_mixed(fuse=True, window=0.02):
    """Mixed batches through the ASGI app, then the same requests
    again (now resident pages); returns (app, responses)."""
    app = app_from_config(
        make_store(), ServerConfig(page_size=20, max_mpr=30,
                                   selector_backend="kernel",
                                   fuse_patterns=fuse),
        batch_window_s=window, max_batch=64)
    reqs = mixed_requests()
    # solo requests take handle()'s own launch inside the page loop
    solo = [Request(TriplePattern(V(0), 6, V(1)))]

    async def main():
        async def late_batch():
            first = asyncio.ensure_future(_post_all(app, reqs))
            await asyncio.sleep(window / 2)
            # hold the loop past the window's end, as an inline flush
            # of another front end on the same loop would
            time.sleep(2 * window)
            return await first
        out = await late_batch()
        out += await _post_all(app, solo)
        out += await _post_all(app, reqs)    # resident now
        return out

    loop = asyncio.new_event_loop()
    try:
        resps = loop.run_until_complete(main())
        loop.run_until_complete(app.aclose())
    finally:
        loop.close()
    assert {r.status_code for r in resps} == {200}
    return app, resps


# ---------------------------------------------------------------------------
# The helper
# ---------------------------------------------------------------------------


class TestPhase:
    def test_counts_elapsed_ns(self):
        stats = Counters()
        with phase(stats, "range"):
            time.sleep(0.01)
        assert stats.range_ns >= 10_000_000
        assert stats.gather_ns == 0

    def test_nested_same_stats_is_self_time(self):
        stats = Counters()
        with phase(stats, "pages"):
            with phase(stats, "dispatch"):
                time.sleep(0.02)
        assert stats.dispatch_ns >= 20_000_000
        assert stats.pages_ns < stats.dispatch_ns

    def test_nested_other_stats_stays_inclusive(self):
        outer, inner = BatchStats(), Counters()
        with phase(outer, "flush"):
            with phase(inner, "pages"):
                with phase(inner, "order"):
                    time.sleep(0.02)
        assert outer.flush_ns >= inner.pages_ns + inner.order_ns
        assert inner.order_ns >= 20_000_000

    @pytest.mark.parametrize("outer,inner", [("range", "prune"),
                                             ("pages", "instantiate")])
    def test_omega_phases_are_self_time(self, outer, inner):
        stats = Counters()
        with phase(stats, outer):
            with phase(stats, inner):
                time.sleep(0.02)
        assert getattr(stats, inner + "_ns") >= 20_000_000
        assert getattr(stats, outer + "_ns") < getattr(stats,
                                                       inner + "_ns")

    def test_charges_when_the_block_raises(self):
        stats = Counters()
        with pytest.raises(KeyError):
            with phase(stats, "order"):
                raise KeyError("x")
        assert stats.order_ns > 0

    def test_threads_nest_independently(self):
        stats = [Counters(), Counters()]

        def work(i):
            with phase(stats[i], "pages"):
                with phase(stats[i], "gather"):
                    time.sleep(0.02)

        threads = [threading.Thread(target=work, args=(i,)) for i in (0, 1)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for s in stats:
            assert s.gather_ns >= 20_000_000
            assert s.pages_ns < s.gather_ns


# ---------------------------------------------------------------------------
# The served path
# ---------------------------------------------------------------------------


def test_every_counter_advances_on_a_mixed_batch():
    app, _ = serve_mixed()
    front = app.backend
    counters = front.server.counters
    for name in COUNTER_FIELDS:
        assert getattr(counters, name) > 0, name
    for name in BATCH_FIELDS:
        assert getattr(front.stats, name) > 0, name
    assert counters.fused_launches > 0
    assert front.stats.fast_path > 0


@pytest.mark.parametrize("fuse", [True, False])
def test_flush_phases_sum_within_the_flush(fuse):
    app, _ = serve_mixed(fuse=fuse)
    counters = app.backend.server.counters
    inside = sum(getattr(counters, name) for name in FLUSH_PHASES)
    assert 0 < inside <= app.backend.stats.flush_ns
    assert counters.kernel_launches > 0


def test_h2d_and_d2h_bytes_of_a_grouped_launch():
    sel = KernelSelector(make_store(1))
    tp = TriplePattern(V(0), 3, V(1))
    rng = np.random.default_rng(7)
    omegas = [rng.choice(15, size=(m, 1), replace=False).astype(np.int32)
              for m in (3, 5)]
    sel.select_same_pattern(tp, omegas)
    (rec,) = sel.launches
    tpad, g = rec.cand_streamed, rec.groups
    m = _pow2_at_least(max(len(instantiate_patterns(tp, om))
                           for om in omegas))  # the grid's padded M
    sent = (tpad * 3 * 4                   # cand, once
            + g * m * 3 * 4 + g * m * 4    # pattern grid and its validity
            + 8 * 4                        # base-pattern vector
            + 4)                           # the count of real rows
    assert sel.counters.h2d_bytes == sent
    back = (g * 4 + g * 4                  # kept counts, cnt
            + g * tpad * 4                 # compacted rows
            + g * tpad * 4)                # their first matching patterns
    assert sel.counters.d2h_bytes == back
    for name in ("range_ns", "gather_ns", "h2d_ns", "dispatch_ns",
                 "pull_ns", "order_ns"):
        assert getattr(sel.counters, name) > 0, name


def _omegas(seed=3, sizes=(3, 5)):
    rng = np.random.default_rng(seed)
    return [rng.choice(15, size=(m, 1), replace=False).astype(np.int32)
            for m in sizes]


def test_pruning_is_charged_to_prune_not_range(monkeypatch):
    store = make_store(1)
    sel = KernelSelector(store)
    subranges = store.subranges

    def slow(*args, **kw):
        time.sleep(0.1)
        return subranges(*args, **kw)

    monkeypatch.setattr(store, "subranges", slow)
    sel.select_same_pattern(TriplePattern(V(0), 3, V(1)), _omegas())
    assert sel.counters.prune_ns >= 100_000_000
    assert sel.counters.range_ns < 100_000_000


def test_instantiation_is_charged_on_the_served_path(monkeypatch):
    import repro.core.server as server_mod
    calls = []

    def slow(tp, omega):
        calls.append(omega)
        time.sleep(0.05)
        return instantiate_patterns(tp, omega)

    monkeypatch.setattr(server_mod, "instantiate_patterns", slow)
    server = BrTPFServer(make_store(1), ServerConfig(
        selector_backend="kernel", max_mpr=30))
    tp = TriplePattern(V(0), 3, V(1))
    reqs = [Request(tp, om) for om in _omegas()]
    reqs.append(Request(TriplePattern(V(0), 4, V(1))))
    server.handle_batch(reqs)
    assert len(calls) == 3
    c = server.counters
    assert c.instantiate_ns >= 150_000_000
    assert c.pages_ns + c.range_ns + c.order_ns < 150_000_000


def test_pattern_slots_used_of_a_grouped_launch():
    sel = KernelSelector(make_store(1))
    tp = TriplePattern(V(0), 3, V(1))
    omegas = _omegas()
    sel.select_same_pattern(tp, omegas)
    (rec,) = sel.launches
    insts = [instantiate_patterns(tp, om) for om in omegas]
    assert rec.pat_used == sum(len(p) for p in insts) == 8
    assert rec.pat_slots == len(omegas) * kops.padded_pattern_slots(
        _pow2_at_least(5))
    # the roofline's least bytes never exceed what the launch is handed
    assert 12 * (rec.cand_rows + rec.pat_used) <= sel.counters.h2d_bytes


def test_pattern_slots_used_of_a_fused_launch():
    sel = KernelSelector(make_store(2))
    om = _omegas(sizes=(4,))[0]
    segs = [FusedSegment(tp=TriplePattern(V(0), 3, V(1)), omegas=[om]),
            FusedSegment(tp=TriplePattern(V(0), 4, V(1)), omegas=[None])]
    sel.select_fused(segs)
    (rec,) = sel.launches
    assert rec.segments == 2
    # segment 0 streams its pruned sub-ranges, segment 1 its whole range
    rows = [sel.store.subranges(segs[0].tp, om).rows,
            len(sel.store.candidate_range(segs[1].tp))]
    assert rec.pruned and rec.cand_rows == sum(rows)
    # each row meets its own segment's grid: 4 filled slots or 1
    assert rec.pat_used == round((4 * rows[0] + 1 * rows[1]) / sum(rows))
    assert 0 < rec.pat_used <= rec.pat_slots


def test_omega_requests_count_what_the_wire_carries():
    app = app_from_config(make_store(), ServerConfig(
        selector_backend="kernel", max_mpr=30), batch_window_s=0.01)
    tp = TriplePattern(V(0), 3, V(1))
    reqs = ([Request(tp, om) for om in _omegas(sizes=(3, 5, 2))]
            + [Request(tp), Request(tp, np.empty((0, 1), dtype=np.int32))])

    async def main():
        await _post_all(app, reqs)
        await app.aclose()

    asyncio.run(main())
    c = app.backend.server.counters
    assert c.omega_requests == 3
    assert c.mappings_sent == 10


def test_h2d_and_d2h_bytes_of_a_fused_launch():
    store = make_store(2)
    sel = KernelSelector(store)
    segs = [FusedSegment(tp=TriplePattern(V(0), p, V(1)), omegas=[None])
            for p in (2, 3, 4)]
    sel.select_fused(segs)
    (rec,) = sel.launches
    assert rec.segments == 3
    t_pad = rec.cand_streamed
    s_pad, g_pad, m_max = _pow2_at_least(3), 1, 1
    sent = (t_pad * 3 * 4                  # cand, once
            + 2 * t_pad // 256 * 4         # segment and real rows of a tile
            + s_pad * g_pad * m_max * 16   # pattern grid and validity
            + s_pad * 8 * 4)               # base-pattern vectors
    assert sel.counters.h2d_bytes == sent
    back = (2 * s_pad * g_pad * 4          # kept counts, cnt per segment
            + g_pad * t_pad * 4            # compacted rows
            + g_pad * t_pad * 4)           # their first matching patterns
    assert sel.counters.d2h_bytes == back


@pytest.mark.parametrize("kind", ["grouped", "count_only", "fused"])
def test_one_upload_and_one_pull_per_launch(kind):
    sel = KernelSelector(make_store(2))
    tp = TriplePattern(V(0), 3, V(1))
    om = _omegas(sizes=(4,))[0]
    for launches in (1, 2):
        if kind == "grouped":
            sel.select_same_pattern(tp, [om, None])
        elif kind == "count_only":
            sel.select_count(tp, om)
        else:
            sel.select_fused([
                FusedSegment(tp=tp, omegas=[om]),
                FusedSegment(tp=TriplePattern(V(0), 4, V(1)),
                             omegas=[None])])
        assert len(sel.launches) == launches
        assert sel.launches[-1].segments == (2 if kind == "fused" else 1)
        assert sel.counters.h2d_copies == launches
        assert sel.counters.d2h_copies == launches


def test_queue_wait_covers_the_window_on_a_timer_flush():
    window = 0.05
    front = AsyncBrTPFServer(
        BrTPFServer(make_store(), ServerConfig(selector_backend="kernel")),
        batch_window_s=window, max_batch=64)

    async def main():
        await front.handle(Request(TriplePattern(V(0), 3, V(1))))
        await front.aclose()

    asyncio.run(main())
    assert front.stats.timer_flushes == 1
    # the loop may run a timer up to its clock resolution early
    assert front.stats.queue_wait_ns >= window * 1e9 - 1_000
    assert front.stats.flush_ns > 0


class SlowServer(BrTPFServer):
    def handle_batch(self, reqs):
        time.sleep(0.1)
        return super().handle_batch(reqs)


def _timer_lateness(block: bool) -> int:
    """Lateness of one front end's window timer while another front
    end on the same loop flushes inline (for 0.1 s) or not at all."""
    store = make_store()
    cfg = ServerConfig(selector_backend="kernel")
    front = AsyncBrTPFServer(BrTPFServer(store, cfg),
                             batch_window_s=0.01)
    other = AsyncBrTPFServer(SlowServer(store, cfg), batch_window_s=0.0)
    req = Request(TriplePattern(V(0), 3, V(1)))

    async def main():
        work = [front.handle(req)]
        if block:
            work.append(other.handle(Request(TriplePattern(V(0), 4,
                                                           V(1)))))
        await asyncio.gather(*work)

    asyncio.run(main())
    assert front.stats.timer_flushes == 1
    return front.stats.timer_late_ns


def test_timer_late_grows_when_a_flush_blocks_the_loop():
    calm = _timer_lateness(block=False)
    blocked = _timer_lateness(block=True)
    # the window ends 10 ms into the other front end's 100-ms flush
    assert blocked >= 80_000_000
    assert blocked > calm


def test_metrics_body_equals_the_inprocess_snapshot():
    store = make_store()
    app = app_from_config(store, ServerConfig(selector_backend="kernel"),
                          batch_window_s=0.01)
    with TestClient(app) as client:
        for req in mixed_requests()[:4]:
            assert client.post("/fragment",
                               json_body=req.to_wire()).status_code == 200
        wire = client.get("/metrics").json()
        local = json.loads(dumps(app.backend.metrics_snapshot()))
    assert wire == local
    assert "routes" not in wire
    for name in COUNTER_FIELDS:
        assert wire["counters"][name] > 0, name
    for name in BATCH_FIELDS:
        assert name in wire["batch"]
    assert wire["batch"]["wire_ns"] > 0
    assert wire["batch"]["flush_ns"] > 0


def test_router_sums_the_replicas_phase_counters():
    app = app_from_config(make_store(),
                          ServerConfig(selector_backend="kernel"),
                          batch_window_s=0.01, replicas=2)
    router = app.backend

    async def main():
        await _post_all(app, mixed_requests())
        await router.aclose()

    asyncio.run(main())
    snap = router.metrics_snapshot()
    for name in COUNTER_FIELDS:
        assert snap["counters"][name] == sum(
            r["counters"][name] for r in snap["replicas"]), name
    assert snap["counters"]["h2d_bytes"] > 0
    assert snap["router"]["wire_ns"] > 0


# ---------------------------------------------------------------------------
# The profiler trace
# ---------------------------------------------------------------------------


def _host_spans(log_dir):
    """brtpf.* spans per host thread line: {line: [(name, start, end)]}."""
    import glob
    import os

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns), int(e.end_ns))
                     for e in line.events if e.name.startswith("brtpf.")]
            if spans:
                lines[(plane.name, line.name)] = spans
    return lines


def test_spans_reach_the_host_plane_nested_in_the_flush(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve_mixed()
    finally:
        jax.profiler.stop_trace()
    lines = _host_spans(str(tmp_path))
    names = {n for spans in lines.values() for n, _, _ in spans}
    for name in ("flush", "pages", "instantiate", "range", "prune",
                 "gather", "h2d", "dispatch", "pull", "order", "resident",
                 "wire"):
        assert "brtpf." + name in names, name
    nested = 0
    for spans in lines.values():
        flushes = [(a, b) for n, a, b in spans if n == "brtpf.flush"]
        for n, a, b in spans:
            if n in ("brtpf.h2d", "brtpf.dispatch", "brtpf.pull"):
                assert any(fa <= a and b <= fb for fa, fb in flushes), n
                nested += 1
    assert nested >= 3
