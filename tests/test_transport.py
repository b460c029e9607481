"""Serving edge (PR 7): wire schema, ASGI app, transports, router.

The contract under test (docs/serving.md): a fragment served over HTTP
is byte-identical to the same request handled in-process on every
selector backend; the GET-parameter and POST-envelope encodings decode
through one code path; maxMpR violations surface as HTTP 414 and
malformed envelopes as 400; ``GET /metrics`` speaks the same canonical
snapshot schema as ``metrics_snapshot()``; and ``ServerConfig`` is
equivalent to the deprecated per-kwarg constructor surface.

Replica-router tests live at the bottom; the deterministic ones
(routing/affinity/fleet parity with tiny batching windows) are tier1 --
failover-under-faults behavior is exercised in test_resilience.py.
"""
import asyncio
import json
import warnings

import numpy as np
import pytest

from repro.core import (AsyncBrTPFClient, BrTPFClient, BrTPFServer,
                        MaxMprExceeded, Request, ServerConfig,
                        TriplePattern, TripleStore, UNBOUND, WIRE_VERSION,
                        WireError, bgp_from_arrays, encode_var,
                        fragment_from_wire, fragment_to_wire,
                        metrics_snapshot, request_from_wire,
                        request_to_wire)
from repro.core.batching import AsyncBrTPFServer
from repro.core.wire import (ERROR_CODES, dumps, error_from_wire,
                             error_to_wire, loads)
from repro.serving.http import TestClient, app_from_config, create_app
from repro.serving.router import ReplicaRouter, stable_replica_index
from repro.serving.transport import AsgiTransport, LoopbackTransport

V = encode_var

TIER1 = pytest.mark.tier1


def make_store(seed=0, n=600, terms=18):
    rng = np.random.default_rng(seed)
    return TripleStore(np.unique(
        rng.integers(0, terms, size=(n, 3)).astype(np.int32), axis=0))


def rand_omega(rng, m, v=2, terms=18, unbound_frac=0.3):
    om = rng.integers(0, terms, size=(m, v)).astype(np.int32)
    om[rng.random((m, v)) < unbound_frac] = UNBOUND
    return om


def sample_requests(store, seed=3, count=24, max_mpr=30):
    """Mixed TPF/brTPF page requests whose patterns actually occur in
    the store (so pages are non-trivial)."""
    rng = np.random.default_rng(seed)
    triples = store.triples
    reqs = []
    for i in range(count):
        s, p, o = (int(x) for x in triples[rng.integers(len(triples))])
        shape = i % 4
        if shape == 0:
            tp = TriplePattern(V(0), p, o)
        elif shape == 1:
            tp = TriplePattern(s, p, V(0))
        elif shape == 2:
            tp = TriplePattern(V(0), p, V(1))
        else:
            tp = TriplePattern(V(0), V(1), o)
        omega = None
        if i % 3:
            omega = rand_omega(rng, int(rng.integers(1, max_mpr)),
                               v=len(tp.variables()))
        reqs.append(Request(pattern=tp, omega=omega, page=0))
    return reqs


def small_workload():
    return [
        ("q1", bgp_from_arrays([[V(0), 2, V(1)], [V(1), 3, V(2)]])),
        ("q2", bgp_from_arrays([[V(0), 1, V(1)], [V(0), 4, V(2)]])),
        ("q3", bgp_from_arrays([[V(0), 5, 7]])),
    ]


def canon(solutions):
    arr = np.asarray(solutions)
    if arr.size == 0:
        return arr.reshape(0, arr.shape[1] if arr.ndim == 2 else 0)
    return arr[np.lexsort(arr.T[::-1])]


# ---------------------------------------------------------------------------
# Wire schema round-trips (satellite 1)
# ---------------------------------------------------------------------------


class TestWireRoundTrip:
    pytestmark = TIER1

    def test_request_round_trip_brtpf(self):
        rng = np.random.default_rng(0)
        req = Request(pattern=TriplePattern(V(0), 3, V(1)),
                      omega=rand_omega(rng, 7), page=2)
        out = request_from_wire(loads(dumps(req.to_wire())))
        assert out.pattern == req.pattern
        assert out.page == req.page
        assert out.omega.dtype == np.int32
        assert np.array_equal(out.omega, req.omega)
        assert out.key() == req.key()

    def test_request_round_trip_tpf(self):
        req = Request(pattern=TriplePattern(5, V(0), V(1)))
        out = Request.from_wire(loads(dumps(request_to_wire(req))))
        assert out.omega is None
        assert out.key() == req.key()

    def test_request_wire_is_byte_stable(self):
        rng = np.random.default_rng(1)
        req = Request(pattern=TriplePattern(V(0), 2, 9),
                      omega=rand_omega(rng, 5, v=1), page=1)
        once = dumps(request_to_wire(req))
        twice = dumps(request_to_wire(request_from_wire(loads(once))))
        assert once == twice

    def test_fragment_round_trip_bytes(self):
        store = make_store()
        server = BrTPFServer(store, config=ServerConfig(page_size=20))
        req = sample_requests(store, count=1)[0]
        frag = server.handle(req)
        once = dumps(fragment_to_wire(frag))
        out = fragment_from_wire(loads(once))
        assert out.data.dtype == np.int32
        assert np.array_equal(out.data, np.asarray(frag.data))
        assert (out.cnt, out.page, out.page_size, out.has_next,
                out.meta_triples) == (frag.cnt, frag.page, frag.page_size,
                                      frag.has_next, frag.meta_triples)
        assert dumps(fragment_to_wire(out)) == once

    def test_envelope_carries_version(self):
        env = request_to_wire(Request(pattern=TriplePattern(1, 2, 3)))
        assert env["v"] == WIRE_VERSION
        assert env["kind"] == "request"

    @pytest.mark.parametrize("mutate", [
        lambda e: e.update(v="brtpf/v0"),
        lambda e: e.update(v=None),
        lambda e: e.update(kind="fragment"),
        lambda e: e.update(pattern=[1, 2]),
        lambda e: e.update(pattern="spo"),
        lambda e: e.update(page=-1),
        lambda e: e.update(page="0"),
        lambda e: e.update(omega=[[1, 2], [3]]),
        lambda e: e.update(omega=42),
    ])
    def test_malformed_request_rejected(self, mutate):
        env = request_to_wire(
            Request(pattern=TriplePattern(V(0), 2, 3),
                    omega=np.zeros((2, 1), dtype=np.int32)))
        mutate(env)
        with pytest.raises(WireError):
            request_from_wire(env)

    def test_invalid_json_rejected(self):
        with pytest.raises(WireError):
            loads(b"{not json")
        with pytest.raises(WireError):
            loads(b"[1,2,3]")

    def test_fragment_missing_field_rejected(self):
        store = make_store()
        server = BrTPFServer(store)
        env = fragment_to_wire(
            server.handle(Request(pattern=TriplePattern(V(0), 2, V(1)))))
        del env["cnt"]
        with pytest.raises(WireError):
            fragment_from_wire(env)

    def test_server_config_wire_round_trip(self):
        cfg = ServerConfig(page_size=25, max_mpr=10,
                           selector_backend="kernel", fast_path_rows=64)
        assert ServerConfig.from_wire(
            json.loads(dumps(cfg.to_wire()))) == cfg

    def test_request_timeout_ms_round_trips(self):
        req = Request(pattern=TriplePattern(V(0), 2, 3), timeout_ms=250.0)
        env = request_to_wire(req)
        assert env["timeout_ms"] == 250.0
        out = request_from_wire(loads(dumps(env)))
        assert out.timeout_ms == 250.0
        # the deadline is delivery metadata, NOT cache identity
        assert out.key() == Request(pattern=req.pattern).key()

    def test_request_without_timeout_is_byte_identical(self):
        """New field must not perturb the brtpf/v1 bytes of existing
        traffic (it is emitted only when set)."""
        req = Request(pattern=TriplePattern(V(0), 2, 3))
        env = request_to_wire(req)
        assert "timeout_ms" not in env
        assert request_from_wire(loads(dumps(env))).timeout_ms is None

    @pytest.mark.parametrize("bad", [0, -5, "100", True, [100]])
    def test_invalid_timeout_ms_rejected(self, bad):
        env = request_to_wire(Request(pattern=TriplePattern(1, 2, 3)))
        env["timeout_ms"] = bad
        with pytest.raises(WireError):
            request_from_wire(env)


class TestErrorEnvelope:
    """Wire error schema (docs/serving.md error-code table)."""

    pytestmark = TIER1

    def test_round_trip_all_codes(self):
        for code in ERROR_CODES:
            env = error_to_wire(503, "busy", retryable=True, code=code,
                                retry_after_ms=12.5)
            out = error_from_wire(loads(dumps(env)))
            assert out["status"] == 503
            assert out["error"] == "busy"
            assert out["retryable"] is True
            assert out["code"] == code
            assert out["retry_after_ms"] == 12.5

    def test_wire_is_byte_stable(self):
        env = error_to_wire(504, "deadline", retryable=True,
                            code="DEADLINE_EXCEEDED")
        once = dumps(env)
        decoded = error_from_wire(loads(once))
        again = dumps(error_to_wire(decoded["status"], decoded["error"],
                                    retryable=decoded["retryable"],
                                    code=decoded["code"],
                                    retry_after_ms=decoded["retry_after_ms"]))
        assert once == again

    def test_optional_fields_emitted_only_when_set(self):
        """Pre-PR-10 consumers must see pre-PR-10 bytes for plain
        errors: code/retry_after_ms appear only when provided."""
        env = error_to_wire(400, "bad request")
        assert "code" not in env and "retry_after_ms" not in env
        out = error_from_wire(loads(dumps(env)))
        assert out["code"] is None
        assert out["retry_after_ms"] is None
        assert out["retryable"] is False

    def test_unknown_code_rejected_at_encode(self):
        with pytest.raises(ValueError):
            error_to_wire(500, "boom", code="EXPLODED")

    @pytest.mark.parametrize("mutate", [
        lambda e: e.update(kind="fragment"),
        lambda e: e.update(status="503"),
        lambda e: e.pop("error"),
        lambda e: e.update(code="NOT_A_CODE"),
        lambda e: e.update(retryable="yes"),
        lambda e: e.update(retry_after_ms=-1),
    ])
    def test_malformed_error_rejected(self, mutate):
        env = error_to_wire(503, "busy", retryable=True,
                            code="QUEUE_SATURATED", retry_after_ms=5.0)
        mutate(env)
        with pytest.raises(WireError):
            error_from_wire(env)


# hypothesis-gated stability sweep (optional dep, like test_pruning.py)
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    MAX_TERMS = 9

    @st.composite
    def wire_requests(draw):
        comps = []
        nvars = 0
        for _ in range(3):
            if draw(st.booleans()):
                comps.append(V(nvars))
                nvars += 1
            else:
                comps.append(draw(st.integers(0, MAX_TERMS - 1)))
        omega = None
        page = draw(st.integers(0, 3))
        if nvars and draw(st.booleans()):
            m = draw(st.integers(0, 6))
            rows = draw(st.lists(
                st.tuples(*[st.integers(-1, MAX_TERMS)] * nvars),
                min_size=m, max_size=m))
            om = np.asarray(rows, dtype=np.int32).reshape(m, nvars)
            om[om < 0] = UNBOUND
            omega = om
        timeout_ms = draw(st.one_of(
            st.none(),
            st.floats(min_value=0.5, max_value=60_000.0,
                      allow_nan=False, allow_infinity=False)))
        return Request(pattern=TriplePattern(*comps), omega=omega,
                       page=page, timeout_ms=timeout_ms)

    @pytest.mark.tier1
    class TestHypothesisWireStability:
        @settings(max_examples=60, deadline=None)
        @given(req=wire_requests())
        def test_round_trip_preserves_key_and_bytes(self, req):
            once = dumps(request_to_wire(req))
            out = request_from_wire(loads(once))
            assert out.key() == req.key()
            assert dumps(request_to_wire(out)) == once
            assert out.timeout_ms == req.timeout_ms


# ---------------------------------------------------------------------------
# ServerConfig vs legacy kwargs (satellite 2)
# ---------------------------------------------------------------------------


class TestServerConfig:
    pytestmark = TIER1

    def test_legacy_kwargs_deprecated_but_equivalent(self):
        store = make_store()
        cfg = ServerConfig(page_size=17, max_mpr=9,
                           meta_triples_per_page=5, fast_path_rows=32)
        modern = BrTPFServer(store, config=cfg)
        with pytest.warns(DeprecationWarning):
            legacy = BrTPFServer(store, page_size=17, max_mpr=9,
                                 meta_triples_per_page=5,
                                 fast_path_rows=32)
        assert legacy.config == modern.config == cfg
        for req in sample_requests(store, count=8, max_mpr=9):
            a = dumps(fragment_to_wire(modern.handle(req)))
            b = dumps(fragment_to_wire(legacy.handle(req)))
            assert a == b

    def test_config_plus_legacy_kwarg_is_an_error(self):
        with pytest.raises(TypeError):
            BrTPFServer(make_store(), config=ServerConfig(), page_size=10)

    def test_invalid_config_values(self):
        with pytest.raises(ValueError):
            ServerConfig(selector_backend="gpu")
        with pytest.raises(ValueError):
            ServerConfig(page_size=0)
        with pytest.raises(ValueError):
            ServerConfig(max_mpr=0)

    def test_defaults_unchanged_without_warning(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            server = BrTPFServer(make_store())
        assert server.config == ServerConfig()

    def test_async_front_end_from_config(self):
        cfg = ServerConfig(page_size=13, max_mpr=7)
        front = AsyncBrTPFServer.from_config(make_store(), cfg)
        assert front.max_mpr == 7
        assert front.server.config == cfg


# ---------------------------------------------------------------------------
# ASGI app over the TestClient (tentpole + satellite 4)
# ---------------------------------------------------------------------------


CFG = ServerConfig(page_size=20, max_mpr=12)


@pytest.fixture()
def store():
    return make_store()


@pytest.fixture()
def client(store):
    with TestClient(app_from_config(store, CFG,
                                    batch_window_s=1e-3)) as tc:
        yield tc


class TestHttpApp:
    pytestmark = TIER1

    def test_service_description(self, client):
        resp = client.get("/")
        assert resp.status_code == 200
        desc = resp.json()
        assert desc["v"] == WIRE_VERSION
        assert desc["max_mpr"] == CFG.max_mpr
        assert "fragment" in desc["endpoints"]

    def test_post_fragment_byte_identical_to_inprocess(self, store,
                                                       client):
        oracle = BrTPFServer(store, config=CFG)
        for req in sample_requests(store, max_mpr=CFG.max_mpr):
            resp = client.post("/fragment", json_body=req.to_wire())
            assert resp.status_code == 200
            assert resp.headers["content-type"] == "application/json"
            assert resp.content == dumps(
                fragment_to_wire(oracle.handle(req)))

    def test_get_and_post_encodings_agree(self, store, client):
        for req in sample_requests(store, count=9, max_mpr=CFG.max_mpr):
            params = {"s": req.pattern.s, "p": req.pattern.p,
                      "o": req.pattern.o, "page": req.page}
            if req.omega is not None:
                params["omega"] = json.dumps(req.omega.tolist())
                params["omega_vars"] = req.omega.shape[1]
            get = client.get("/fragment", params=params)
            post = client.post("/fragment", json_body=req.to_wire())
            assert get.status_code == post.status_code == 200
            assert get.content == post.content

    def test_over_max_mpr_is_414(self, client):
        rng = np.random.default_rng(5)
        req = Request(pattern=TriplePattern(V(0), 2, V(1)),
                      omega=rand_omega(rng, CFG.max_mpr + 1))
        resp = client.post("/fragment", json_body=req.to_wire())
        assert resp.status_code == 414
        assert resp.json()["kind"] == "error"

    def test_malformed_body_is_400(self, client):
        env = request_to_wire(Request(pattern=TriplePattern(1, 2, 3)))
        env["v"] = "tpf/v9"
        assert client.post("/fragment", json_body=env).status_code == 400
        resp = client.request("GET", "/fragment",
                              params={"s": "1", "p": "x", "o": "3"})
        assert resp.status_code == 400

    def test_unknown_path_and_method(self, client):
        assert client.get("/nope").status_code == 404
        assert client.post("/metrics").status_code == 405
        assert client.request("DELETE", "/fragment").status_code == 405

    def test_metrics_same_schema_as_inprocess(self, store, client):
        for req in sample_requests(store, count=6, max_mpr=CFG.max_mpr):
            client.post("/fragment", json_body=req.to_wire())
        wire = client.get("/metrics").json()
        local = json.loads(dumps(client.app.backend.metrics_snapshot()))
        assert wire == local
        assert wire["v"] == WIRE_VERSION
        assert wire["counters"]["num_requests"] == 6
        assert "batch" in wire


@pytest.mark.parametrize("backend,extra", [
    pytest.param("numpy", {}, marks=TIER1),
    pytest.param("kernel", {"fast_path_rows": 4}, marks=TIER1),
    pytest.param("sharded", {"shard_window": 64}),
])
def test_http_parity_across_selector_backends(backend, extra):
    """The ISSUE's acceptance bar: HTTP fragments byte-identical to
    in-process ``handle`` on every selector backend."""
    store = make_store(seed=7)
    cfg = ServerConfig(page_size=25, max_mpr=16,
                       selector_backend=backend, **extra)
    oracle = BrTPFServer(store, config=cfg)
    with TestClient(app_from_config(store, cfg,
                                    batch_window_s=1e-3)) as tc:
        for req in sample_requests(store, seed=11, count=12,
                                   max_mpr=cfg.max_mpr):
            resp = tc.post("/fragment", json_body=req.to_wire())
            assert resp.status_code == 200
            assert resp.content == dumps(
                fragment_to_wire(oracle.handle(req)))


# ---------------------------------------------------------------------------
# Transport parity: loopback == ASGI == in-process oracle
# ---------------------------------------------------------------------------


class TestTransportParity:
    pytestmark = TIER1

    def _oracle(self, store, cfg):
        server = BrTPFServer(store, config=cfg)
        out = {}
        for name, bgp in small_workload():
            res = BrTPFClient(server, max_mpr=cfg.max_mpr).execute(bgp)
            out[name] = (canon(res.solutions), res.num_requests)
        return out

    def _run_transport(self, make_transport):
        async def main():
            transport = make_transport()
            try:
                out = {}
                client = AsyncBrTPFClient(transport)
                for name, bgp in small_workload():
                    res = await client.execute(bgp)
                    out[name] = (canon(res.solutions), res.num_requests)
                return out, await transport.metrics()
            finally:
                await transport.aclose()
        return asyncio.run(main())

    def test_loopback_and_asgi_match_oracle(self):
        store = make_store(seed=9)
        cfg = ServerConfig(page_size=30)
        expected = self._oracle(store, cfg)

        def loopback():
            return LoopbackTransport(AsyncBrTPFServer.from_config(
                store, cfg, batch_window_s=1e-3))

        def asgi():
            return AsgiTransport(app_from_config(store, cfg,
                                                 batch_window_s=1e-3))

        for factory in (loopback, asgi):
            got, metrics = self._run_transport(factory)
            assert set(got) == set(expected)
            for name in expected:
                sols, nreq = expected[name]
                assert np.array_equal(got[name][0], sols), name
                assert got[name][1] == nreq, name
            total = sum(nreq for _, nreq in expected.values())
            assert metrics["counters"]["num_requests"] == total
            # wire boundary charged the attached mappings exactly once
            assert metrics["counters"]["mappings_sent"] > 0

    def test_asgi_transport_maps_414(self):
        store = make_store()
        cfg = ServerConfig(max_mpr=4)

        async def main():
            transport = AsgiTransport(app_from_config(
                store, cfg, batch_window_s=1e-3))
            rng = np.random.default_rng(2)
            req = Request(pattern=TriplePattern(V(0), 2, V(1)),
                          omega=rand_omega(rng, 9))
            try:
                with pytest.raises(MaxMprExceeded):
                    await transport.handle(req)
            finally:
                await transport.aclose()
        asyncio.run(main())


# ---------------------------------------------------------------------------
# Replica router. The deterministic routing/affinity/parity tests are
# tier1 (tiny batching windows keep them fast); only the full
# ASGI-wrapped fleet test stays out of the fast gate.
# ---------------------------------------------------------------------------


class TestReplicaRouter:
    @TIER1
    def test_stable_replica_index_deterministic(self):
        tp = (V(0), 3, 7)
        assert stable_replica_index(tp, 4) == stable_replica_index(tp, 4)
        hits = {stable_replica_index((s, 2, V(0)), 4)
                for s in range(64)}
        assert len(hits) > 1  # patterns spread across the fleet

    @TIER1
    def test_pattern_affinity_pins_requests(self):
        store = make_store()
        router = ReplicaRouter(store, ServerConfig(), replicas=3,
                               batch_window_s=1e-3)
        req = Request(pattern=TriplePattern(V(0), 2, V(1)))
        idxs = {router.route(req) for _ in range(10)}
        assert len(idxs) == 1
        asyncio.run(router.aclose())

    @TIER1
    def test_round_robin_advances(self):
        store = make_store()
        router = ReplicaRouter(store, ServerConfig(), replicas=3,
                               policy="round_robin", batch_window_s=1e-3)
        req = Request(pattern=TriplePattern(V(0), 2, V(1)))
        assert [router.route(req) for _ in range(6)] == [0, 1, 2, 0, 1, 2]
        asyncio.run(router.aclose())

    @TIER1
    def test_invalid_construction(self):
        with pytest.raises(ValueError):
            ReplicaRouter(make_store(), replicas=0)
        with pytest.raises(ValueError):
            ReplicaRouter(make_store(), policy="sticky")

    @TIER1
    @pytest.mark.parametrize("policy", ["pattern", "round_robin"])
    def test_fleet_parity_and_merged_metrics(self, policy):
        store = make_store(seed=13)
        cfg = ServerConfig(page_size=30)
        oracle = BrTPFServer(store, config=cfg)
        reqs = sample_requests(store, seed=17, count=18,
                               max_mpr=cfg.max_mpr)
        expected = [dumps(fragment_to_wire(oracle.handle(r)))
                    for r in reqs]

        async def main():
            router = ReplicaRouter(store, cfg, replicas=3, policy=policy,
                                   batch_window_s=1e-3)
            try:
                frags = [await router.handle(r) for r in reqs]
                return frags, router.metrics_snapshot()
            finally:
                await router.aclose()

        frags, snap = asyncio.run(main())
        assert [dumps(fragment_to_wire(f)) for f in frags] == expected
        assert snap["counters"]["num_requests"] == len(reqs)
        assert snap["router"]["policy"] == policy
        assert snap["router"]["replicas"] == 3
        assert sum(snap["router"]["requests_per_replica"]) == len(reqs)
        assert len(snap["replicas"]) == 3
        per_replica = sum(s["counters"]["num_requests"]
                          for s in snap["replicas"])
        assert per_replica == len(reqs)

    def test_router_behind_asgi_app(self):
        store = make_store()
        cfg = ServerConfig(page_size=25)
        oracle = BrTPFServer(store, config=cfg)
        with TestClient(app_from_config(store, cfg, batch_window_s=1e-3,
                                        replicas=2)) as tc:
            for req in sample_requests(store, seed=19, count=8,
                                       max_mpr=cfg.max_mpr):
                resp = tc.post("/fragment", json_body=req.to_wire())
                assert resp.status_code == 200
                assert resp.content == dumps(
                    fragment_to_wire(oracle.handle(req)))
            snap = tc.get("/metrics").json()
            assert snap["router"]["replicas"] == 2


def test_create_app_wraps_existing_backend():
    store = make_store()
    front = AsyncBrTPFServer.from_config(store, ServerConfig(max_mpr=6),
                                         batch_window_s=1e-3)
    with TestClient(create_app(front)) as tc:
        assert tc.get("/").json()["max_mpr"] == 6
