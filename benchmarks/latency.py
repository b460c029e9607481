"""Closed-loop latency/SLO load generator for the serving edge (PR 7).

Every prior benchmark measured req/s of in-process method calls; this
one drives the WIRE. N closed-loop :class:`~repro.core.client.
AsyncBrTPFClient`s (1/4/16/64) execute the WatDiv workload over a
transport that round-trips every request and response through the
brtpf/v1 envelope (``core/wire.py``):

* ``loopback`` -- :class:`~repro.serving.transport.LoopbackTransport`
  over one async front end: the serialization boundary without HTTP
  framing. This is the CI-gated configuration (``budgets.json``
  ``loopback:p95_latency_ms`` max / ``loopback:req_per_s`` min) --
  wall-clock dependent, so the bounds are deliberately loose, but a
  10x serialization regression trips them on any machine.
* ``asgi`` -- :class:`~repro.serving.transport.AsgiTransport` over the
  ASGI app (optionally with a replica router): the complete HTTP layer
  minus the socket.

Each transport is wrapped in a per-request timer; the run reports the
canonical latency schema (``core/metrics.py``: p50/p95/p99/mean ms +
closed-loop req/s) per concurrency level plus the *saturation*
throughput (max req/s over the sweep -- the knee of the closed-loop
curve), and persists a per-PR trajectory entry (p50/p95/p99 at c=16,
saturation req/s) to ``BENCH_throughput.json`` next to the throughput
series.
"""
from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Optional

from repro.core import AsyncBrTPFClient, latency_summary
from repro.core.batching import AsyncBrTPFServer
from repro.core.config import ServerConfig
from repro.core.sim import split_workload
from repro.serving.http import app_from_config
from repro.serving.transport import AsgiTransport, LoopbackTransport

from .common import BenchConfig, FAST_PATH_ROWS, dataset, emit, persist, \
    use_compile_cache, workload
from .throughput import BUDGETS_PATH, SHARD_WINDOW, check_budgets

CLIENT_COUNTS = [1, 4, 16, 64]


class _TimingTransport:
    """Per-request latency probe around any transport (the closed-loop
    clients call ``handle`` exactly once per wire request)."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.samples_s: List[float] = []

    @property
    def max_mpr(self) -> int:
        return self.inner.max_mpr

    async def handle(self, req):
        t0 = time.perf_counter()
        frag = await self.inner.handle(req)
        self.samples_s.append(time.perf_counter() - t0)
        return frag

    async def metrics(self) -> dict:
        return await self.inner.metrics()

    async def aclose(self) -> None:
        await self.inner.aclose()


def _make_transport(kind: str, config: ServerConfig,
                    batch_window_s: float, replicas: int):
    store = dataset().store
    if kind == "loopback":
        front = AsyncBrTPFServer.from_config(
            store, config, batch_window_s=batch_window_s)
        return _TimingTransport(LoopbackTransport(front))
    if kind == "asgi":
        app = app_from_config(store, config,
                              batch_window_s=batch_window_s,
                              replicas=replicas)
        return _TimingTransport(AsgiTransport(app))
    raise ValueError(f"unknown transport kind {kind!r}")


def run_level(kind: str, clients: int, wl, request_budget: int,
              config: ServerConfig, batch_window_s: float = 2e-3,
              replicas: int = 1) -> Dict:
    """One closed-loop level: ``clients`` concurrent AsyncBrTPFClients
    over one timed transport; returns the canonical latency schema plus
    wire metrics read back over the same transport."""
    transport = _make_transport(kind, config, batch_window_s, replicas)
    per_client = split_workload(wl, clients)

    async def main():
        cs = [AsyncBrTPFClient(transport, request_budget=request_budget)
              for _ in range(clients)]
        try:
            await asyncio.gather(
                *[c.run_workload(w)
                  for c, w in zip(cs, per_client, strict=True)])
            return await transport.metrics()
        finally:
            await transport.aclose()

    t0 = time.perf_counter()
    wire_metrics = asyncio.run(main())
    wall = time.perf_counter() - t0
    out = latency_summary(transport.samples_s, wall_s=wall)
    counters = wire_metrics["counters"]
    out.update({
        "clients": clients,
        "transport": kind,
        "replicas": replicas,
        "wall_s": wall,
        # served-side accounting, read over the wire (GET /metrics keys
        # == in-process metrics_snapshot keys)
        "server_requests": counters["num_requests"],
        "launches": counters["kernel_launches"],
        "launches_skipped": counters["launches_skipped"],
        "batched_requests": counters["kernel_batched_requests"],
    })
    return out


def run_sweep(kinds=("loopback", "asgi"), smoke: bool = False,
              full: bool = False, replicas: int = 1) -> Dict:
    cfg = BenchConfig.default()
    config = ServerConfig(selector_backend="kernel",
                          fast_path_rows=FAST_PATH_ROWS,
                          shard_window=SHARD_WINDOW)
    wl = list(workload())
    if smoke:
        wl = wl[:6]
        counts = [1, 8]
    else:
        if not full:
            wl = wl[:12]
        counts = CLIENT_COUNTS
    out: Dict = {}
    for kind in kinds:
        for n in counts:
            r = run_level(kind, n, wl, cfg.request_budget, config,
                          replicas=replicas if kind == "asgi" else 1)
            out[(kind, n)] = r
            emit(
                f"latency/{kind}_c{n}", 0.0,
                f"p50={r['p50_latency_ms']:.2f}ms;"
                f"p95={r['p95_latency_ms']:.2f}ms;"
                f"p99={r['p99_latency_ms']:.2f}ms;"
                f"req_per_s={r['req_per_s']:.0f};"
                f"requests={r['requests']};"
                f"launches_skipped={r['launches_skipped']};"
                f"batched={r['batched_requests']};"
                f"wall={r['wall_s']:.1f}s")
        # closed-loop saturation: the knee of the req/s-vs-clients curve
        peak = max((out[(kind, n)] for n in counts),
                   key=lambda r: r["req_per_s"])
        out[(kind, "saturation")] = {
            "req_per_s": peak["req_per_s"],
            "clients": peak["clients"],
        }
        emit(f"latency/{kind}_saturation", 0.0,
             f"req_per_s={peak['req_per_s']:.0f};"
             f"at_clients={peak['clients']}")
    return out


def headline_metrics(out: Dict) -> Dict:
    """Per-PR trajectory entry: the SLO quantities at a fixed load
    point (c=16 loopback) + saturation throughput per transport."""
    h: Dict = {}
    anchor = out.get(("loopback", 16)) or out.get(("loopback", 8))
    if anchor:
        h.update({
            "latency_loopback_p50_ms": anchor["p50_latency_ms"],
            "latency_loopback_p95_ms": anchor["p95_latency_ms"],
            "latency_loopback_p99_ms": anchor["p99_latency_ms"],
            "latency_loopback_clients": anchor["clients"],
        })
    for kind in ("loopback", "asgi"):
        sat = out.get((kind, "saturation"))
        if sat:
            h[f"saturation_{kind}_req_per_s"] = sat["req_per_s"]
    return h


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="closed-loop wire latency / saturation sweep")
    parser.add_argument("--full", action="store_true")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny loopback run + budget gate (CI)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="server replicas behind the ASGI router")
    args = parser.parse_args(argv)
    use_compile_cache()
    if args.smoke:
        out = run_sweep(kinds=("loopback",), smoke=True)
        # budget gate reads the c=8 smoke level under the plain name
        results = {"loopback": out[("loopback", 8)]}
        failures = check_budgets(results, path=BUDGETS_PATH)
        return 1 if failures else 0
    out = run_sweep(smoke=False, full=args.full, replicas=args.replicas)
    path = persist("throughput", out, headline=headline_metrics(out),
                   section="latency")
    print(f"# persisted -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
