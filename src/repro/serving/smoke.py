"""Served-path smoke check: concurrent clients over the ASGI app, every
answer held to the numpy oracle.

``chip_smoke.py`` runs this at WatDiv scale on the chip; the test suite
rehearses it at a tiny scale on the CPU (Pallas interpret mode).

The served side is the path a deployment runs: ``app_from_config`` ->
:class:`~repro.serving.transport.AsgiTransport` (brtpf/v1 envelopes
through real ASGI messages) -> the async batching front end ->
``BrTPFServer.handle_batch`` -> the selector backend's kernels. There is
no retry layer, so a failed request fails its query. The oracle is the
sequential :class:`~repro.core.client.BrTPFClient` over a numpy-backend
:class:`~repro.core.server.BrTPFServer`: the same left-deep plan, so a
query that completes within the request budget on one side completes on
the other, with the identical solution set.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.bgp import BGP
from ..core.client import AsyncBrTPFClient, BrTPFClient, ExecutionResult
from ..core.config import ServerConfig
from ..core.server import BrTPFServer
from ..core.store import TripleStore
from .http import app_from_config
from .transport import AsgiTransport

Query = Tuple[str, BGP]


class StatusLog:
    """ASGI middleware counting the HTTP status of every ``/fragment``
    response (the smoke requires all of them to be 200)."""

    def __init__(self, app) -> None:
        self.app = app
        self.statuses: collections.Counter = collections.Counter()

    @property
    def max_mpr(self) -> int:
        return self.app.max_mpr

    async def aclose(self) -> None:
        await self.app.aclose()

    async def __call__(self, scope, receive, send) -> None:
        if scope.get("path") != "/fragment":
            await self.app(scope, receive, send)
            return

        async def record(message) -> None:
            if message["type"] == "http.response.start":
                self.statuses[message["status"]] += 1
            await send(message)

        await self.app(scope, receive, record)


@dataclasses.dataclass
class ServedRun:
    """What one pass of the queries over the served path returned."""

    results: List[ExecutionResult]
    first_query_s: float     # cold: includes every first-shape compile
    wall_s: float
    statuses: Dict[int, int]
    metrics: dict            # GET /metrics after the pass

    @property
    def requests(self) -> int:
        return sum(r.num_requests for r in self.results)


def oracle_answers(store: TripleStore, queries: Sequence[Query], *,
                   page_size: int, max_mpr: int,
                   request_budget: int) -> List[ExecutionResult]:
    """The numpy-backend reference, one query at a time."""
    server = BrTPFServer(store, ServerConfig(page_size=page_size,
                                             max_mpr=max_mpr))
    return [BrTPFClient(server, max_mpr=max_mpr,
                        request_budget=request_budget).execute(bgp)
            for _name, bgp in queries]


def within_budget(store: TripleStore, queries: Sequence[Query], count: int,
                  *, page_size: int, max_mpr: int, request_budget: int
                  ) -> Tuple[List[Query], List[ExecutionResult], int]:
    """The first ``count`` queries the oracle completes within the
    request budget (the harness analogue of the paper's query timeout),
    their oracle answers, and how many were passed over."""
    picked: List[Query] = []
    answers: List[ExecutionResult] = []
    skipped = 0
    for query in queries:
        if len(picked) == count:
            break
        (answer,) = oracle_answers(store, [query], page_size=page_size,
                                   max_mpr=max_mpr,
                                   request_budget=request_budget)
        if answer.timed_out:
            skipped += 1
            continue
        picked.append(query)
        answers.append(answer)
    return picked, answers, skipped


async def serve_queries(app, queries: Sequence[Query], *, clients: int,
                        request_budget: int) -> ServedRun:
    """Run the queries over ``app`` with ``clients`` concurrent clients
    (client c takes queries c, c + clients, ...). Leaves the app open,
    so a caller can repartition between passes."""
    log = StatusLog(app)
    transport = AsgiTransport(log)
    results: List[Optional[ExecutionResult]] = [None] * len(queries)
    done_s: List[float] = []
    t0 = time.perf_counter()

    async def client_loop(c: int) -> None:
        client = AsyncBrTPFClient(transport, request_budget=request_budget)
        for qi in range(c, len(queries), clients):
            results[qi] = await client.execute(queries[qi][1])
            done_s.append(time.perf_counter() - t0)

    await asyncio.gather(*(client_loop(c) for c in range(clients)))
    wall = time.perf_counter() - t0
    metrics = await transport.metrics()
    return ServedRun(results=results, first_query_s=min(done_s),
                     wall_s=wall, statuses=dict(log.statuses),
                     metrics=metrics)


def problems(run: ServedRun, oracle: Sequence[ExecutionResult],
             queries: Sequence[Query], *, expect_fused: bool = True,
             counters_before: Optional[dict] = None) -> List[str]:
    """Every way ``run`` falls short of the smoke's contract (empty list
    = pass). ``counters_before`` turns the cumulative wire counters into
    this pass's own (a second pass over the same app)."""
    out: List[str] = []
    for qi, (got, want) in enumerate(zip(run.results, oracle, strict=True)):
        name = queries[qi][0]
        if got.timed_out or want.timed_out:
            out.append(f"query {qi} ({name}) exceeded the request budget")
        elif not np.array_equal(got.solutions, want.solutions):
            out.append(f"query {qi} ({name}): {got.solutions.shape[0]} "
                       f"solutions served, oracle has "
                       f"{want.solutions.shape[0]} (or they differ)")
    if set(run.statuses) != {200}:
        out.append(f"HTTP statuses {run.statuses}, expected only 200")
    if run.statuses.get(200, 0) != run.requests:
        out.append(f"{run.statuses.get(200, 0)} responses for "
                   f"{run.requests} requests")
    before = counters_before or {}
    counters = {k: v - before.get(k, 0)
                for k, v in run.metrics["counters"].items()}
    if counters["kernel_launches"] <= 0:
        out.append("no kernel launch: the device path did not run")
    if expect_fused and counters["fused_launches"] <= 0:
        out.append("no fused launch: the heterogeneous-window path "
                   "did not run")
    if counters["fast_path_selects"]:
        out.append(f"{counters['fast_path_selects']} selections took the "
                   "numpy fast path")
    batch = run.metrics.get("batch", {})
    if batch.get("rejected") or batch.get("shed"):
        out.append(f"front end rejected {batch.get('rejected')} and shed "
                   f"{batch.get('shed')} requests")
    return out


def check_served_path(store: TripleStore, queries: Sequence[Query],
                      config: ServerConfig, *, clients: int = 4,
                      request_budget: int,
                      oracle: Optional[Sequence[ExecutionResult]] = None
                      ) -> Tuple[List[str], ServedRun]:
    """Serve ``queries`` over a fresh app built from ``config`` and hold
    the answers to the oracle; returns (problems, the served run)."""
    if oracle is None:
        oracle = oracle_answers(store, queries, page_size=config.page_size,
                                max_mpr=config.max_mpr,
                                request_budget=request_budget)
    app = app_from_config(store, config)

    async def main() -> ServedRun:
        try:
            return await serve_queries(app, queries, clients=clients,
                                       request_budget=request_budget)
        finally:
            await app.aclose()

    run = asyncio.run(main())
    return problems(run, oracle, queries), run
