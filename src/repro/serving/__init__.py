"""Serving: the brTPF HTTP edge + the KV-cache LM engine.

* ``repro.serving.http`` -- ASGI app over the async brTPF front end
  (GET/POST /fragment, GET /metrics), ``TestClient``, ``run_app``.
* ``repro.serving.transport`` -- client-side transports speaking the
  brtpf/v1 wire schema (in-process loopback and ASGI/HTTP).
* ``repro.serving.router`` -- front-end router fanning requests across
  N server replicas, with per-replica circuit breakers and health-gated
  failover (docs/resilience.md).
* ``repro.serving.resilience`` -- client-side retry/backoff, hedged
  requests and deadline budgets over any transport.
* ``repro.serving.faults`` -- deterministic seeded fault injection
  (delay / error / drop / stall / crash) for chaos tests and
  ``benchmarks/chaos.py``.
* ``repro.serving.smoke`` -- the served-path check ``chip_smoke.py``
  runs on the chip: concurrent clients over the ASGI app, every answer
  held to the numpy oracle.
* ``repro.serving.engine`` -- the LM serving engine (jax; imported
  lazily so the brTPF edge stays usable without an accelerator stack).
"""
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover
    from .engine import GenerationResult, ServingEngine

__all__ = ["GenerationResult", "ServingEngine"]


def __getattr__(name: str):
    # Lazy: engine.py imports jax at module scope; the HTTP edge and its
    # tests must not pay (or require) that import.
    if name in __all__:
        from . import engine
        return getattr(engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
