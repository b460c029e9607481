"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Default scales are CI-friendly;
``--full`` (or REPRO_BENCH_FULL=1) switches to the EXPERIMENTS.md
configuration. ``--only <prefix>`` restricts to one bench family.
``--check-trajectory`` instead verifies that the current PR has landed
a trajectory entry in ``BENCH_throughput.json`` (the CI guard against
the empty-trajectory regression: benchmark runs that forget to
``persist`` a headline).
"""
from __future__ import annotations

import json
import os
import sys


def check_trajectory() -> int:
    """Exit 0 iff ``BENCH_throughput.json`` has a trajectory entry for
    the current PR id (run AFTER the smoke benchmarks in CI)."""
    from .common import REPO_ROOT, pr_id
    path = os.path.join(REPO_ROOT, "BENCH_throughput.json")
    if not os.path.exists(path):
        print(f"trajectory FAIL: {path} missing")
        return 1
    with open(path) as fh:
        trajectory = json.load(fh).get("trajectory", [])
    pr = pr_id()
    entries = [e for e in trajectory if e.get("pr") == pr]
    if not entries:
        seen = [e.get("pr") for e in trajectory]
        print(f"trajectory FAIL: no entry for {pr} (have {seen})")
        return 1
    keys = sorted(k for e in entries for k in e if k != "pr")
    print(f"trajectory OK: {pr} present with {len(keys)} metric(s)")
    return 0


def main() -> None:
    if "--check-trajectory" in sys.argv:
        raise SystemExit(check_trajectory())
    full = "--full" in sys.argv
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]

    from .common import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    benches = []
    from . import (network_load, pagesize, throughput, cache_hits,
                   kernels, chaos)
    benches = [
        ("network_load", network_load.run),
        ("pagesize", pagesize.run),
        ("throughput", throughput.run),
        ("cache_hits", cache_hits.run),
        ("kernels", kernels.run),
        ("chaos", chaos.run),
    ]
    try:
        from . import roofline_report
        benches.append(("roofline", roofline_report.run))
    except ImportError:
        pass

    for name, fn in benches:
        if only and not name.startswith(only):
            continue
        fn(full=full)


if __name__ == "__main__":
    main()
