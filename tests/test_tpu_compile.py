"""The served kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e and refuses what the chip would refuse (block shapes off the (8, 128)
tiling, lowering gaps that interpret mode never hits, programs that do
not fit the device). The topology is described inside a fixture, never at
import time, so every test worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import importlib
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.federation import _MIN_TILE
from repro.core.kernel_selectors import (FUSED_BT, _fused_inputs,
                                         _fused_launch, _fused_outputs,
                                         _grouped_inputs, _grouped_launch,
                                         _grouped_outputs)

# The package re-exports ``bindjoin``/``tpf_match`` as functions, which
# shadow the submodules of the same names.
bindjoin = importlib.import_module("repro.kernels.bindjoin")
tpf_match = importlib.import_module("repro.kernels.tpf_match")

# HBM of one v5e chip is 16 GB; a kernel launch at these widths must use
# a small share of it (the (T, 1) column layout took 3 GB at T = 2**20).
LAUNCH_BYTES_LIMIT = 1 << 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _size(shapes):
    return sum(math.prod(s) for s in shapes)


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` for int32 arguments of ``shapes`` and compile it for
    the described chip."""
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < LAUNCH_BYTES_LIMIT, used
    return compiled


def test_tpf_match_compiles(one_chip):
    t = 1 << 20
    _compile(lambda s, p, o, v: tpf_match.tpf_match_pallas(s, p, o, v),
             one_chip, (t,), (t,), (t,), (8,))


@pytest.mark.parametrize("groups", [1, 8])
def test_bindjoin_grouped_compiles(one_chip, groups):
    t, m = 1 << 20, 128
    _compile(lambda s, p, o, ps, pp, po, pv:
             bindjoin.bindjoin_grouped_pallas(s, p, o, ps, pp, po, pv,
                                              groups=groups),
             one_chip, (t,), (t,), (t,), *[(groups * m,)] * 4)


@pytest.mark.parametrize("bt", [FUSED_BT, _MIN_TILE],
                         ids=["served", "smallest"])
def test_bindjoin_fused_compiles(one_chip, bt):
    """S = 4 segments of G = 4 groups at the served tile, and the
    smallest tile the sharded windowed path launches."""
    segments, groups, m = 4, 4, 128
    t = 1 << 17 if bt == FUSED_BT else 64 * bt
    _compile(lambda seg, s, p, o, ps, pp, po, pv:
             bindjoin.bindjoin_fused_pallas(seg, s, p, o, ps, pp, po, pv,
                                            segments=segments,
                                            groups=groups, bt=bt),
             one_chip, (t // bt,), (t,), (t,), (t,),
             *[(segments * groups * m,)] * 4)


# The served launch programs: each launch's kernel, base-pattern mask and
# epilogue as one program, at the buckets a served launch meets and the
# widths of a brTPF batch (G 16 groups of M up to 32 patterns).
LAUNCH_G, LAUNCH_M, LAUNCH_S = 16, 32, 16


@pytest.fixture
def launch_chip(one_chip, monkeypatch):
    """``one_chip``, with the launch programs tracing their kernels for
    the TPU compiler: this process's own backend may be the CPU, where
    ``kops`` would trace them for the Pallas interpreter. The trace
    caches are cleared around the test, so no trace crosses over."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "_use_interpret", lambda: False)
    jax.clear_caches()
    yield one_chip
    jax.clear_caches()


@pytest.mark.parametrize("tpad", [8192, 131072])
@pytest.mark.parametrize("count_only", [False, True],
                         ids=["rows", "count"])
def test_grouped_launch_program_compiles(launch_chip, tpad, count_only):
    g, m = LAUNCH_G, LAUNCH_M
    n = _size(_grouped_inputs(tpad, g, m))
    compiled = _compile(
        lambda buf: _grouped_launch(buf, capacity=tpad, groups=g, m=m,
                                    count_only=count_only),
        launch_chip, (n,))
    # one buffer back: the counts, or counts, cnts, rows and first
    out = compiled.out_info
    assert out.shape == ((g,) if count_only
                         else (_size(_grouped_outputs(tpad, g)),))


@pytest.mark.parametrize("tpad", [8192, 131072])
def test_fused_launch_program_compiles(launch_chip, tpad):
    s, g, m = LAUNCH_S, LAUNCH_G, LAUNCH_M
    n = _size(_fused_inputs(tpad, s, g, m))
    compiled = _compile(
        lambda buf: _fused_launch(buf, capacity=tpad, segments=s, groups=g,
                                  m=m),
        launch_chip, (n,))
    assert compiled.out_info.shape == (_size(_fused_outputs(tpad, s, g)),)
