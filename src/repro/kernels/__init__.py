"""Pallas TPU kernels for the brTPF compute hot-spots.

``bindjoin``  -- server-side bindings-restricted filter (Definition 1)
``tpf_match`` -- single-triple-pattern matcher (TPF selector)

Each kernel ships with a pure-jnp oracle in ``ref.py``; ``ops.py`` holds
the padded/jit public entry points (compiled on the TPU, interpret mode
on the CPU).
"""
from .ops import (bindjoin, bindjoin_grouped, compact_mask,
                  pattern_vec_from, tpf_match)

__all__ = ["bindjoin", "bindjoin_grouped", "compact_mask",
           "pattern_vec_from", "tpf_match"]
