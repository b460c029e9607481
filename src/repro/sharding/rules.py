"""Logical-axis sharding: one model definition, any mesh.

Parameters and activations are annotated with *logical* axis names
("embed", "ff", "heads", "experts", "batch", ...). A ``Rules`` object
maps logical names to mesh axes; ``constrain`` applies
``with_sharding_constraint`` when a rule-set is active and is a no-op
otherwise (single-device smoke tests never touch the mesh machinery).

Default rules implement the production layout:
  batch        -> (pod, data)   [DP across pods and the data axis]
  ff/heads/... -> model         [TP: Megatron-style column/row splits]
  experts      -> model         [EP: expert parallelism for MoE]
  kv_seq       -> data          [SP: sequence-sharded KV cache, decode]
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

_state = threading.local()


def default_rules(multi_pod: bool = False) -> Dict[str, MeshAxes]:
    dp: MeshAxes = ("pod", "data") if multi_pod else "data"
    return {
        # activations
        "batch": dp,
        "seq": None,
        "kv_seq": "data",          # sequence-sharded cache for B=1 decode
        "act_embed": None,
        "act_heads": "model",
        "act_kv_heads": "model",   # only when kv_heads divides the axis
        "act_ff": "model",
        # parameters
        "embed": None,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "ff_expert": None,         # expert-internal dim stays local
        "experts": "model",
        "experts_r": None,         # router output dim (tiny) replicated
        "ssm_inner": "model",
        "layers": None,
        # ZeRO: optimizer state / grad accumulators shard their largest
        # replicated dim over the data axis (pod included when present)
        "zero": dp,
    }


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Dict[str, MeshAxes]):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, rules)
    try:
        yield
    finally:
        _state.ctx = prev


def active() -> Optional[Tuple[Mesh, Dict[str, MeshAxes]]]:
    return getattr(_state, "ctx", None)


def spec_for(axes: Sequence[Optional[str]],
             rules: Dict[str, MeshAxes]) -> P:
    """Logical axes tuple -> PartitionSpec, dropping unknown names."""
    parts = []
    used = set()

    def resolve(name):
        if name is None:
            return None
        target = rules.get(name)
        if target is None:
            return None
        # avoid using one mesh axis twice in a spec
        flat = (target,) if isinstance(target, str) else tuple(target)
        flat = tuple(a for a in flat if a not in used)
        if not flat:
            return None
        used.update(flat)
        return flat if len(flat) > 1 else flat[0]

    for name in axes:
        parts.append(resolve(name))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint if rules are active.

    Divisibility-aware: a mapped mesh axis that does not evenly divide
    the tensor dimension is dropped (e.g. 2 KV heads cannot shard over a
    16-way model axis -- they stay replicated for that arch)."""
    ctx = active()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = spec_for(axes, rules)
    parts = list(spec) + [None] * (x.ndim - len(spec))
    fixed = []
    for dim, part in zip(x.shape, parts, strict=True):
        if part is not None:
            names = (part,) if isinstance(part, str) else tuple(part)
            size = 1
            for n in names:
                size *= mesh.shape[n]
            if dim % size != 0:
                part = None
        fixed.append(part)
    while fixed and fixed[-1] is None:
        fixed.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))


def param_shardings(axes_tree, mesh: Mesh, rules: Dict[str, MeshAxes],
                    shapes_tree=None):
    """Map an axes pytree (tuples of logical names) to NamedShardings.

    With ``shapes_tree`` (matching pytree of ShapeDtypeStructs/arrays),
    applies the same divisibility guard as ``constrain``."""
    is_leaf = lambda a: a is None or isinstance(a, tuple)

    def leaf(axes, shape=None):
        if axes is None:
            return NamedSharding(mesh, P())
        spec = spec_for(axes, rules)
        if shape is not None:
            parts = list(spec) + [None] * (len(shape.shape) - len(spec))
            fixed = []
            for dim, part in zip(shape.shape, parts, strict=True):
                if part is not None:
                    names = ((part,) if isinstance(part, str)
                             else tuple(part))
                    size = 1
                    for n in names:
                        size *= mesh.shape[n]
                    if dim % size != 0:
                        part = None
                fixed.append(part)
            while fixed and fixed[-1] is None:
                fixed.pop()
            spec = P(*fixed)
        return NamedSharding(mesh, spec)

    if shapes_tree is None:
        return jax.tree.map(leaf, axes_tree, is_leaf=is_leaf)
    # axes_tree has tuple leaves where shapes_tree has array leaves;
    # walk shapes_tree and look up axes by path
    flat_axes, _ = jax.tree.flatten_with_path(axes_tree, is_leaf=is_leaf)
    flat_shapes, treedef = jax.tree.flatten_with_path(shapes_tree)
    axes_by_path = {path: a for path, a in flat_axes}
    out = [leaf(axes_by_path.get(path), s) for path, s in flat_shapes]
    return jax.tree.unflatten(treedef, out)
