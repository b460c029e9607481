"""Dry-run + roofline for the brTPF engine itself (§Perf (D)).

Lowers the distributed bind-join request step on the production mesh
with a ~1B-triple sharded store (ShapeDtypeStruct only -- no data):

* ``baseline``  -- the paper-faithful path: every shard streams its whole
  partition through the bind-join kernel; full (capacity, 3) pages are
  all-gathered back.
* ``windowed``  -- beyond-paper: shard-local sorted-range window scan +
  unbound-column projection of the response.

Writes ``artifacts/dryrun/engine__{variant}.json`` with the same
roofline record as the model cells.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time

import jax
import jax.numpy as jnp

from jax import enable_x64
from repro.core.federation import FederatedStore
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

TOTAL_TRIPLES = 1 << 30          # ~1.07B global
MAX_MPR = 64
CAPACITY = 4096
WINDOW = 1 << 17                 # 131,072-row shard window


def specs(mesh, shard_n):
    n = shard_n * mesh.shape["data"]
    sh = lambda spec: NamedSharding(mesh, spec)
    return dict(
        triples=jax.ShapeDtypeStruct((n, 3), jnp.int32,
                                     sharding=sh(P("data", None))),
        valid=jax.ShapeDtypeStruct((n,), jnp.bool_,
                                   sharding=sh(P("data"))),
        keys=jax.ShapeDtypeStruct((n,), jnp.int64,
                                  sharding=sh(P("data"))),
        pats=jax.ShapeDtypeStruct((MAX_MPR, 3), jnp.int32,
                                  sharding=sh(P())),
        pat_valid=jax.ShapeDtypeStruct((MAX_MPR,), jnp.int32,
                                       sharding=sh(P())),
        base_vec=jax.ShapeDtypeStruct((8,), jnp.int32, sharding=sh(P())),
        lo=jax.ShapeDtypeStruct((), jnp.int64, sharding=sh(P())),
        hi=jax.ShapeDtypeStruct((), jnp.int64, sharding=sh(P())),
        page=jax.ShapeDtypeStruct((), jnp.int32, sharding=sh(P())),
    )


def lower_variant(variant: str, out_dir: str):
    mesh = make_production_mesh()
    shard_n = TOTAL_TRIPLES // mesh.shape["data"] // mesh.shape["model"] \
        * mesh.shape["model"]
    # store sharded over 'data' only (one federation member per data row)
    shard_n = TOTAL_TRIPLES // mesh.shape["data"]
    fed = FederatedStore(mesh=mesh, axis="data", triples=None,
                         valid=None, keys=None, shard_n=shard_n)
    sp = specs(mesh, shard_n)

    t0 = time.time()
    with enable_x64(True):
        if variant == "baseline":
            fn = fed.lowerable(CAPACITY)
            lowered = fn.lower(sp["triples"], sp["valid"], sp["pats"],
                               sp["pat_valid"], sp["base_vec"])
        else:
            fn = fed.lowerable_windowed(CAPACITY, WINDOW,
                                        wild_cols=(1, 2))
            lowered = fn.lower(sp["triples"], sp["valid"], sp["keys"],
                               sp["pats"], sp["pat_valid"],
                               sp["base_vec"], sp["lo"], sp["hi"],
                               sp["page"])
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    rl = RL.analyze_compiled("brtpf-engine", variant, "pod16x16",
                             mesh.size, hlo, model_flops=0.0,
                             memory_analysis=mem)
    rec = {
        "arch": "brtpf-engine", "shape": variant, "mesh": "pod16x16",
        "chips": mesh.size, "compile_s": round(t_compile, 2),
        "total_triples": TOTAL_TRIPLES, "max_mpr": MAX_MPR,
        "capacity": CAPACITY, "window": WINDOW,
        "memory_analysis": {
            "argument_size_gb": mem.argument_size_in_bytes / 1e9,
            "temp_size_gb": mem.temp_size_in_bytes / 1e9,
        },
        "roofline": rl.to_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"engine__{variant}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    r = rec["roofline"]
    print(f"[engine:{variant}] compile={t_compile:.1f}s "
          f"compute={r['compute_s']:.5f}s memory={r['memory_s']:.5f}s "
          f"coll={r['collective_s']:.6f}s dominant={r['dominant']}",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join("artifacts", "dryrun"))
    ap.add_argument("--variant", default="",
                    choices=["", "baseline", "windowed"])
    args = ap.parse_args()
    variants = [args.variant] if args.variant else ["baseline",
                                                    "windowed"]
    for v in variants:
        lower_variant(v, args.out)


if __name__ == "__main__":
    main()
