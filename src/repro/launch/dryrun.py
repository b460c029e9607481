"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) cell this lowers and
compiles the real step function (train_step for training shapes,
prefill/serve steps for inference shapes) against ShapeDtypeStruct
inputs -- no allocation -- on the production meshes:

  single-pod: (data=16, model=16)            = 256 chips
  multi-pod:  (pod=2, data=16, model=16)     = 512 chips

and records memory_analysis / cost_analysis / roofline terms as JSON
artifacts under ``artifacts/dryrun/``.
"""
# The VERY FIRST lines, before ANY other import (jax locks the device
# count on first init):
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (ALL_SHAPES, all_archs, get_arch,
                                shapes_for, skipped_shapes_for)
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (batch_specs, opt_state_specs, param_specs,
                                prefill_input_specs, serve_input_specs)
from repro.launch.steps import (make_prefill_step, make_serve_step,
                                make_train_step)
from repro.models.model import build_model
from repro.sharding.rules import default_rules, use_rules
from repro.train.optimizer import AdamW, constant_lr

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               step_override: str = "", save_hlo: bool = False,
               mini: bool = False, rules_override=None):
    """Lower + compile one (arch, shape, mesh) cell; returns the record.

    ``mini``: reduced config on a (2,2[,2]) mesh with scaled shapes --
    the CI-runnable version of the same code path."""
    import dataclasses as _dc
    from repro.configs.base import reduced_for_smoke

    cfg = get_arch(arch_name)
    shape = {s.name: s for s in ALL_SHAPES}[shape_name]
    if mini:
        cfg = _dc.replace(reduced_for_smoke(cfg), name=cfg.name)
        shape = _dc.replace(shape, seq_len=256,
                            global_batch=8 if shape.global_batch > 1
                            else 1)
        shp = (2, 2, 2) if multi_pod else (2, 2)
        axes = (("pod", "data", "model") if multi_pod
                else ("data", "model"))
        mesh = jax.make_mesh(shp, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = ("pod2x16x16" if multi_pod else "pod16x16")
    if mini:
        mesh_name = "mini" + ("2x2x2" if multi_pod else "2x2")
    chips = mesh.size
    rules = rules_override or default_rules(multi_pod=multi_pod)
    rules.update(dict(cfg.sharding_overrides))
    model = build_model(cfg, dtype=jnp.bfloat16)

    t0 = time.time()
    with use_rules(mesh, rules):
        p_specs, p_axes = param_specs(model, mesh, rules)
        kind = step_override or shape.kind
        if kind == "train":
            optimizer = AdamW(learning_rate=constant_lr(1e-4))
            # microbatching: keep per-microbatch local batch ~2-8 rows
            # so activations fit 16 GB HBM (EXPERIMENTS.md SPerf)
            data_shards = mesh.shape.get("data", 1) * mesh.shape.get(
                "pod", 1)
            local_b = max(shape.global_batch // data_shards, 1)
            target = 1 if cfg.d_model >= 8192 else (
                2 if cfg.d_model >= 4096 else 4)
            grad_accum = max(1, local_b // target)
            while shape.global_batch % (grad_accum) != 0:
                grad_accum //= 2
            step = make_train_step(model, optimizer,
                                   grad_accum=grad_accum,
                                   grad_axes=p_axes)
            o_specs = opt_state_specs(p_specs, mesh, p_axes, rules)
            b_specs = batch_specs(cfg, shape, mesh, rules)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                p_specs, o_specs, b_specs)
        elif kind == "prefill":
            step = make_prefill_step(model, max_seq=shape.seq_len)
            ins = prefill_input_specs(model, shape, mesh, rules)
            lowered = jax.jit(step).lower(p_specs, *ins)
        else:  # decode
            step = make_serve_step(model)
            ins = serve_input_specs(model, shape, mesh, rules)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                p_specs, *ins)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()

    rl = RL.analyze_compiled(
        arch_name, shape_name, mesh_name, chips, hlo_text,
        RL.model_flops_for(cfg, shape), memory_analysis=mem)

    record = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "kind": kind, "chips": chips,
        "grad_accum": locals().get("grad_accum", 1),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_size_gb": mem.argument_size_in_bytes / 1e9,
            "output_size_gb": mem.output_size_in_bytes / 1e9,
            "temp_size_gb": mem.temp_size_in_bytes / 1e9,
            "generated_code_size_mb":
                mem.generated_code_size_in_bytes / 1e6,
        },
        "cost_analysis": {
            "flops_raw": cost.get("flops", 0.0),
            "bytes_accessed_raw": cost.get("bytes accessed", 0.0),
        },
        "roofline": rl.to_dict(),
        "hlo_bytes": len(hlo_text),
    }
    if save_hlo:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        with open(os.path.join(
                ARTIFACT_DIR,
                f"{arch_name}__{shape_name}__{mesh_name}.hlo.txt"),
                "w") as f:
            f.write(hlo_text)
    return record


def cell_list(multi_pod: bool):
    cells = []
    for name, cfg in sorted(all_archs().items()):
        for shape in shapes_for(cfg):
            cells.append((name, shape.name))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mini", action="store_true",
                    help="reduced configs on a tiny mesh (CI)")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=os.path.join("artifacts", "dryrun"))
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = cell_list(False)
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]

    failures = 0
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        if args.mini:
            mesh_name = "mini" + ("2x2x2" if multi_pod else "2x2")
        for arch_name, shape_name in cells:
            out_path = os.path.join(
                args.out, f"{arch_name}__{shape_name}__{mesh_name}.json")
            if os.path.exists(out_path):
                print(f"[skip] {arch_name} x {shape_name} x {mesh_name}"
                      " (artifact exists)", flush=True)
                continue
            print(f"[dryrun] {arch_name} x {shape_name} x {mesh_name}",
                  flush=True)
            try:
                rec = lower_cell(arch_name, shape_name, multi_pod,
                                 save_hlo=args.save_hlo, mini=args.mini)
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"mem(temp)={rec['memory_analysis']['temp_size_gb']:.2f}GB "
                      f"compute={r['compute_s']:.4f}s "
                      f"memory={r['memory_s']:.4f}s "
                      f"coll={r['collective_s']:.4f}s "
                      f"dominant={r['dominant']}", flush=True)
            except Exception:
                failures += 1
                print(f"  FAILED:\n{traceback.format_exc()}", flush=True)
    # record the per-brief skips
    skips = []
    for name, cfg in sorted(all_archs().items()):
        for shape, reason in skipped_shapes_for(cfg):
            skips.append({"arch": name, "shape": shape.name,
                          "reason": reason})
    with open(os.path.join(args.out, "skips.json"), "w") as f:
        json.dump(skips, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
