"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes, print memory/cost analysis, and dump the
artifacts launch/roofline.py consumes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch phi4-mini-3.8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results are cached as JSON under experiments/dryrun/ (one file per cell) so
re-runs skip completed cells; --force recompiles.
"""

import os

# 512 virtual CPU devices for the production meshes, added to whatever
# XLA_FLAGS already holds (a caller's own device count wins)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512"
    ).strip()

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cells_for, get_config, list_configs
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.models import build_model

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# global-batch microbatch sizes for the train cells (activation memory knob;
# chosen so remat-saved activations fit v5e HBM — see EXPERIMENTS.md §Dry-run)
MICROBATCH = {
    "command-r-plus-104b": 32,
    "jamba-1.5-large-398b": 32,
    "gemma-7b": 64,
    "llava-next-mistral-7b": 64,
    "phi4-mini-3.8b": 64,
    "deepseek-v2-lite-16b": 64,
    "qwen2-moe-a2.7b": 64,
    "h2o-danube-1.8b": 64,
    "whisper-base": 128,
    "xlstm-125m": 128,
}


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def collect_collectives(hlo_text: str):
    """Sum operand bytes per collective kind from optimized HLO.

    Counts each op once; ops inside while bodies must be scaled by trip
    count by the caller (roofline.py does this with the known scan lengths —
    see EXPERIMENTS.md §Roofline methodology).
    """
    sizes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
             "pred": 1, "f64": 8, "s64": 8, "u64": 8, "bf8": 1}
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: {"count": 0, "bytes": 0} for k in kinds}
    # e.g.:  %all-reduce.1 = f32[16,1024]{1,0} all-reduce(...)
    pat = re.compile(
        r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\b(" + "|".join(kinds) + r")\("
    )
    for m in pat.finditer(hlo_text):
        dt, dims, kind = m.groups()
        if dt not in sizes:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind]["count"] += 1
        out[kind]["bytes"] += n * sizes[dt]
    return out


def lower_cell(arch: str, shape: str, multi_pod: bool, mesh_override=None):
    """Lower + compile one cell; returns the result record.

    mesh_override: (shape tuple, axes tuple) — small-mesh testing hook
    (tests/test_dryrun_small.py); production meshes otherwise."""
    cfg = get_config(arch)
    cell = SHAPES[shape]
    if mesh_override is not None:
        mesh = mesh_mod.make_mesh(*mesh_override)
    else:
        mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    shd.enable_constraints(mesh)
    model = build_model(cfg)

    rec = {
        "arch": arch, "shape": shape, "mesh": _mesh_tag(multi_pod),
        "devices": int(mesh.size), "kind": cell.kind,
    }
    t0 = time.time()

    # NamedShardings carry the mesh explicitly; no mesh context is needed.
    if True:
        if cell.kind == "train":
            train_step, opt, _ = steps_mod.make_train_step(
                cfg, microbatch=MICROBATCH.get(arch, 64)
            )
            param_specs = jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32)
            )
            opt_specs = jax.eval_shape(opt.init, param_specs)
            batch_specs = model.input_specs(cell)
            p_sh = shd.param_shardings(mesh, param_specs)
            o_sh = opt.state_shardings(mesh, p_sh, param_specs)
            b_sh = shd.batch_shardings(mesh, batch_specs)
            step_spec = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(
                train_step,
                in_shardings=(p_sh, o_sh, b_sh, None),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            ).lower(param_specs, opt_specs, batch_specs, step_spec)
        elif cell.kind == "prefill":
            prefill_step, _ = steps_mod.make_serve_steps(cfg)
            param_specs = jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32)
            )
            batch_specs = model.input_specs(cell)
            p_sh = shd.param_shardings(mesh, param_specs)
            b_sh = shd.batch_shardings(mesh, batch_specs)
            lowered = jax.jit(
                prefill_step, in_shardings=(p_sh, b_sh)
            ).lower(param_specs, batch_specs)
        else:  # decode
            _, decode_step = steps_mod.make_serve_steps(cfg)
            param_specs = jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32)
            )
            batch_specs = model.input_specs(cell)
            p_sh = shd.param_shardings(mesh, param_specs)
            b_sh = shd.batch_shardings(mesh, batch_specs)
            lowered = jax.jit(
                decode_step,
                in_shardings=(p_sh, b_sh),
                donate_argnums=(1,),
            ).lower(param_specs, batch_specs)

        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        if mem is not None:
            for field in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            ):
                v = getattr(mem, field, None)
                if v is not None:
                    rec[field] = int(v)
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax wraps the dict
            cost = cost[0] if cost else None
        if cost:
            rec["hlo_flops"] = float(cost.get("flops", -1))
            rec["hlo_bytes"] = float(cost.get("bytes accessed", -1))
            rec["cost_keys"] = sorted(cost.keys())[:40]
        hlo = compiled.as_text()
        rec["collectives"] = collect_collectives(hlo)
        rec["hlo_len"] = len(hlo)
        print(f"[{arch} x {shape} x {rec['mesh']}] "
              f"compile={rec['compile_s']}s flops={rec.get('hlo_flops', 0):.3e} "
              f"temp={rec.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"args={rec.get('argument_size_in_bytes', 0)/2**30:.2f}GiB")
        print("  memory_analysis:", mem)
        coll_str = ", ".join(
            f"{k}:{v['count']}x/{v['bytes']/2**20:.1f}MiB"
            for k, v in rec["collectives"].items() if v["count"]
        )
        print("  collectives:", coll_str or "none")
    shd.enable_constraints(None)
    return rec


def run_reservoir_dryrun(multi_pod: bool, variant: str = "base"):
    """The paper's own workload on the production mesh: sharded ensemble
    integration (E over data axes, N over model).

    §Perf C variants:
      base        N over model, f32 all-gather of m^x per stage
      bf16gather  same, but the per-stage wire traffic is bf16 (half bytes)
      eonly       E-only sharding (W replicated; zero collectives, but the
                  per-device matmul lane dim drops to E/devices)
    """
    from repro.core.ensemble import lower_sharded_ensemble

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    ens_axes = ("pod", "data") if multi_pod else ("data",)
    kw = dict(model_axis="model")
    if variant == "bf16gather":
        kw["gather_dtype"] = jnp.bfloat16
    elif variant == "eonly":
        ens_axes = (
            ("pod", "data", "model") if multi_pod else ("data", "model")
        )
        kw["model_axis"] = None
    rec = {
        "arch": "sto-reservoir", "shape": f"n16384-e8192-{variant}",
        "mesh": _mesh_tag(multi_pod),
        "devices": int(mesh.size), "kind": "reservoir",
    }
    t0 = time.time()
    lowered = lower_sharded_ensemble(
        mesh, n=16_384, e=8_192, dt=1e-11, n_steps=100,
        ensemble_axes=ens_axes, dtype=jnp.float32, **kw,
    )
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    for field in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes"):
        v = getattr(mem, field, None)
        if v is not None:
            rec[field] = int(v)
    cost = compiled.cost_analysis()
    if cost:
        rec["hlo_flops"] = float(cost.get("flops", -1))
        rec["hlo_bytes"] = float(cost.get("bytes accessed", -1))
    rec["collectives"] = collect_collectives(compiled.as_text())
    print(f"[sto-reservoir x {rec['mesh']}] compile={rec['compile_s']}s "
          f"flops={rec.get('hlo_flops', 0):.3e}")
    print("  memory_analysis:", mem)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--reservoir", action="store_true")
    ap.add_argument("--variant", default="base",
                    choices=["base", "bf16gather", "eonly"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    if args.reservoir:
        for mp in meshes:
            rec = run_reservoir_dryrun(mp, variant=args.variant)
            suffix = "" if args.variant == "base" else f"_{args.variant}"
            path = OUT_DIR / f"sto-reservoir{suffix}_{_mesh_tag(mp)}.json"
            path.write_text(json.dumps(rec, indent=1))
        return

    if args.all:
        jobs = [
            (a, s)
            for a in list_configs()
            for s, ok in cells_for(get_config(a)).items()
            if ok
        ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        jobs = [(args.arch, args.shape)]

    failures = []
    for arch, shape in jobs:
        for mp in meshes:
            path = OUT_DIR / f"{arch}_{shape}_{_mesh_tag(mp)}.json"
            if path.exists() and not args.force:
                print(f"skip cached {path.name}")
                continue
            try:
                rec = lower_cell(arch, shape, mp)
                path.write_text(json.dumps(rec, indent=1))
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                print(f"FAIL [{arch} x {shape} x {_mesh_tag(mp)}]: {e}")
                traceback.print_exc(limit=5)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
