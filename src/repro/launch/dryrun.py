import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
if os.environ.get("REPRO_DRYRUN_DEVICES"):  # tests: shrink the fake fleet
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes (16x16 single-pod; 2x16x16 multi-pod), record
memory_analysis, cost_analysis, and the HLO-derived roofline inputs.

MUST be run as its own process (the two lines above lock jax's device count
before any other import). Results land in artifacts/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ASSIGNED_ARCHS, SHAPES_BY_NAME, ShapeCell,
                                get_config)
from repro.launch import hlo_analysis
from repro.launch.mesh import auto_axes, make_production_mesh
from repro.launch.steps import (cell_batch_struct, jit_decode, jit_prefill,
                                jit_train_step, make_ctx, microbatches_for)
from repro.models.registry import build_model
from repro.sharding.specs import param_specs
from repro.train.optimizer import AdamW

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _variant_suffix() -> str:
    v = os.environ.get("REPRO_VARIANT", "")
    return f"__{v}" if v else ""


def _mem_stats(compiled):
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception as e:  # pragma: no cover
        out["error"] = str(e)
    return out


def _analytic_param_bytes(pstruct, cfg, ctx) -> float:
    """Per-device parameter bytes under the sharding policy."""
    specs = param_specs(pstruct, cfg, ctx)
    total = 0.0
    for sds, spec in zip(jax.tree_util.tree_leaves(pstruct),
                         jax.tree_util.tree_leaves(
                             specs, is_leaf=lambda x: isinstance(
                                 x, jax.sharding.PartitionSpec))):
        shards = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shards *= ctx.mesh.shape[a]
        total += sds.size * sds.dtype.itemsize / shards
    return total


def model_flops_for(cfg, cell: ShapeCell) -> float:
    n_act = cfg.n_active_params()
    if cell.kind == "train":
        return 6.0 * n_act * cell.global_batch * cell.seq_len
    if cell.kind == "prefill":
        return 2.0 * n_act * cell.global_batch * cell.seq_len
    return 2.0 * n_act * cell.global_batch  # decode: one token per sequence


def _mesh_for(multi_pod: bool):
    spec = os.environ.get("REPRO_DRYRUN_MESH")
    if spec:  # tests: e.g. "2x2" or "2x2x2"
        dims = tuple(int(d) for d in spec.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        return jax.make_mesh(dims, axes, axis_types=auto_axes(len(dims)))
    return make_production_mesh(multi_pod=multi_pod)


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    import dataclasses

    cfg = get_config(arch)
    cell = SHAPES_BY_NAME[shape]
    mesh = _mesh_for(multi_pod)
    ctx = make_ctx(mesh, cell, cfg)
    # perf-variant knobs (EXPERIMENTS.md §Perf); default = paper-faithful
    zero1 = os.environ.get("REPRO_ZERO1") == "1"
    if os.environ.get("REPRO_MOE_WS") == "1":
        ctx = dataclasses.replace(ctx, moe_weight_stationary=True)
    if os.environ.get("REPRO_QBLOCK") == "1":
        ctx = dataclasses.replace(ctx, attn_qblock=True)
    if os.environ.get("REPRO_SLSTM_LG") == "1":
        ctx = dataclasses.replace(ctx, slstm_local_grad=True)
    if os.environ.get("REPRO_DP_ONLY") == "1":
        # right-size parallelism: the model axis joins data parallelism —
        # no tensor sharding (small models on a fixed wide mesh)
        ctx = dataclasses.replace(
            ctx, batch_axes=tuple(ctx.batch_axes) + ("model",),
            model_axis=None)
    if os.environ.get("REPRO_SSM_CHUNK_LOCAL") == "1" and cfg.ssm:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk_local=True))
    model = build_model(cfg, ctx)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "kind": cell.kind, "ok": False,
        "variant": os.environ.get("REPRO_VARIANT", ""),
    }
    t0 = time.time()
    with mesh:
        if cell.kind == "train":
            opt = AdamW(
                state_dtype=jnp.bfloat16
                if cfg.optimizer_dtype == "bfloat16" else jnp.float32,
                total_steps=10_000)
            nmb = microbatches_for(cfg, cell, mesh,
                                   batch_axes=ctx.batch_axes)
            rec["microbatches"] = nmb
            batch = cell_batch_struct(cfg, cell)
            jitted, (pstruct, ostruct, pshard, _) = jit_train_step(
                model, ctx, opt, batch, nmb, zero1=zero1)
            lowered = jitted.lower(pstruct, ostruct, batch)
        elif cell.kind == "prefill":
            batch = cell_batch_struct(cfg, cell)
            jitted, (pstruct, pshard) = jit_prefill(model, ctx, batch)
            lowered = jitted.lower(pstruct, batch)
        else:
            jitted, (pstruct, cstruct, tok, pos) = jit_decode(
                model, ctx, cell.global_batch, cell.seq_len)
            lowered = jitted.lower(pstruct, cstruct, tok, pos)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    rec["memory"] = _mem_stats(compiled)
    try:
        ca = compiled.cost_analysis()
        rec["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                           "bytes": float(ca.get("bytes accessed", 0.0))}
    except Exception as e:
        rec["xla_cost"] = {"error": str(e)}
    hlo_text = compiled.as_text()
    rec["hlo"] = hlo_analysis.analyze_text(hlo_text)
    if os.environ.get("REPRO_SAVE_HLO", "1") == "1":
        import gzip
        ART.mkdir(parents=True, exist_ok=True)
        tag = (f"{arch}__{shape}__"
               f"{'multi' if multi_pod else 'single'}{_variant_suffix()}")
        with gzip.open(ART / (tag + ".hlo.gz"), "wt") as f:
            f.write(hlo_text)
    rec["model_flops"] = model_flops_for(cfg, cell)
    rec["param_bytes_per_device"] = _analytic_param_bytes(
        jax.eval_shape(lambda k: model.init(k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)), cfg, ctx)
    rec["n_params"] = cfg.n_params()
    rec["n_active_params"] = cfg.n_active_params()
    rec["ok"] = True
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(ART))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = ASSIGNED_ARCHS if args.all or not args.arch else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        shapes = [c.name for c in cfg.shape_cells()]
        if args.shape:
            shapes = [s for s in shapes if s == args.shape]
        for s in shapes:
            if args.mesh in ("single", "both"):
                cells.append((arch, s, False))
            if args.mesh in ("multi", "both"):
                cells.append((arch, s, True))

    n_ok = 0
    for arch, shape, mp in cells:
        tag = (f"{arch}__{shape}__{'multi' if mp else 'single'}"
               f"{_variant_suffix()}")
        path = out_dir / (tag + ".json")
        try:
            rec = run_cell(arch, shape, mp)
        except Exception as e:
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16", "ok": False,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        path.write_text(json.dumps(rec, indent=1))
        status = "OK" if rec.get("ok") else "FAIL"
        print(f"[{status}] {tag} "
              f"compile={rec.get('compile_s', '-')}s "
              f"flops={rec.get('hlo', {}).get('flops', 0):.3e}"
              if rec.get("ok") else f"[{status}] {tag}: "
              f"{rec.get('error', '')[:200]}", flush=True)
        n_ok += bool(rec.get("ok"))
    print(f"dry-run: {n_ok}/{len(cells)} cells OK")
    return 0 if n_ok == len(cells) else 1


if __name__ == "__main__":
    raise SystemExit(main())
