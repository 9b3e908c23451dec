import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape × mesh) cell on the production meshes and dump
memory/cost/collective analysis for the roofline (deliverable g).

MUST keep the two lines above first: jax locks the device count on first
backend initialization.  Do NOT replicate that env var anywhere global —
smoke tests and benches see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi     # 2×16×16 only

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json with:
  status, flops/bytes (per device, from compiled.cost_analysis()),
  collective bytes per op type (parsed from compiled HLO),
  memory_analysis fields (proves it fits), MODEL_FLOPS, timings.
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import ASSIGNED, SHAPES, get_config
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import batch_spec, transformer as tf
from repro.distributed.sharding import (as_shardings, batch_specs,
                                        cache_specs, param_specs)
from repro.training.train_loop import build_train_step
from repro.training.optimizer import OptConfig
from repro.serving.generator import build_prefill_step, build_serve_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _tensor_bytes(lhs: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(lhs):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device link traffic by collective type (ring-algorithm
    accounting; see EXPERIMENTS.md §Roofline for the formulas)."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "=" not in line:
            continue
        op, suffix = m.group(1), m.group(2)
        if suffix == "-done":
            continue  # the matching -start already carried the payload
        lhs = line[:m.start()]
        nbytes = _tensor_bytes(lhs)
        gm = _GROUPS_RE.search(line)
        g = int(gm.group(2)) if gm else 2
        if op == "all-reduce":
            moved = 2 * (g - 1) / g * nbytes
        elif op == "all-gather":
            moved = (g - 1) / g * nbytes            # lhs is the gathered result
        elif op == "reduce-scatter":
            moved = (g - 1) * nbytes                # lhs is the scattered shard
        elif op == "all-to-all":
            moved = (g - 1) / g * nbytes
        else:
            moved = nbytes
        out[op] += int(moved)
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-reduce", "all-gather", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS (global): 6·N_active·tokens for train, 2·N_active·tokens
    for inference-style cells."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_cell(arch_name: str, shape_name: str, mesh):
    """Returns (jitted_fn, arg_shapestructs) for the cell."""
    cfg = get_config(arch_name)
    shape = SHAPES[shape_name]
    pshapes = tf.param_shapes(cfg)
    pspecs = param_specs(pshapes, mesh, cfg)
    bspec_tree = batch_spec(cfg, shape)

    if shape.kind == "train":
        from jax.sharding import PartitionSpec as P
        oshapes = jax.eval_shape(
            lambda: {"m": jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, np.float32), pshapes),
                "v": jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, np.float32), pshapes),
                "step": jax.ShapeDtypeStruct((), np.int32)})
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        bspecs = batch_specs(bspec_tree, mesh)
        accum = int(os.environ.get("DRYRUN_ACCUM", "4"))
        pshard, oshard, bshard = (as_shardings(x, mesh)
                                  for x in (pspecs, ospecs, bspecs))
        fn = jax.jit(build_train_step(cfg, OptConfig(), accum=accum),
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        args = (pshapes, oshapes, bspec_tree)
    elif shape.kind == "prefill":
        bspecs = batch_specs(bspec_tree, mesh)
        fn = jax.jit(build_prefill_step(cfg),
                     in_shardings=(as_shardings(pspecs, mesh),
                                   as_shardings(bspecs, mesh)),
                     out_shardings=None)
        args = (pshapes, bspec_tree)
    else:  # decode
        from repro.distributed.sharding import sanitize_spec
        from jax.sharding import PartitionSpec as P
        cache_shapes = jax.eval_shape(
            lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = cache_specs(cache_shapes, mesh, cfg)
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        tok_spec = sanitize_spec(P(dp), (shape.global_batch,), mesh,
                                 strict=False)
        pshard, tshard, cshard = (as_shardings(x, mesh)
                                  for x in (pspecs, tok_spec, cspecs))
        fn = jax.jit(build_serve_step(cfg),
                     in_shardings=(pshard, tshard, cshard),
                     out_shardings=(tshard, None, cshard),
                     donate_argnums=(2,))
        args = (pshapes, bspec_tree["tokens"], cache_shapes)
    return fn, args


def run_cell(arch_name: str, shape_name: str, mesh_kind: str) -> dict:
    cfg = get_config(arch_name)
    shape = SHAPES[shape_name]
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
           "params": cfg.param_count(), "active_params": cfg.active_param_count(),
           "model_flops": model_flops_for(cfg, shape)}
    ok, why = cfg.runnable(shape)
    if not ok:
        rec.update(status="skipped", skip_reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["mesh_shape"] = dict(mesh.shape)
    chips = int(np.prod(list(mesh.shape.values())))
    rec["chips"] = chips
    try:
        fn, args = build_cell(arch_name, shape_name, mesh)
        t0 = time.time()
        with jax.set_mesh(mesh):
            lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ca = compiled.cost_analysis()
        if not isinstance(ca, dict):
            ca = ca[0]
        ma = compiled.memory_analysis()
        txt = compiled.as_text()
        # loop-aware walker (XLA's cost_analysis counts while bodies once —
        # see launch/hlo_analysis.py); raw values kept for reference.
        cost = hlo_analysis.analyze(txt)
        coll = dict(cost.collective)
        coll["total"] = cost.collective_total
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            flops_per_device=float(cost.flops),
            bytes_per_device=float(cost.bytes),
            transcendentals=float(cost.transcendentals),
            xla_flops_raw=float(ca.get("flops", 0.0)),
            xla_bytes_raw=float(ca.get("bytes accessed", 0.0)),
            collective=coll,
            memory=dict(
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes,
                generated_code_bytes=ma.generated_code_size_in_bytes,
            ),
            hlo_bytes=len(txt),
        )
        # per-device peak = args + temps (aliased buffers counted once)
        rec["memory"]["peak_per_device"] = (
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception as e:  # noqa: BLE001 — recorded, not swallowed
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (the 10 assigned)")
    ap.add_argument("--shape", default="all", choices=["all", *SHAPES])
    ap.add_argument("--mesh", default="both", choices=["both", "single", "multi"])
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have a JSON")
    args = ap.parse_args(argv)

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list(ASSIGNED) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                name = f"{arch}__{shape}__{mesh_kind}.json"
                path = outdir / name
                if path.exists() and not args.force:
                    rec = json.loads(path.read_text())
                    print(f"skip (cached) {name}: {rec['status']}")
                    continue
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_kind)
                rec["wall_s"] = round(time.time() - t0, 2)
                path.write_text(json.dumps(rec, indent=1))
                mem = rec.get("memory", {}).get("peak_per_device", 0) / 2**30
                print(f"{rec['status']:<8s} {name:<58s} "
                      f"flops/dev={rec.get('flops_per_device', 0):.3e} "
                      f"coll={rec.get('collective', {}).get('total', 0):.3e}B "
                      f"peak={mem:.2f}GiB wall={rec['wall_s']}s",
                      flush=True)
                if rec["status"] == "error":
                    failures += 1
                    print(rec["error"], file=sys.stderr)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
