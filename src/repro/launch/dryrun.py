import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices stand in for 2 pods x 256 chips.  For every cell this script
  * builds abstract params / optimizer state / batch / cache
    (ShapeDtypeStruct -- nothing is allocated),
  * attaches NamedShardings from repro.dist.sharding,
  * ``jit(step).lower(...).compile()`` on the production mesh,
  * records memory_analysis / cost_analysis / per-collective bytes parsed
    from the compiled HLO into a JSON artifact consumed by
    ``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --out dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --archs tinyllama-1.1b
"""
import argparse
import json
import re
import time
import traceback

import jax

from ..configs import (ARCHS, SHAPES, SHAPE_BY_NAME, SUBQUADRATIC_FAMILIES,
                       get_arch)
from ..dist import sharding as shd
from ..models import abstract_params
from ..optim import adamw
from .mesh import make_production_mesh
from .steps import input_specs, make_prefill_step, make_serve_step, \
    make_train_step

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo: str):
    """Sum result bytes of every collective op in compiled HLO text.

    Returns (totals, counts, in_loop_totals): collectives that live inside a
    while-loop body (the layer scan) are bucketed separately -- HLO cost
    analysis counts loop bodies ONCE, so the roofline harness multiplies the
    in-loop bucket by the scan trip count (n_layers).
    """
    totals = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    in_loop = {k: 0 for k in _COLLECTIVES}
    cur_comp_is_body = False
    for line in hlo.splitlines():
        ls = line.lstrip()
        if ls.startswith("%") and ("{" in line) and ("= " not in ls[:40]):
            # computation definition header; jax scan bodies lower to
            # %...region_0..._spmd... (region_1 = the loop condition)
            name = ls.split(" ", 1)[0]
            cur_comp_is_body = ("body" in name) or ("region_0" in name)
        for op in _COLLECTIVES:
            if f" {op}(" in line or f" {op}-start(" in line:
                lhs = line.split(f" {op}", 1)[0]
                b = sum(_bytes_of(d, s) for d, s in _SHAPE_RE.findall(lhs))
                totals[op] += b
                counts[op] += 1
                if cur_comp_is_body:
                    in_loop[op] += b
                break
    return totals, counts, in_loop


def _attach(tree, shardings):
    return jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        tree, shardings)


def should_skip(cfg, shape) -> str:
    if shape.name == "long_500k" and cfg.family not in SUBQUADRATIC_FAMILIES:
        return "long_500k needs sub-quadratic attention (full-attn arch)"
    return ""


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             collect_hlo: bool = True):
    cfg = get_arch(arch)
    shape = SHAPE_BY_NAME[shape_name]
    skip = should_skip(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "params": cfg.param_count(),
           "active_params": cfg.active_param_count(),
           "seq_len": shape.seq_len, "global_batch": shape.global_batch}
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    t0 = time.time()
    params = abstract_params(cfg)
    pshard = shd.param_shardings(mesh, params)
    params = _attach(params, pshard)
    specs = input_specs(cfg, shape)

    # ambient mesh so activation sharding constraints (dist.annotate) bind
    with jax.set_mesh(mesh):
        return _lower_and_analyze(cfg, shape, mesh, rec, params, pshard,
                                  specs, t0, collect_hlo)


def _lower_and_analyze(cfg, shape, mesh, rec, params, pshard, specs, t0,
                       collect_hlo):
    if shape.kind == "train":
        opt = jax.eval_shape(adamw.init, params)
        opt = _attach(opt, shd.opt_shardings(mesh, opt, pshard))
        batch = _attach(specs["batch"],
                        shd.batch_shardings(mesh, specs["batch"]))
        step = make_train_step(cfg, grad_shardings=pshard)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt, batch)
    elif shape.kind == "prefill":
        batch = _attach(specs["batch"],
                        shd.batch_shardings(mesh, specs["batch"]))
        step = make_prefill_step(cfg, cache_len=shape.seq_len)
        lowered = jax.jit(step).lower(params, batch)
    else:  # decode
        cache = _attach(specs["cache"],
                        shd.cache_shardings(mesh, specs["cache"]))
        tokens = _attach({"t": specs["tokens"]},
                         shd.batch_shardings(mesh, {"t": specs["tokens"]}))["t"]
        cur = specs["cur_idx"]
        step = make_serve_step(cfg)
        lowered = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, tokens, cur)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rec["status"] = "ok"
    rec["lower_s"] = round(t_lower, 2)
    rec["compile_s"] = round(t_compile, 2)
    try:
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # CPU backend may not implement it
        rec["memory_analysis"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["cost_analysis"] = {
            k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "bytes accessed", "optimal_seconds")
                or k.startswith("bytes accessed"))}
    except Exception as e:
        rec["cost_analysis"] = {"error": str(e)}
    if collect_hlo:
        try:
            hlo = compiled.as_text()
            totals, counts, in_loop = collective_bytes(hlo)
            rec["collective_bytes"] = totals
            rec["collective_counts"] = counts
            rec["collective_bytes_in_loop"] = in_loop
            rec["hlo_chars"] = len(hlo)
            del hlo
        except Exception as e:
            rec["collective_bytes"] = {"error": str(e)}
    # analytic per-device weight+opt memory (CPU memory_analysis is partial)
    rec["arg_bytes_per_device"] = arg_bytes_per_device(
        mesh, params, None if shape.kind != "train" else opt)
    return rec


def arg_bytes_per_device(mesh, params, opt=None) -> int:
    """Exact per-device bytes of weights+optimizer given their shardings."""
    total = 0
    for leaf in jax.tree.leaves(params) + (jax.tree.leaves(opt) if opt else []):
        size = leaf.size * leaf.dtype.itemsize
        ns = getattr(leaf, "sharding", None)
        if ns is not None and ns.spec is not None:
            shards = 1
            for axes, dim in zip(ns.spec, leaf.shape):
                if axes is None:
                    continue
                for a in (axes,) if isinstance(axes, str) else axes:
                    shards *= mesh.shape.get(a, 1)
            size = -(-size // max(1, shards))
        total += size
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="*", default=sorted(ARCHS))
    ap.add_argument("--shapes", nargs="*", default=[s.name for s in SHAPES])
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="dryrun.json")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip HLO text parsing (faster)")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    records = []
    if args.append and os.path.exists(args.out):
        records = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in records}

    for mesh_name, mesh in meshes:
        for arch in args.archs:
            for shape_name in args.shapes:
                if (arch, shape_name, mesh_name) in done:
                    continue
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape_name, mesh, mesh_name,
                                   collect_hlo=not args.no_hlo)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                rec["wall_s"] = round(time.time() - t0, 2)
                records.append(rec)
                print(f"[{rec.get('status'):7s}] {mesh_name} {arch} "
                      f"{shape_name} ({rec['wall_s']}s)"
                      + (f" :: {rec.get('error', rec.get('reason', ''))}"
                         if rec.get("status") != "ok" else ""),
                      flush=True)
                json.dump(records, open(args.out, "w"), indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors -> {args.out}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
