import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual devices; never the chip

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) cell, on the single-pod 16×16
mesh AND the 2×16×16 multi-pod mesh:

    lowered  = jax.jit(step, in_shardings=…).lower(**input_specs)
    compiled = lowered.compile()
    compiled.memory_analysis() / cost_analysis()

Success = the jit lowers, SPMD-partitions over all 512 placeholder
devices, and compiles without sharding mismatches or OOM.  Each cell's
FLOPs / bytes / per-collective byte counts are written to
``bench_artifacts/dryrun/<arch>__<shape>__<mesh>.json`` — the roofline
analysis (benchmarks/roofline.py, EXPERIMENTS.md §Roofline) reads them.

NOTE the XLA_FLAGS line above MUST precede any jax import (device count
locks on first init); smoke tests / benches see 1 device because only
this module sets it.
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import (get_config, smoke_config, ARCH_NAMES, SHAPES,
                       input_specs, shape_applicable)
from ..models.lm import (init_model, init_decode_cache,
                         model_trainable_mask)
from ..optim.optimizers import AdamWConfig, init_opt_state
from .mesh import make_production_mesh
from .sharding import (param_shardings, batch_shardings, cache_shardings,
                       opt_state_shardings, replicated)
from .steps import build_update_step, build_prefill_step
from ..models.lm import build_serve_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "bench_artifacts", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-op RESULT bytes from the (per-device SPMD) HLO.

    For all-reduce / all-to-all / collective-permute the result size is
    the per-device payload; all-gather's result is the gathered size
    (≈ bytes moved per device over a ring); reduce-scatter's payload is
    its input ≈ result × world — we approximate with the declared
    operand type where present on the def line.
    """
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^[%\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s/]+?)\s+"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", s)
        if not m:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
        out["count"] += 1
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             smoke: bool = False, periods: int | None = None,
             unroll: bool = False, cfg_override=None) -> dict:
    """One dry-run cell.  ``periods``: override the layer-stack depth to
    this many periods (same widths) — used by the roofline driver's
    2-point extrapolation (``unroll=True`` replaces the lax.scan with an
    unrolled stack so cost_analysis counts every layer; full-depth
    FLOPs are then f(L) = f(1) + (L−1)·(f(2)−f(1)))."""
    import dataclasses as _dc
    from ..models.lm import period_plan
    cfg = cfg_override if cfg_override is not None else (
        smoke_config(arch) if smoke else get_config(arch))
    if periods is not None:
        plan, n_periods = period_plan(cfg)
        cfg = _dc.replace(
            cfg, n_layers=len(plan) * periods,
            n_enc_layers=periods if cfg.n_enc_layers else 0)
    if unroll:
        cfg = _dc.replace(cfg, unroll=True)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    batch = input_specs(cfg, shape)
    pshapes = jax.eval_shape(lambda k: init_model(k, cfg),
                             jax.random.PRNGKey(0))
    pshard = param_shardings(mesh, pshapes)
    bshard = batch_shardings(mesh, batch)
    rep = replicated(mesh)

    with mesh:
        if shape.kind == "train":
            step = build_update_step(cfg, AdamWConfig())
            oshapes = jax.eval_shape(
                lambda p: init_opt_state(p, model_trainable_mask(p)), pshapes)
            oshard = opt_state_shardings(mesh, oshapes, pshard)
            lowered = jax.jit(
                step, in_shardings=(pshard, oshard, bshard, rep),
                donate_argnums=(0, 1)).lower(pshapes, oshapes, batch, key)
        elif shape.kind == "prefill":
            step = build_prefill_step(cfg)
            lowered = jax.jit(step, in_shardings=(pshard, bshard)
                              ).lower(pshapes, batch)
        else:   # decode
            step = build_serve_step(cfg)
            cshapes = jax.eval_shape(
                lambda: init_decode_cache(cfg, shape.global_batch,
                                          shape.seq_len))
            cshard = cache_shardings(mesh, cshapes, shape.global_batch)
            lowered = jax.jit(
                step, in_shardings=(pshard, cshard, bshard),
                donate_argnums=(1,)).lower(pshapes, cshapes, batch)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": dict(mesh.shape), "n_devices": n_dev,
        "status": "ok",
        "kind": shape.kind,
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": ca.get("flops", 0.0),
        "bytes_per_device": ca.get("bytes accessed", 0.0),
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
        } if mem is not None else None,
    }
    return rec


def cell_list(archs, shapes):
    cells = []
    for a in archs:
        for s in shapes:
            cells.append((a, s))
    return cells


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (debug)")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch, shape in cell_list(archs, shapes):
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
            try:
                rec = run_cell(arch, shape, mp, smoke=args.smoke)
            except Exception as e:   # a failure here is a bug in our system
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single",
                       "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
            st = rec["status"]
            n_ok += st == "ok"
            n_skip += st == "skipped"
            n_fail += st == "FAIL"
            extra = ""
            if st == "ok":
                extra = (f" flops/dev={rec['flops_per_device']:.3g}"
                         f" coll={rec['collectives']['count']}"
                         f" t={rec['compile_s']}s")
            elif st == "FAIL":
                extra = " " + rec["error"][:160]
            print(f"[{st:7s}] {tag}{extra}", flush=True)
    print(f"\ndry-run summary: ok={n_ok} skipped={n_skip} FAILED={n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
