import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the very first lines: jax locks the device count on first init.
# (No `from __future__ import annotations` here for the same reason: nothing
# may precede the env var except this comment and the os import.)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, WITHOUT allocating any real arrays
(ShapeDtypeStruct stand-ins only):

  * compiled.memory_analysis()  - proves the per-device footprint,
  * compiled.cost_analysis()    - HLO FLOPs / bytes for the roofline,
  * a collective-bytes breakdown parsed from the optimized HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand sizes),

and writes one JSON artifact per cell under --out (default
artifacts/dryrun).  EXPERIMENTS.md SDry-run and SRoofline are generated
from these artifacts by launch/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--mesh 4x4]
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

from repro.configs import ARCHS, get_config
from repro.configs.base import SHAPES
from repro.models import get_model
from repro.models.blueprint import abstract_params, count_params
from repro.models.registry import input_specs
from repro.launch.mesh import make_production_mesh, make_mesh
from repro.train.train_step import (StepConfig, jit_train_step,
                                    jit_prefill_step, jit_decode_step)

# ---- perf-iteration variants (EXPERIMENTS.md §Perf) ----------------------
import dataclasses as _dc

VARIANTS = {
    # beyond-paper: causal block skipping in chunked attention (the
    # tile-level divergence management of DESIGN.md §3)
    "skip_blocks": lambda c: _dc.replace(c, attn_skip_masked_blocks=True),
    # larger attention chunk (fewer scan steps, bigger tiles)
    "chunk1k": lambda c: _dc.replace(c, attn_chunk=1024),
    # chunked loss (no full-logits materialization)
    "loss_chunk": lambda c: _dc.replace(c, loss_chunk=512),
    "loss_full": lambda c: _dc.replace(c, loss_chunk=0),
    # naive attention baseline (paper-faithful floor for §Perf)
    "naive_attn": lambda c: _dc.replace(c, attn_impl="naive"),
    # bigger mamba chunk
    "ssm_chunk1k": lambda c: _dc.replace(c, ssm_chunk=1024),
    # bigger xlstm chunk (fewer inter-chunk corrections)
    "xlstm_chunk512": lambda c: _dc.replace(c, xlstm_chunk=512),
    "xlstm_chunk64": lambda c: _dc.replace(c, xlstm_chunk=64),
    # MoE capacity tightening
    "moe_cap1": lambda c: _dc.replace(c, moe_capacity=1.0),
    "moe_cap2": lambda c: _dc.replace(c, moe_capacity=2.0),
    # sequence parallelism on the residual stream (AR -> RS+AG)
    "seqpar": lambda c: _dc.replace(c, seq_shard_activations=True),
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))"
    r"[^=]*?\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)\b")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    out = {k: 0 for k in ("all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute")}
    counts = {k: 0 for k in out}
    for m in _COLL_RE.finditer(hlo_text):
        shapes_blob, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(shapes_blob):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] += nbytes
        counts[kind] += 1
    return {"bytes": out, "counts": counts}


def _cost_dict(compiled) -> dict:
    return dict(compiled.cost_analysis() or {})


def _mem_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {"available": False}
    if ma is None:
        return {"available": False}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    d = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            d[k] = int(v)
    d["available"] = bool(d)
    return d


def run_cell(arch: str, shape: str, mesh_spec: str, out_dir: Path,
             verbose: bool = True, variant: str = "") -> dict:
    cfg = get_config(arch)
    if variant:
        for v in variant.split("+"):
            cfg = VARIANTS[v](cfg)
    if shape not in cfg.applicable_shapes():
        rec = {"arch": arch, "shape": shape, "mesh": mesh_spec,
               "status": "skipped",
               "reason": f"{cfg.family} does not support {shape} "
                         "(see DESIGN.md SArch-applicability)"}
        _write(out_dir, rec)
        return rec

    if mesh_spec == "multipod":
        mesh = make_production_mesh(multi_pod=True)
    elif mesh_spec == "pod":
        mesh = make_production_mesh(multi_pod=False)
    else:
        dims = tuple(int(x) for x in mesh_spec.split("x"))
        names = ("data", "model")[:len(dims)] if len(dims) == 2 \
            else ("pod", "data", "model")
        mesh = make_mesh(dims, names)

    model = get_model(cfg)
    bp = model.blueprint()
    params_abs = abstract_params(bp)
    n_params = count_params(bp)
    kind = SHAPES[shape].kind
    t0 = time.time()

    with mesh:
        if kind == "train":
            step, (psh, osh, bsh) = jit_train_step(
                model, mesh, StepConfig(remat=True), shape)
            opt_abs = {
                "step": jax.ShapeDtypeStruct((), np.int32),
                "m": jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, np.float32),
                    params_abs),
                "v": jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, np.float32),
                    params_abs),
            }
            batch_abs = input_specs(cfg, shape)
            lowered = step.lower(params_abs, opt_abs, batch_abs)
        elif kind == "prefill":
            fn, (psh, bsh) = jit_prefill_step(model, mesh, shape)
            lowered = fn.lower(params_abs, input_specs(cfg, shape))
        else:  # decode / long_decode -> serve_step
            fn, (psh, bsh) = jit_decode_step(model, mesh, shape)
            lowered = fn.lower(params_abs, input_specs(cfg, shape))

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = _cost_dict(compiled)
    mem = _mem_analysis(compiled)
    coll = collective_bytes(compiled.as_text())

    # ---- scan-depth extrapolation --------------------------------------
    # XLA's cost_analysis counts a while/scan body ONCE; the layer stack
    # is a scan over n_periods, so flops/bytes/collectives must be
    # extrapolated: cost(P) = cost(1) + (P-1) * [cost(2) - cost(1)].
    try:
        extrap = _depth_extrapolate(cfg, shape, mesh, kind)
    except Exception as e:            # pragma: no cover
        extrap = {"error": f"{type(e).__name__}: {e}"}

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_spec,
        "variant": variant,
        "status": "ok",
        "n_devices": int(np.prod(mesh.devices.shape)),
        "mesh_shape": list(mesh.devices.shape),
        "n_params": int(n_params),
        "step_kind": kind,
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem,
        "collectives": coll,
        "extrapolated": extrap,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if verbose:
        ag = coll["bytes"]
        print(f"[dryrun] {arch:26s} {shape:12s} {mesh_spec:9s} "
              f"flops/dev={rec['flops']:.3e} bytes/dev="
              f"{rec['bytes_accessed']:.3e} "
              f"coll(AG/AR/RS/A2A)={ag['all-gather']:.2e}/"
              f"{ag['all-reduce']:.2e}/{ag['reduce-scatter']:.2e}/"
              f"{ag['all-to-all']:.2e} compile={t_compile:.1f}s",
              flush=True)
    _write(out_dir, rec)
    return rec


def _cost_of(cfg2, shape: str, mesh, kind: str) -> dict:
    """Compile one reduced-depth variant and return raw cost numbers."""
    model = get_model(cfg2)
    params_abs = abstract_params(model.blueprint())
    with mesh:
        if kind == "train":
            step, _ = jit_train_step(model, mesh, StepConfig(remat=True),
                                     shape)
            opt_abs = {
                "step": jax.ShapeDtypeStruct((), np.int32),
                "m": jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, np.float32),
                    params_abs),
                "v": jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, np.float32),
                    params_abs),
            }
            lowered = step.lower(params_abs, opt_abs,
                                 input_specs(cfg2, shape))
        elif kind == "prefill":
            fn, _ = jit_prefill_step(model, mesh, shape)
            lowered = fn.lower(params_abs, input_specs(cfg2, shape))
        else:
            fn, _ = jit_decode_step(model, mesh, shape)
            lowered = fn.lower(params_abs, input_specs(cfg2, shape))
        compiled = lowered.compile()
    cost = _cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll["bytes"]}


def _depth_extrapolate(cfg, shape: str, mesh, kind: str) -> dict:
    """cost(P) = cost(1 period) + (P-1) * per-period delta, measured on
    UNROLLED reduced-depth variants (XLA counts scan bodies once)."""
    import dataclasses
    pat = len(cfg.layer_pattern())
    P = cfg.n_layers // pat
    if P < 2:
        c1 = _cost_of(dataclasses.replace(cfg, unroll_stack=True),
                      shape, mesh, kind)
        return {"periods": P, "flops": c1["flops"], "bytes": c1["bytes"],
                "coll": c1["coll"], "method": "exact-1"}

    def variant(k: int):
        kw = {"n_layers": k * pat, "unroll_stack": True}
        if cfg.enc_dec:
            kw["enc_layers"] = k
        return dataclasses.replace(cfg, **kw)

    c1 = _cost_of(variant(1), shape, mesh, kind)
    c2 = _cost_of(variant(2), shape, mesh, kind)
    out = {"periods": P, "method": "linear-extrapolation"}
    out["flops"] = c1["flops"] + (P - 1) * (c2["flops"] - c1["flops"])
    out["bytes"] = c1["bytes"] + (P - 1) * (c2["bytes"] - c1["bytes"])
    out["coll"] = {k: c1["coll"][k] + (P - 1) * (c2["coll"][k]
                                                 - c1["coll"][k])
                   for k in c1["coll"]}
    return out


def _write(out_dir: Path, rec: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    if rec.get("variant"):
        name = name.replace(".json", f"__{rec['variant']}.json")
    (out_dir / name).write_text(json.dumps(rec, indent=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod",
                    help="pod | multipod | AxB (e.g. 4x4)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="",
                    help="'+'-joined names from VARIANTS (SPerf knobs)")
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    mesh_spec = "multipod" if args.multi_pod else args.mesh
    cells = []
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    for a in archs:
        shapes = (list(SHAPES) if (args.all or args.shape is None)
                  else [args.shape])
        for s in shapes:
            cells.append((a, s))

    failures = 0
    for a, s in cells:
        fname = out_dir / f"{a}__{s}__{mesh_spec}.json"
        if args.skip_existing and fname.exists():
            prev = json.loads(fname.read_text())
            if prev.get("status") in ("ok", "skipped"):
                print(f"[dryrun] skip existing {a} {s}", flush=True)
                continue
        try:
            run_cell(a, s, mesh_spec, out_dir, variant=args.variant)
        except Exception as e:
            failures += 1
            rec = {"arch": a, "shape": s, "mesh": mesh_spec,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            _write(out_dir, rec)
            print(f"[dryrun] FAIL {a} {s}: {e}", flush=True)
    print(f"[dryrun] done, {failures} failures", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
