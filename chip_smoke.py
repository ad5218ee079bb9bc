"""Smoke run of the compiler's launch path on one TPU chip.

    python chip_smoke.py

One process, one chip.  Four suite kernels go through the front end,
the pass pipeline and ``Runtime(jax=True).launch`` at the sizes their
users run (``suite.USER_SIZES``), each lowering a different way:

  vecadd    2^22 elements  streaming gathers / scatters
  sgemm     512^3          uniform loop with float accumulation
  spmv_csr  2^18 rows      ragged ``vx_pred`` loop
  reduce0   2^22 elements  shared tile + barriers, one warp per group

Each kernel runs one cold launch (trace, compile, differential
certification against the host chain) and a few warm launches; every
output is compared with the suite's numpy reference.  A few requests
from two tenants then go through ``LaunchService.submit``.  Earlier
lines report, per kernel, the rung that served each launch, the
certification verdict and tier, compile and certification seconds, the
median warm launch time, the rung's telemetry and the device.

The last line is ``{"ok": true, "device": {...}}`` only when every output
matched, vecadd and at least one loop kernel were served by the jax rung
on warm launches, and no launch met a compile or device error.  Without
a TPU the script exits non-zero before launching anything.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path
from statistics import median
from time import perf_counter

WARM = 3
LOOP_KERNELS = ("sgemm", "spmv_csr", "reduce0")
SERVE_KERNEL = "vecadd"
SERVE_REQUESTS = 2          # per tenant


def require_tpu():
    """The chip's ``jax.Device``; exits non-zero on any other platform."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
                 f"({dev.device_kind})")
    return dev


def import_repro():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke: the repository's src/repro is not at {src}")
    sys.path.insert(0, str(src))


def mismatch(bench, got: dict, want: dict) -> str | None:
    """The first output outside the suite's tolerance, or None."""
    import numpy as np
    for k in sorted(want):
        ok = np.isclose(got[k], want[k], atol=bench.atol, rtol=1e-3)
        if not ok.all():
            i = int(np.flatnonzero(~ok.reshape(-1))[0])
            return (f"{k}[{i}] = {got[k].reshape(-1)[i]!r}, reference "
                    f"{want[k].reshape(-1)[i]!r} ({int((~ok).sum())} "
                    f"elements off)")
    return None


def run_kernel(rt, name: str, maker_args: dict, where: str) -> dict:
    import numpy as np
    from repro.core.backends import jaxgen
    from repro.core.runtime import compile_kernel
    from repro.volt_bench.suite import BENCHES

    b = BENCHES[name]
    bufs0, scalars, params = b.make(np.random.default_rng(0), **maker_args)
    # the oracle's instruction budget, raised to what the jax rung's
    # int32 counters allow (a 512^3 sgemm issues ~10^8 warp instructions)
    params = dataclasses.replace(params, fuel=jaxgen.FUEL_MAX)
    want = b.ref({k: v.copy() for k, v in bufs0.items()}, scalars)
    fn = compile_kernel(b.handle).fn
    size = dict(maker_args)
    if name == "spmv_csr":
        size["nnz_per_row"] = round(len(bufs0["cols"]) / scalars["n"], 2)
    tel0 = dict(jaxgen.JAX_TELEMETRY)
    executors, times, bad = [], [], None
    for i in range(1 + WARM):
        bufs = {k: v.copy() for k, v in bufs0.items()}
        t0 = perf_counter()
        rt.launch(fn, grid=params.grid, block=params.local_size,
                  scalar_args=scalars, buffers=bufs, fuel=params.fuel)
        # the results are host arrays on return: the time ends in a sync
        times.append((perf_counter() - t0) * 1e3)
        rep = rt.last_report
        executors.append(rep.executor)
        if rep.demotions:
            print(f"{name}: launch {i} demoted: {rep.summary()}; "
                  f"{[a.reason for a in rep.attempts]}", flush=True)
        bad = bad or mismatch(b, bufs, want)

    d = jaxgen.describe(fn, params, bufs0, scalars)
    tel = {k: v - tel0[k] for k, v in jaxgen.JAX_TELEMETRY.items()
           if v != tel0[k]}
    print(f"{name} on {where}: size {size} (grid {params.grid} x "
          f"{params.local_size}); executors cold..warm {executors}; "
          f"verdict {d['verdict']} tier {d['tier']}; trace {d['trace_s']} s,"
          f" compile {d['compile_s']} s, certification {d['cert_s']} s; "
          f"cold launch {times[0]:.1f} ms, warm median "
          f"{median(times[1:]):.3f} ms of {times[1:]}; JAX_TELEMETRY {tel}",
          flush=True)
    if d["detail"] or d["refused"]:
        print(f"{name}: certification detail: {d['detail'] or d['refused']}",
              flush=True)
    if bad:
        print(f"{name}: OUTPUT MISMATCH {bad}", flush=True)
    return {"executors": executors, "mismatch": bad}


def serve(rt, where: str) -> dict:
    """A few launches from two tenants through the launch service."""
    import numpy as np
    from repro.core.runtime import LaunchService, compile_kernel
    from repro.volt_bench.suite import BENCHES, USER_SIZES

    b = BENCHES[SERVE_KERNEL]
    fn = compile_kernel(b.handle).fn
    svc = LaunchService(rt)
    jobs = []
    t0 = perf_counter()
    for r in range(SERVE_REQUESTS):
        for tenant in ("a", "b"):
            bufs, scalars, params = b.make(
                np.random.default_rng(len(jobs) + 1), **USER_SIZES[b.name])
            want = b.ref({k: v.copy() for k, v in bufs.items()}, scalars)
            h = svc.submit(fn, grid=params.grid, block=params.local_size,
                           buffers=bufs, scalar_args=scalars, tenant=tenant)
            jobs.append((h, bufs, want))
    svc.flush()
    wall_ms = (perf_counter() - t0) * 1e3
    out = {"kernel": SERVE_KERNEL, "requests": len(jobs), "device": 0,
           "host_grid": 0, "other": 0, "errors": [], "mismatch": None,
           "modes": []}
    for h, bufs, want in jobs:
        if h.error is not None:
            out["errors"].append(f"{type(h.error).__name__}: {h.error}")
            continue
        ex = h.report.executor if h.report else None
        key = {"jax": "device", "grid": "host_grid"}.get(ex, "other")
        out[key] += 1
        out["modes"].append(h.mode)
        out["mismatch"] = out["mismatch"] or mismatch(b, bufs, want)
    print(f"serve on {where}: {out['requests']} {SERVE_KERNEL} requests from "
          f"2 tenants at {USER_SIZES[SERVE_KERNEL]} in {wall_ms:.1f} ms; "
          f"served on the device {out['device']}, on the host grid walk "
          f"{out['host_grid']}, other {out['other']}; modes {out['modes']}; "
          f"errors {out['errors']}", flush=True)
    return out


def main() -> int:
    dev = require_tpu()
    import_repro()
    import jax
    from repro.core import interp, runtime
    from repro.core.backends import jaxgen
    from repro.core.runtime import LAUNCH_TELEMETRY, Runtime
    from repro.volt_bench.suite import USER_SIZES

    runtime.use_jax_compile_cache()
    # verdicts stay in memory: one persisted by an earlier run would skip
    # the certification this smoke exists to exercise
    interp.JAX_CERT_HOOKS = None
    where = f"{dev.platform} {dev.device_kind}"
    print(f"device: {where}, {len(jax.devices())} device(s), jax "
          f"{jax.__version__}; sizes as in suite.USER_SIZES, no cut; "
          f"compile cache {jax.config.jax_compilation_cache_dir}",
          flush=True)
    rt = Runtime(jax=True)
    rows = {n: run_kernel(rt, n, USER_SIZES[n], where) for n in USER_SIZES}
    svc = serve(rt, where)
    tel = dict(jaxgen.JAX_TELEMETRY)
    print(f"JAX_TELEMETRY on {where}: {json.dumps(tel)}", flush=True)
    print(f"LAUNCH_TELEMETRY by_executor: "
          f"{dict(LAUNCH_TELEMETRY['by_executor'])}", flush=True)

    def warm_on_jax(n):
        return all(e == "jax" for e in rows[n]["executors"][1:])

    problems = [f"{n}: output mismatch {r['mismatch']}"
                for n, r in rows.items() if r["mismatch"]]
    if svc["mismatch"] or svc["errors"]:
        problems.append(f"serve: {svc['mismatch'] or svc['errors']}")
    if not warm_on_jax("vecadd"):
        problems.append("vecadd warm launches not served by the jax rung")
    if not any(warm_on_jax(n) for n in LOOP_KERNELS):
        problems.append("no loop kernel served by the jax rung when warm")
    for key in ("compile_errors", "cert_errors", "demotions"):
        if tel[key]:
            problems.append(f"jax rung {key} = {tel[key]}")
    for p in problems:
        print(f"FAILED: {p}", flush=True)
    print(json.dumps({"ok": not problems,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
