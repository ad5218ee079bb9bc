"""Benchmark harness — one section per paper table/figure.

Outputs ``name,us_per_call,derived`` CSV lines (plus human-readable
markdown tables above them).  Sections:

  divergence_opt : Fig 7 (instruction reduction) + Fig 8 (speedups)
  isa_ext        : Fig 9 (vote/shuffle/aggregated-atomic ISA extensions)
  sharedmem      : Fig 10 (shared-memory mapping under cache configs)
  compile_time   : SS5.2 compile-time overhead geomean + analysis-cache
                   before/after + persistent-disk-cache second process
  interp_speed   : decoded-interpreter vs instruction-at-a-time executor
  interp_speed_batched : workgroup-batched lockstep executor on
                   multi-warp workgroups
  interp_speed_ragged : vx_pred loop ride-along on ragged-loop kernels
                   vs the desync-on-mixed-exit (PR 2) executor
  interp_speed_grid : grid-level batching of single-warp workgroups vs
                   the per-workgroup decoded executor
  interp_speed_grid_mw : multi-warp grid batching (whole workgroups as
                   grouped rows, per-workgroup barrier groups) vs
                   per-workgroup dispatch
  interp_speed_mem : vectorized/analytic coalescing engine +
                   private-shared-tile grid batching on the
                   memory-bound benches vs the PR 4 configuration
  interp_speed_jax : certified jax-codegen rung (whole-kernel XLA
                   compilation, tiered fast/exact executables) vs the
                   grid executor on every licensed bench
  interp_speed_parallel : host-parallel grid dispatcher — decode-
                   licensed grid chunks farmed across the worker pool
                   vs the sequential chunk walk on large-grid launches,
                   parity-gated bit-identical at every worker count
  bench_robust   : fault-isolation costs — transactional-snapshot
                   overhead on the clean path (<5% acceptance) and
                   degraded-mode throughput per executor rung
                   (docs/robustness.md)
  bench_serve    : multi-tenant small-launch streaming — LaunchService
                   continuous launch batching + pooled staging tables
                   vs per-launch dispatch, parity-gated (launches/sec,
                   p50/p99 latency, >= 2x acceptance)
  kernels        : Pallas kernel vs jnp-oracle timings (CPU interpret)
  roofline       : per (arch x shape x mesh) three-term roofline rows

Running the perf sections also writes a machine-readable
``BENCH_perf.json`` next to this file with the measured speedups, so CI /
later sessions can diff regressions:

  python benchmarks/run.py                # everything
  python benchmarks/run.py perf          # just the perf sections + JSON
  python benchmarks/run.py perf --check  # measure fresh and exit non-zero
                                          # on a >20% regression against
                                          # the committed BENCH_perf.json
  python benchmarks/run.py perf --profile # additionally run each section
                                          # under cProfile and print its
                                          # top functions by cumulative
                                          # time — so the NEXT hot-spot
                                          # hunt starts from data, not
                                          # folklore
"""
import cProfile
import io
import json
import pstats
import sys
from pathlib import Path

PERF_JSON = Path(__file__).resolve().parent.parent / "BENCH_perf.json"

# speedup-type aggregates (higher is better) compared by ``--check``;
# a fresh value below (1 - tolerance) x committed fails
CHECKED_METRICS = [
    ("interp_speed", "suite_speedup"),
    ("interp_speed", "geomean_speedup"),
    ("interp_speed_batched", "suite_speedup"),
    ("interp_speed_batched", "geomean_speedup"),
    ("interp_speed_ragged", "suite_speedup"),
    ("interp_speed_ragged", "geomean_speedup"),
    ("interp_speed_grid", "suite_speedup"),
    ("interp_speed_grid", "geomean_speedup"),
    ("interp_speed_grid_mw", "suite_speedup"),
    ("interp_speed_grid_mw", "geomean_speedup"),
    ("interp_speed_mem", "suite_speedup"),
    ("interp_speed_mem", "geomean_speedup"),
    # certified jax rung vs the grid executor, geomean over the
    # steady-state kernels (fast-tier certified, launch long enough to
    # amortize dispatch) — the headline claim for the codegen backend
    ("interp_speed_jax", "steady_geomean_speedup"),
    ("interp_speed_jax", "steady_suite_speedup"),
    # host-parallel dispatch vs sequential chunk walk on the large-grid
    # bench set — the PR 10 headline (acceptance floor 1.5x at 4 workers)
    ("interp_speed_parallel", "parallel_geomean_speedup"),
    ("compile_time", "suite_speedup"),
    # clean/transactional wall-time ratio: a drop below the committed
    # value means the degradation chain's snapshot got more expensive
    ("bench_robust", "snapshot_clean_geomean"),
    # ungoverned/governed wall-time ratio: a drop means the armed-but-
    # untripped governor (deadline polling, budget accounting, breaker
    # bookkeeping) got more expensive
    ("bench_robust", "governed_clean_geomean"),
    # demoted-walk/pinned wall-time ratio: a drop means an open breaker
    # no longer buys back the doomed fast-path attempt during outages
    ("bench_robust", "breaker_pinned_recovery"),
    # coalesced-vs-solo wall-time ratio on small-launch streaming —
    # the launch service's headline claim (acceptance floor 2x)
    ("bench_serve", "coalesce_speedup"),
]

#: top-N functions shown per section under ``--profile``
PROFILE_TOP_N = 15
# Default tolerance.  A single global knob lets noisy, small entries
# (sub-ms compile timings, tiny kernels) mask real regressions in big
# ones, so the committed BENCH_perf.json may override it per entry under
# a top-level "check_tolerances" key:
#
#   "check_tolerances": {"compile_time.suite_speedup": 0.35,
#                        "interp_speed_ragged.geomean_speedup": 0.15}
#
# The key is preserved across `perf` rewrites (the writer only updates
# measured sections).
REGRESSION_TOLERANCE = 0.20


def _write_perf_json(perf: dict) -> None:
    existing = {}
    if PERF_JSON.exists():
        try:
            existing = json.loads(PERF_JSON.read_text())
        except Exception:
            existing = {}
    existing.update(perf)
    PERF_JSON.write_text(json.dumps(existing, indent=1, sort_keys=True))
    print(f"\n[run] wrote {PERF_JSON}", flush=True)


def check_regressions(fresh: dict, committed: dict,
                      tolerance: float = REGRESSION_TOLERANCE) -> list:
    """Compare fresh aggregate speedups against the committed baseline;
    returns a list of human-readable regression descriptions.  Per-entry
    tolerances from the committed file's "check_tolerances" key override
    the global default."""
    overrides = committed.get("check_tolerances", {})
    failures = []
    for section, metric in CHECKED_METRICS:
        base = committed.get(section, {}).get("aggregate", {}).get(metric)
        new = fresh.get(section, {}).get("aggregate", {}).get(metric)
        if base is None:
            continue         # no committed baseline for this metric yet
        if new is None:
            # a section/metric present in the committed baseline but
            # absent from the fresh run is a CHECK FAILURE, not a skip —
            # a wiring regression (section renamed, driver dropped,
            # bench crashed into a partial dict) must not silently pass
            failures.append(
                f"{section}.{metric}: missing from fresh run "
                f"(committed {base:.3f})")
            continue
        tol = overrides.get(f"{section}.{metric}", tolerance)
        if new < base * (1.0 - tol):
            failures.append(
                f"{section}.{metric}: {new:.3f} vs committed {base:.3f} "
                f"({new / base - 1:+.1%}, tolerance -{tol:.0%})")
    return failures


def main() -> None:
    from repro.core.runtime import use_jax_compile_cache
    use_jax_compile_cache()
    from benchmarks import (compile_time, divergence_opt, interp_speed,
                            isa_ext, kernels_bench, robustness,
                            roofline_bench, serve_bench, sharedmem)
    sections = [
        ("divergence_opt", divergence_opt.main),
        ("isa_ext", isa_ext.main),
        ("sharedmem", sharedmem.main),
        ("compile_time", compile_time.main),
        ("interp_speed", interp_speed.main),
        ("interp_speed_batched", interp_speed.main_batched),
        ("interp_speed_ragged", interp_speed.main_ragged),
        ("interp_speed_grid", interp_speed.main_grid),
        ("interp_speed_grid_mw", interp_speed.main_grid_mw),
        ("interp_speed_mem", interp_speed.main_mem),
        ("interp_speed_jax", interp_speed.main_jax),
        ("interp_speed_parallel", interp_speed.main_parallel),
        ("bench_robust", robustness.main),
        ("bench_serve", serve_bench.main),
        ("kernels", kernels_bench.main),
        ("roofline", roofline_bench.main),
    ]
    args = [a for a in sys.argv[1:]]
    check = "--check" in args
    profile = "--profile" in args
    args = [a for a in args if a not in ("--check", "--profile")]
    only = args[0] if args else None
    perf_sections = {"interp_speed", "interp_speed_batched",
                     "interp_speed_ragged", "interp_speed_grid",
                     "interp_speed_grid_mw", "interp_speed_mem",
                     "interp_speed_jax", "interp_speed_parallel",
                     "compile_time", "bench_robust", "bench_serve"}
    perf: dict = {}
    for name, fn in sections:
        if only == "perf":
            if name not in perf_sections:
                continue
        elif only and name != only:
            continue
        print(f"\n{'='*72}\n== {name}\n{'='*72}", flush=True)
        if profile:
            prof = cProfile.Profile()
            prof.enable()
            result = fn()
            prof.disable()
            buf = io.StringIO()
            pstats.Stats(prof, stream=buf).sort_stats(
                "cumulative").print_stats(PROFILE_TOP_N)
            print(f"\n[run] --profile: top {PROFILE_TOP_N} by cumulative "
                  f"time for section {name}", flush=True)
            # strip the pstats banner down to the table
            lines = buf.getvalue().splitlines()
            start = next((j for j, ln in enumerate(lines)
                          if ln.lstrip().startswith("ncalls")), 0)
            print("\n".join(lines[start:start + PROFILE_TOP_N + 1]),
                  flush=True)
        else:
            result = fn()
        if name in perf_sections and isinstance(result, dict):
            perf[name] = result
    if not perf:
        return
    if profile:
        # launch-engine telemetry accumulated across the profiled
        # sections: which executor rungs actually served the launches,
        # and whether any degraded (docs/robustness.md)
        from repro.core.runtime import LAUNCH_TELEMETRY
        t = LAUNCH_TELEMETRY
        print(f"\n[run] --profile launch telemetry: "
              f"{t['launches']} launches, by executor "
              f"{dict(t['by_executor'])}, {t['demotions']} demotions "
              f"{dict(t['demotion_reasons'])}, "
              f"{t['engine_faults']} engine faults, "
              f"{t['kernel_faults']} kernel faults", flush=True)
        # profiled timings carry cProfile overhead — never let them
        # replace the committed baseline numbers or trip the
        # regression gate
        print("\n[run] --profile run: BENCH_perf.json left untouched, "
              "--check skipped", flush=True)
        return
    if check:
        committed = {}
        if PERF_JSON.exists():
            try:
                committed = json.loads(PERF_JSON.read_text())
            except Exception:
                committed = {}
        failures = check_regressions(perf, committed)
        if failures:
            print("\n[run] PERF REGRESSION (>"
                  f"{REGRESSION_TOLERANCE:.0%} below committed "
                  f"{PERF_JSON.name}):", flush=True)
            for f in failures:
                print(f"  {f}", flush=True)
            sys.exit(1)
        print(f"\n[run] perf check OK: no metric more than "
              f"{REGRESSION_TOLERANCE:.0%} below {PERF_JSON.name} "
              f"(committed file left untouched)", flush=True)
    else:
        _write_perf_json(perf)


if __name__ == "__main__":
    main()
