"""One run of one cell: set-up, the measured window, the comparison
with the reference, and the result line.

The process's environment is fixed by :func:`prepare_env` before JAX
or the program is imported; :func:`run` then needs the accelerator the
cell asks for, or ``rehearse=True``, which runs the same path on the
CPU at the configurations' rehearsal sizes and reports no metric.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
from contextlib import nullcontext
from pathlib import Path
from time import perf_counter

from chipbench import drivers, spec, tracing
from chipbench.drivers import log

PEAKS = spec.HERE / "peaks.json"
#: a traced run traces a window of at most this many seconds: a longer
#: trace would not be read within a run's time limit
TRACE_SECONDS = 10.0


def prepare_env(root: Path = spec.ROOT) -> None:
    """Caches inside the checkout at fixed paths, no knob of the program
    from the caller's environment, and the program on ``sys.path``."""
    for k in [k for k in os.environ if k.startswith("VOLT_")]:
        del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".cache" / "jax")
    os.environ["TPU_LOG_DIR"] = "disabled"
    src = root / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: the program is not at {src}/repro")
    for p in (str(root), str(src)):
        if p not in sys.path:
            sys.path.insert(0, p)


def devices(chips: int, rehearse: bool) -> list:
    """The devices the cell runs on; exits non-zero without them."""
    import jax
    devs = jax.devices()
    if rehearse:
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found "
                       f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                       f"found {len(devs)}")
    return devs[:chips]


def peak_of(kind: str) -> dict:
    peaks = json.loads(PEAKS.read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return peaks[kind]


class RunView:
    """What a per-layer metric's reader may look at."""

    def __init__(self, cell, driver, out, trace, kind):
        self.cell, self.driver, self.out, self.trace = cell, driver, out, trace
        self.device_kind = kind

    @property
    def launches(self) -> int:
        return self.out.attempted

    def share(self, values: list, want) -> float | None:
        return (sum(v == want for v in values) / len(values)
                if values else None)

    def roofline(self) -> float | None:
        """Percent of the least device time the algorithm's operations
        and bytes need at the chip's peaks, over the device's busy time
        per launch.  None without a device trace."""
        if self.trace is None or not self.out.attempted \
                or self.trace["busy_s"] <= 0:
            return None
        peak = peak_of(self.device_kind)
        flops, nbytes = self.cell.ref.work(self.driver.scalars)
        least = max(flops / peak["flops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
        return 100.0 * least / (self.trace["busy_s"] / self.out.attempted)


def setup(name: str, seed: int, rehearse: bool = False,
          t0: float | None = None):
    """The cell, its devices and its driver, built and warmed up; logs
    how long each step of set-up took."""
    t0 = perf_counter() if t0 is None else t0
    cell = spec.load_cell(name)
    devs = devices(cell.chips, rehearse)
    import jax
    from repro.core.runtime import Runtime
    dev = devs[0]
    log(f"chipbench {name}: seed {seed} on {dev.platform} {dev.device_kind}"
        f" x{len(jax.devices())}, jax {jax.__version__}, compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = drivers.DRIVERS[cell.traffic["driver"]](
        cell, Runtime(jax=True), rehearse)
    marks = [("start, imports and JAX's devices", perf_counter())]
    driver.make_inputs(seed)
    marks.append(("inputs", perf_counter()))
    driver.build()
    marks.append(("build", perf_counter()))
    driver.warm()
    marks.append(("warm-up", perf_counter()))
    split, last = [], t0
    for what, t in marks:
        split.append(f"{what} {t - last:.3f} s")
        last = t
    log("set-up split: " + ", ".join(split))
    return cell, devs, driver


def run(name: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, t0: float | None = None,
        window_hook=None) -> dict:
    """One run; returns the result line as a dict.  ``window_hook`` is
    a context manager entered around the window alone (the control and
    the planted faults).  A traced run measures a window of at most
    ``TRACE_SECONDS``."""
    t0 = perf_counter() if t0 is None else t0
    cell, devs, driver = setup(name, seed, rehearse, t0)
    import jax
    dev = devs[0]
    setup_s = perf_counter() - t0
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with window_hook or nullcontext():
        out = driver.window(min(seconds, TRACE_SECONDS) if trace
                            else seconds)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        path = tracing.find_trace(tdir)
        summary = tracing.reduce(path) if path else None
        shutil.rmtree(tdir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats),
                     default=0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    log(f"window: {out.attempted} launches in {out.seconds:.4f} s, "
        f"{out.failed} failed; executors {sorted(set(out.executors))}; "
        f"trace {summary and {k: summary[k] for k in ('busy_s', 'window_s', 'programs')}}")

    compared = drivers.compare(driver, out)
    limits = cell.config["limits"]
    correct = bool(out.kept) and all(compared[k] <= limits[k]
                                     for k in limits)
    metrics = {}
    if trace:
        view = RunView(cell, driver, out, summary, dev.device_kind)
        for m in cell.per_layer:
            v = cell.readers[m["name"]](view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        # ``<quantity>.<part>`` reports the driver's ``<quantity>``: one
        # quantity under a bound of its own in each cell that names it
        values = dict(driver.end_to_end(out), setup_s=setup_s)
        for m in cell.end_to_end:
            v = values.get(m["name"].split(".")[0])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if rehearse:
        # a CPU run reports no metric under a device metric's name
        result["rehearsal_metrics"] = result.pop("metrics")
        result["metrics"] = {}
        result["device"].pop("busy_s", None)
        result["device"].pop("window_s", None)
        result.pop("breakdown", None)
    result["router"] = {str(k): v for k, v in driver.routes.items()}
    result["compared"] = {k: {"value": compared[k], "limit": limits[k]}
                          for k in limits}
    return result


def report(result: dict) -> None:
    """The compared numbers as the last lines on standard error, then
    the result as the last line on standard output."""
    for k, v in result["compared"].items():
        log(f"compared {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
