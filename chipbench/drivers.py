"""The general traffic drivers, by the name a mix's ``driver`` gives.

A driver makes its inputs from the seed, warms up every shape its
window will use, runs the window, and keeps what each launch of the
window wrote, for the comparison after it.  Its parameters come from
``traffic/<mix>.json``; its sizes from the cell's configuration.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
from time import perf_counter

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def span(what: str):
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + what)


def settled(d: dict) -> bool:
    """Whether the jax rung's route for a shape class is fixed: refused,
    failed, or certified with both of the router's timings recorded."""
    return bool(d["refused"] or d["verdict"] in ("fail", "error")
                or (d["jax_ms"] is not None and d["grid_ms"] is not None))


def route(d: dict) -> dict:
    """The router's choice for a shape class, from ``jaxgen.describe``,
    with what it was made from."""
    from repro.core.backends import jaxgen
    if d["refused"] or d["verdict"] not in ("pass", "pass-exact"):
        where = "host"
    elif d["grid_ms"] < d["jax_ms"] * jaxgen._ROUTE_MARGIN:
        where = "grid"
    else:
        where = "jax"
    return {"route": where, **{k: d[k] for k in (
        "verdict", "jax_ms", "grid_ms", "cert_s", "refused")}}


def ulp_gap(got: np.ndarray, want: np.ndarray) -> int:
    """Largest distance in units of the last place between two arrays
    (exact difference for integers)."""
    if got.shape != want.shape:
        return 2**31
    if got.dtype.kind != "f":
        return int(np.abs(got.astype(np.int64) - want.astype(np.int64))
                   .max(initial=0))
    w = 4 if got.dtype.itemsize == 4 else 8
    it = np.int32 if w == 4 else np.int64

    def ordered(a):
        i = np.ascontiguousarray(a).view(it).astype(np.int64)
        return np.where(i < 0, -(i & (2**(8 * w - 1) - 1)), i)

    both_nan = np.isnan(got) & np.isnan(want)
    d = np.abs(ordered(got) - ordered(want))
    return int(np.where(both_nan, 0, d).max(initial=0))


@dataclasses.dataclass
class Outcome:
    """What a window did, for the metrics and the comparison."""
    t_start: float = 0.0
    t_end: float = 0.0
    attempted: int = 0
    failed: int = 0
    executors: list = dataclasses.field(default_factory=list)
    kept: list = dataclasses.field(default_factory=list)  # (key, outputs)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


class Solo:
    """One caller, closed loop, warm ``Runtime.launch`` calls back to
    back.  Before each launch the caller writes that launch's inputs
    into the same host arrays: the seeded set, with an offset added to
    every floating-point input that repeats only every ``offset_period``
    launches.  So a program that served a launch from an earlier
    launch's inputs answers wrongly.  A share of
    the launches, drawn from the seed, write a fresh zeroed output
    buffer that is kept for the comparison; the others write one reused
    buffer."""

    def __init__(self, cell, rt, rehearse: bool = False):
        self.cell, self.rt = cell, rt
        cfg = cell.config
        self.size = cfg["rehearsal"]["size"] if rehearse else cfg["size"]
        self.block = cfg["block"]
        self.fuel = cfg["fuel"]
        self.outputs = cfg["outputs"]
        self.compile: dict = {}
        self.routes: dict = {}

    def params(self, grid: int):
        from repro.core.interp import LaunchParams
        p = LaunchParams(grid=grid, local_size=self.block,
                         warp_size=self.rt.warp_size)
        return p if self.fuel is None else \
            dataclasses.replace(p, fuel=self.fuel)

    def make_inputs(self, seed: int) -> None:
        rng = np.random.default_rng(seed)
        self.base, self.scalars, self.grid = self.cell.ref.make(rng,
                                                                self.size)
        self.live = self.inputs(0)
        self.reused = {o: np.zeros_like(self.base[o]) for o in self.outputs}
        self.sample_rng = np.random.default_rng([seed, 2])
        self.count = 0

    def inputs(self, i: int, out: dict | None = None) -> dict:
        """Launch ``i``'s input buffers, written into ``out`` (into new
        arrays where ``out`` is None)."""
        mix = self.cell.traffic
        off = np.float32(mix["offset_step"] * (i % mix["offset_period"] + 1))
        got = {}
        for nm, a in self.base.items():
            if nm in self.outputs:
                continue
            dst = np.empty_like(a) if out is None else out[nm]
            if a.dtype.kind == "f":
                np.add(a, off, out=dst)
            else:
                np.copyto(dst, a)
            got[nm] = dst
        return got

    def expected(self, i: int) -> dict:
        """The reference's output buffers for launch ``i``."""
        bufs = dict(self.inputs(i), **{o: np.zeros_like(self.base[o])
                                       for o in self.outputs})
        return self.cell.ref.reference(bufs, self.scalars)

    def build(self) -> None:
        """Kernel source to the device program, timed by its parts: the
        front end and pass pipeline with every compile cache off, the
        jax rung's trace, and XLA's build of the executable (from JAX's
        persistent cache after a checkout's first run).  The process's
        first compiles also initialise JAX's tracing and the device's
        compiler client: the first ``compile_warm`` are left out, and
        the mean of the next ``compile_reps`` is kept."""
        from repro.core.backends import jaxgen
        from repro.core.runtime import compile_kernel
        handle = self.cell.kernel()
        p = self.params(self.grid)
        warm, reps = (self.cell.traffic[k]
                      for k in ("compile_warm", "compile_reps"))
        got = {"passes_ms": 0.0, "lower_ms": 0.0, "xla_build_s": 0.0}
        for rep in range(warm + reps):
            # the same garbage-collector state before every compile, so
            # that each collection falls in the same place in every run
            gc.collect()
            t0 = perf_counter()
            with span("compile"):
                ck = compile_kernel(handle, use_cache=False,
                                    use_disk_cache=False)
                t1 = perf_counter()
                ok, why = jaxgen.licence_check(ck.fn, p, self.base,
                                               self.scalars)
            t2 = perf_counter()
            if not ok:
                raise RuntimeError(f"the jax rung refuses {self.cell.name}:"
                                   f" {why}")
            d = jaxgen.describe(ck.fn, p, self.base, self.scalars)
            build = d["compile_s"]["fast"]
            log(f"compile {rep}: {t2 - t0:.4f} s, passes {t1 - t0:.4f} s, "
                f"trace {d['trace_s']:.4f} s, XLA build {build:.4f} s")
            if rep >= warm:
                got["passes_ms"] += (t1 - t0) * 1e3 / reps
                got["lower_ms"] += d["trace_s"] * 1e3 / reps
                got["xla_build_s"] += build / reps
        self.fn = ck.fn
        self.compile = got
        log(f"compile: {got} (mean of {reps} after the first {warm})")

    def launch(self, bufs: dict) -> str:
        self.rt.launch(self.fn, grid=self.grid, block=self.block,
                       scalar_args=self.scalars, buffers=bufs,
                       fuel=self.fuel)
        return self.rt.last_report.executor

    def next_launch(self, keep: bool) -> tuple:
        """The next launch's index and buffers: its inputs written into
        the caller's arrays, and a fresh zeroed output buffer to keep,
        or the reused one."""
        i = self.count
        self.count += 1
        with span("copy_inputs"):
            self.inputs(i, self.live)
        outs = {o: np.zeros_like(self.base[o]) if keep else self.reused[o]
                for o in self.outputs}
        return i, dict(self.live, **outs)

    def warm(self) -> None:
        """Launch until the router's choice for this shape is fixed, and
        twice more on the route the window will take."""
        from repro.core.backends import jaxgen
        p = self.params(self.grid)
        execs, after = [], 0
        while after < 2 and len(execs) < 8:
            before = settled(jaxgen.describe(self.fn, p, self.base,
                                             self.scalars))
            t = perf_counter()
            execs.append((self.launch(self.next_launch(False)[1]),
                          round(perf_counter() - t, 3)))
            after += before
        self.routes = {self.size: route(jaxgen.describe(
            self.fn, p, self.base, self.scalars))}
        log(f"warm-up executors {execs}; router {self.routes}")

    def window(self, seconds: float) -> Outcome:
        out = Outcome()
        every = self.cell.traffic["keep_one_in"]
        with span("window"):
            out.t_start = t = perf_counter()
            while t - out.t_start < seconds or not out.attempted:
                keep = not out.attempted or \
                    self.sample_rng.integers(every) == 0
                i, bufs = self.next_launch(keep)
                out.attempted += 1
                try:
                    with span("launch"):
                        ex = self.launch(bufs)
                except Exception as e:  # counted, and the window goes on
                    out.failed += 1
                    if out.failed <= 3:
                        log(f"launch failed: {type(e).__name__}: {e}")
                else:
                    out.executors.append(ex)
                    if keep:
                        out.kept.append((i, {o: bufs[o]
                                             for o in self.outputs}))
                t = perf_counter()
            out.t_end = t
        return out

    def end_to_end(self, out: Outcome) -> dict:
        return {"launch_ms": out.seconds / max(out.attempted, 1) * 1e3}


DRIVERS = {"solo": Solo}


def compare(driver, out: Outcome) -> dict:
    """The numbers that decide ``correct``: the widest gap, in units of
    the last place, between what a window's launch wrote and the
    reference on that launch's inputs, and the launches that failed or
    never resolved."""
    gap = 0
    for key, got in out.kept:
        want = driver.expected(key)
        for nm, arr in got.items():
            gap = max(gap, ulp_gap(arr, want[nm]))
    return {"max_ulp_gap": gap, "failed_launches": out.failed}
