"""The program's own host spans (``volt.*``, ``src/repro/core/spans.py``)
in a traced window, and the device's idle time filed under them.

    python3 chipbench/spans.py --workload vecadd.solo --seed 7 --seconds 10 \\
        [--size 262144] [--keep vecadd.xplane.pb]

Sets the cell up as a run does (at ``--size`` elements where given), runs
one window untraced and one under the profiler, each of ``--seconds``,
and prints a JSON line: ms per launch of both windows, the benchmark's
own reduction of the trace (``tracing.reduce``), the program's spans
summed inside the window, the idle time filed under the innermost span
of either prefix, and the per-launch host times the spans give.
``--keep`` copies the trace there.  The benchmark's own runs never run
this: ``tracing.reduce`` reads only the benchmark's ``chipbench.``
spans.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import tracing  # noqa: E402

VOLT = "volt."
LAUNCH = VOLT + "launch"


def events(path: str) -> list:
    """The program's spans in a trace: ``[(name, start_ns, end_ns)]``
    from every host thread."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events
            if e.name.startswith(VOLT)]


def read(path: str) -> dict | None:
    """``spans``: for each ``volt.`` name, ``{"s": seconds inside the
    window (clipped), "n": spans that overlap it}``; ``idle_gaps``: the
    device's idle time in the window summed by the innermost span of
    either prefix around the middle of each gap (``chipbench.`` names
    stripped, ``volt.`` names whole, ``outside_spans`` where none is),
    every entry, largest first.  None where no accelerator plane holds
    an operation, as for ``tracing.reduce``."""
    ps = tracing.planes(path)
    bench = [ev for pname, lines in ps if pname.startswith("/host")
             for _ln, evs in lines for ev in evs]
    devices = [dict(lines) for pname, lines in ps
               if tracing._DEVICE.match(pname)
               and any(evs for _ln, evs in lines)]
    win = [s for s in bench if s[0] == tracing.WINDOW]
    if not devices or not win:
        return None
    lo, hi = win[0][1], win[0][2]
    volt = events(path)
    spans: dict = {}
    for name, s, e in volt:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            got = spans.setdefault(name, {"s": 0.0, "n": 0})
            got["s"] += d * 1e-9
            got["n"] += 1
    inner = sorted([(n[len(tracing.SPAN):], s, e) for n, s, e in bench
                    if n != tracing.WINDOW] + volt,
                   key=lambda sp: sp[2] - sp[1])
    idle: dict = defaultdict(float)
    for lines in devices:
        ops = lines.get(tracing.OPS) or lines.get(tracing.MODULES)
        merged = tracing.union(((s, e) for _, s, e in ops), lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            what = next((sp[0] for sp in inner if sp[1] <= mid < sp[2]),
                        "outside_spans")
            idle[what] += (e - s) * 1e-9 / len(devices)
    return {"spans": spans,
            "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                                key=lambda kv: -kv[1])}


def per_launch(spans: dict) -> dict:
    """Host ms per launch from the spans of a window: the chain's own
    time (``volt.launch`` less every ``volt.jax.`` span, the snapshot
    included), the upload, the dispatch, and the download with the copy
    into the caller's buffers.  Empty without ``volt.launch``."""
    launches = spans.get(LAUNCH, {}).get("n")
    if not launches:
        return {}

    def ms(*names):
        return sum(spans.get(n, {"s": 0.0})["s"] for n in names) \
            * 1e3 / launches

    jax_steps = [n for n in spans if n.startswith(VOLT + "jax.")]
    return {"runtime_host_ms": ms(LAUNCH) - ms(*jax_steps),
            "upload_ms": ms("volt.jax.upload"),
            "dispatch_ms": ms("volt.jax.dispatch"),
            "download_ms": ms("volt.jax.download", "volt.jax.apply")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--size", type=int, default=None,
                    help="elements, in place of the configuration's size")
    ap.add_argument("--keep", default=None)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the rehearsal sizes")
    args = ap.parse_args(argv)
    from chipbench import bench, drivers, spec
    bench.prepare_env()
    cell = spec.load_cell(args.workload)
    if args.size is not None:
        cell.config["size"] = cell.config["rehearsal"]["size"] = args.size
    bench.devices(cell.chips, args.rehearse)
    import jax
    from repro.core.backends import jaxgen
    from repro.core.runtime import Runtime
    driver = drivers.DRIVERS[cell.traffic["driver"]](
        cell, Runtime(jax=True), args.rehearse)
    driver.make_inputs(args.seed)
    driver.build()
    driver.warm()
    untraced = driver.window(args.seconds)
    tdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    traced = driver.window(args.seconds)
    jax.profiler.stop_trace()
    path = tracing.find_trace(tdir)
    if args.keep:
        Path(args.keep).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(path, args.keep)
    got = read(path)
    t = jaxgen.JAX_TELEMETRY
    dev = jax.devices()[0]
    print(json.dumps({
        "workload": args.workload, "size": driver.size,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "untraced": {"launches": untraced.attempted,
                     "ms_per_launch": untraced.seconds * 1e3
                     / untraced.attempted},
        "traced": {"launches": traced.attempted,
                   "ms_per_launch": traced.seconds * 1e3
                   / traced.attempted},
        "reduce": tracing.reduce(path),
        **(got or {}),
        "per_launch": per_launch(got["spans"]) if got else {},
        "transfer_mib": (t["upload_bytes"] + t["download_bytes"])
        / t["engaged"] / 2**20 if t["engaged"] else None,
        "failed": untraced.failed + traced.failed}), flush=True)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
