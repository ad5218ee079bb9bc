"""From a JAX profiler trace to the device's busy time, the device
programs run and the breakdown of a traced window.

The benchmark puts ``jax.profiler.TraceAnnotation`` spans named
``chipbench.<what>`` around its own calls into the program; the one
named ``chipbench.window`` bounds the measured window.  Device planes
are those of the accelerator (``/device:TPU:<n>``); on each, the line
``XLA Ops`` holds the operations that ran and ``XLA Modules`` the
executions of whole device programs.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SPAN = "chipbench."
WINDOW = SPAN + "window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS, MODULES = "XLA Ops", "XLA Modules"


def find_trace(trace_dir: str) -> str | None:
    """The ``.xplane.pb`` file a trace into ``trace_dir`` wrote."""
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def planes(path: str) -> list:
    """``[(plane, [(line, [(name, start_ns, end_ns), ...]), ...])]`` of a
    trace: on device planes the operation and program lines, on host
    planes the benchmark's own spans.  Several host lines can share a
    name (threads are named after the process)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for p in pd.planes:
        device = bool(_DEVICE.match(p.name))
        lines = []
        for ln in p.lines:
            if device and ln.name in (OPS, MODULES):
                lines.append((ln.name, [(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns)
                                        for e in ln.events]))
            elif p.name.startswith("/host"):
                lines.append((ln.name, [(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns)
                                        for e in ln.events
                                        if e.name.startswith(SPAN)]))
        out.append((p.name, lines))
    return out


def reduce(path: str) -> dict | None:
    """The traced window's device time, or None where no accelerator
    plane holds an operation.

    ``busy_s``: the union of operation intervals inside the window,
    averaged over the devices that ran any; ``window_s``: the window's
    length; ``programs``: executions of device programs that started in
    the window, averaged the same way; ``device_ops``: the ten
    operations with the most device time, ``[name, seconds]``, each
    name the first 160 characters of the operation's HLO text;
    ``idle_gaps``: the device's idle time inside the window, summed by
    what the host was doing (the innermost ``chipbench.`` span around
    the middle of each gap, ``outside_spans`` where none is), the ten
    largest, ``[activity, seconds]``."""
    ps = planes(path)
    spans = [ev for pname, lines in ps if pname.startswith("/host")
             for _ln, evs in lines for ev in evs]
    devices = [(pname, dict(lines)) for pname, lines in ps
               if _DEVICE.match(pname) and any(evs for _ln, evs in lines)]
    if not devices:
        return None
    win = [s for s in spans if s[0] == WINDOW]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        evs = [e for _, lines in devices for e in lines.get(MODULES, [])]
        lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    inner = sorted((s for s in spans if s[0] != WINDOW),
                   key=lambda s: s[2] - s[1])
    busy, programs = [], []
    op_time: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    for _pname, lines in devices:
        ops = lines.get(OPS) or lines.get(MODULES)
        merged = union(((s, e) for _, s, e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        programs.append(sum(1 for _, s, _e in lines.get(MODULES, [])
                            if lo <= s < hi))
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name[:160]] += d / len(devices)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            what = next((sp[0][len(SPAN):] for sp in inner
                         if sp[1] <= mid < sp[2]), "outside_spans")
            idle[what] += (e - s) / len(devices)

    def top(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "programs": sum(programs) / len(programs),
            "device_ops": top(op_time),
            "idle_gaps": top(idle)}
