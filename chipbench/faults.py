"""The control, and faults planted under the timed path.

``planted(kind, cell)`` replaces, for as long as it is entered, the call
every window's launch goes through, ``interp.launch`` under
``Runtime.launch``:

  control    the config's reference computed from bfloat16-rounded
             inputs, in the program's place
  unchanged  a launch that returns and writes nothing
  half       a launch over the first half of its workgroups only
  altered    a launch whose first output has one element moved by one
             unit in the last place where it is produced
  raises     a launch that raises, so its answer never comes
  stale      a launch that reads the inputs an earlier launch found in
             the same host arrays, as a cache of uploads keyed by the
             array and blind to the caller's writes would

A run under any of them must come out not ``correct``.
"""
from __future__ import annotations

import dataclasses
from contextlib import contextmanager

import numpy as np

KINDS = ("control", "unchanged", "half", "altered", "raises", "stale")


@contextmanager
def planted(kind: str, cell):
    from repro.core import runtime
    from repro.core.faults import KernelFault
    from repro.core.interp import ExecStats
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; known: {KINDS}")
    real_launch = runtime.interp_launch
    outputs = cell.config["outputs"]
    uploaded: dict = {}

    def launch(fn, bufs, params, scalar_args=None, **kw):
        if kind == "control":
            want = cell.ref.reference(bufs, scalar_args or {}, lowp=True)
            for nm, arr in want.items():
                np.copyto(bufs[nm], arr)
            return ExecStats()
        if kind == "unchanged":
            return ExecStats()
        if kind == "raises":
            raise KernelFault("planted: the launch raises")
        if kind == "half":
            params = dataclasses.replace(params, grid=max(1, params.grid // 2))
        if kind == "stale":
            bufs = {nm: a if nm in outputs else
                    uploaded.setdefault((nm, id(a)), a.copy())
                    for nm, a in bufs.items()}
        st = real_launch(fn, bufs, params, scalar_args=scalar_args, **kw)
        if kind == "altered":
            z = bufs[outputs[0]]
            z[len(z) // 2] = np.nextafter(z[len(z) // 2], np.float32(np.inf))
        return st

    runtime.interp_launch = launch
    try:
        yield
    finally:
        runtime.interp_launch = real_launch
