"""Named host spans of the launch path, for the JAX profiler.

Where JAX is loaded, ``span(name)`` is a ``jax.profiler.TraceAnnotation``:
while a profiler trace is being recorded it is kept in memory and written
into the same ``.xplane.pb`` as the device's operations, on the same
clock, so each stretch of device idle time can be put down to the host
step that was running.  Where JAX is not loaded (the numpy chain) it is
a shared no-op context, and this module imports no JAX.

Spans are named ``volt.<layer>.<step>`` and nest by call on the caller's
thread (docs/performance.md, "Tracing a launch").  Keep them off
per-chunk and per-workgroup loops: one span costs about a microsecond.
"""
from __future__ import annotations

import sys
from contextlib import nullcontext

_NOOP = nullcontext()


def span(name: str):
    """A context manager that records ``name`` as a host span."""
    jax = sys.modules.get("jax")
    return _NOOP if jax is None else jax.profiler.TraceAnnotation(name)
