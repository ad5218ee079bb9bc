"""Share of the global-buffer accesses the jax rung's device program
executed that were served by a row window, a broadcast or an update
slice, not an element gather or scatter: ``mem_window / (mem_window +
mem_gather)`` of ``jaxgen.JAX_TELEMETRY`` over the run.  None where the
program keeps no such counters."""


def read(run):
    from repro.core.backends import jaxgen
    t = jaxgen.JAX_TELEMETRY
    n = t.get("mem_window", 0) + t.get("mem_gather", 0)
    if not n:
        return None
    return t["mem_window"] / n
