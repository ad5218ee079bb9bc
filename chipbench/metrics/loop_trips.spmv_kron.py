"""Device loop iterations per launch the jax rung served, every stage
and chunk: ``loop_trips / engaged`` of ``jaxgen.JAX_TELEMETRY`` over
the run.  None where the program keeps no such counters."""


def read(run):
    from repro.core.backends import jaxgen
    t = jaxgen.JAX_TELEMETRY
    if not t.get("engaged") or "loop_trips" not in t:
        return None
    return t["loop_trips"] / t["engaged"]
