"""Share of the rows a device loop's body was computed for that held a
live lane: ``loop_rows_live / loop_rows_paid`` of
``jaxgen.JAX_TELEMETRY`` over the run.  None where the program keeps no
such counters."""


def read(run):
    from repro.core.backends import jaxgen
    t = jaxgen.JAX_TELEMETRY
    if not t.get("loop_rows_paid"):
        return None
    return t["loop_rows_live"] / t["loop_rows_paid"]
