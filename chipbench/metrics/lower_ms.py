"""The jax rung's lowering, ms: the trace of the chunk program to what
XLA builds (``jaxgen.describe`` ``trace_s``), mean of the timed compiles
in set-up."""


def read(run):
    return run.driver.compile.get("lower_ms")
