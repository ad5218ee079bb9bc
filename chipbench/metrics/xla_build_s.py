"""XLA's build of the jax rung's chunk program, s: what
``jaxgen.describe`` reads as the build (from JAX's persistent cache
after a checkout's first run), mean of the timed compiles in set-up."""


def read(run):
    return run.driver.compile.get("xla_build_s")
