"""Front end and 9-pass pipeline, ms: a host span around the
uncached ``compile_kernel`` of the cell's kernel in set-up."""


def read(run):
    return run.driver.compile.get("passes_ms")
