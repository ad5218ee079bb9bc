"""Bytes the jax rung moved between host and device per launch it
served, MiB: (``upload_bytes`` + ``download_bytes``) / ``engaged`` of
``jaxgen.JAX_TELEMETRY`` over the run.  None where the program keeps
no such counters."""


def read(run):
    from repro.core.backends import jaxgen
    t = jaxgen.JAX_TELEMETRY
    if not t.get("engaged") or "upload_bytes" not in t:
        return None
    return (t["upload_bytes"] + t["download_bytes"]) / t["engaged"] / 2**20
