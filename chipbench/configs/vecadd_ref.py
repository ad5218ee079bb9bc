"""Inputs, plain numpy reference and work counts of vecadd.

Imports nothing of the program under test."""
import numpy as np

from chipbench.configs.lowp import to_bf16


def make(rng, size):
    """Seeded inputs at ``size`` elements: buffers, scalars and the
    number of 32-wide workgroups that cover them."""
    g = -(-size // 32)
    x = rng.standard_normal(g * 32, dtype=np.float32)
    y = rng.standard_normal(g * 32, dtype=np.float32)
    return {"x": x, "y": y, "z": np.zeros(g * 32, np.float32)}, \
        {"n": size}, g


def reference(bufs, scalars, lowp=False):
    """The expected output buffers.  ``lowp`` rounds the inputs to
    bfloat16 first: the control that a correct run must not pass for."""
    n = scalars["n"]
    x, y = bufs["x"][:n], bufs["y"][:n]
    if lowp:
        x, y = to_bf16(x), to_bf16(y)
    z = np.zeros_like(bufs["z"])
    z[:n] = x + y
    return {"z": z}


def work(scalars):
    """(floating-point operations, bytes moved) of one launch."""
    n = scalars["n"]
    return n, 12 * n
