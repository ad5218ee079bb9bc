"""Inputs, plain numpy reference and work counts of sgemm.

Imports nothing of the program under test."""
import numpy as np

from chipbench.configs.lowp import to_bf16


def make(rng, size):
    """Seeded inputs for m = n = k = ``size``: buffers, scalars and the
    number of 32-wide workgroups (one work item per output)."""
    m = n = k = size
    g = -(-(m * n) // 32)
    a = rng.standard_normal(m * k, dtype=np.float32)
    b = rng.standard_normal(k * n, dtype=np.float32)
    return {"a": a, "b": b, "c": np.zeros(g * 32, np.float32)}, \
        {"m": m, "n": n, "k": k}, g


def reference(bufs, scalars, lowp=False):
    """The expected output buffers.  Each output sums its products in
    order of k, multiply and add rounded separately in float32, as the
    kernel states.  ``lowp`` rounds the inputs to bfloat16 first (the
    default precision of a TPU matrix unit): the control that a correct
    run must not pass for."""
    m, n, k = scalars["m"], scalars["n"], scalars["k"]
    a = bufs["a"][:m * k].reshape(m, k)
    b = bufs["b"][:k * n].reshape(k, n)
    if lowp:
        a, b = to_bf16(a), to_bf16(b)
    acc = np.zeros((m, n), np.float32)
    for i in range(k):
        acc = acc + a[:, i:i + 1] * b[i:i + 1, :]
    c = np.zeros_like(bufs["c"])
    c[:m * n] = acc.reshape(-1)
    return {"c": c}


def work(scalars):
    """(floating-point operations, bytes moved) of one launch."""
    m, n, k = scalars["m"], scalars["n"], scalars["k"]
    return 2 * m * n * k, 4 * (m * k + k * n + m * n)
