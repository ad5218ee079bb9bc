"""Inputs, plain numpy reference and work counts of SpMV over the
adjacency matrix of a Graph500 Kronecker graph.

The graph is the Graph 500 specification's Kronecker generator
(initiator A = 0.57, B = 0.19, C = 0.19, D = 0.05, ``EDGEFACTOR``
edges per vertex, vertices relabelled by a random permutation), built
into CSR as the GAP Benchmark Suite builds its ``kron`` graph
(Beamer, Asanovic, Patterson, arXiv:1508.03619): symmetrised, self-loops
and duplicate edges dropped, each row's columns sorted.

Imports nothing of the program under test."""
import numpy as np

from chipbench.configs.lowp import to_bf16

A, B, C = 0.57, 0.19, 0.19
EDGEFACTOR = 16


def kronecker_edges(rng, scale: int):
    """The generator's ``EDGEFACTOR * 2**scale`` directed edges
    ``(i, j)``, int64, vertices relabelled."""
    n = 1 << scale
    m = EDGEFACTOR * n
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int64) << bit
        j |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[i], perm[j]


def csr(n: int, i, j):
    """Symmetrised CSR of the edges: self-loops and duplicates dropped,
    columns sorted within each row; int32 ``row_ptr`` and ``cols``."""
    src = np.concatenate([i, j])
    dst = np.concatenate([j, i])
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=row_ptr[1:])
    return row_ptr.astype(np.int32), (key % n).astype(np.int32)


def capacity(scale: int) -> int:
    """Entries of the ``cols`` and ``vals`` buffers: the most nonzeros
    the symmetrised graph can have, every generated edge kept in both
    directions."""
    return 2 * EDGEFACTOR << scale


def make(rng, scale):
    """Seeded inputs at 2**``scale`` rows: buffers, scalars and the
    number of 32-wide workgroups (one work item per row).  ``vals`` and
    ``x`` are standard-normal float32.  ``cols`` and ``vals`` hold the
    ``row_ptr[n]`` nonzeros in buffers of ``capacity(scale)`` entries,
    zero past the last nonzero and never read there, so that every seed
    of a scale gives buffers of the same shapes."""
    n = 1 << scale
    row_ptr, edges = csr(n, *kronecker_edges(rng, scale))
    cols = np.zeros(capacity(scale), np.int32)
    vals = np.zeros(capacity(scale), np.float32)
    cols[:len(edges)] = edges
    vals[:len(edges)] = rng.standard_normal(len(edges), dtype=np.float32)
    x = rng.standard_normal(n, dtype=np.float32)
    return {"row_ptr": row_ptr, "cols": cols, "vals": vals, "x": x,
            "y": np.zeros(n, np.float32)}, {"n": n}, -(-n // 32)


def reference(bufs, scalars, lowp=False):
    """The expected output buffers.  Each row sums its products in
    column order, multiply and add rounded separately in float32, as the
    kernel states; rows are vectorised by trip index (trip ``t`` adds
    the ``t``-th product of every row longer than ``t``).  ``lowp``
    rounds ``vals`` and ``x`` to bfloat16 first: the control that a
    correct run must not pass for."""
    n = scalars["n"]
    row_ptr = bufs["row_ptr"][:n + 1].astype(np.int64)
    cols, vals, x = bufs["cols"], bufs["vals"], bufs["x"]
    if lowp:
        vals, x = to_bf16(vals), to_bf16(x)
    deg = np.diff(row_ptr)
    order = np.argsort(-deg, kind="stable")    # longest rows first
    start = row_ptr[order]
    longer = n - np.searchsorted(np.sort(deg), np.arange(deg.max(initial=0)),
                                 side="right")  # rows longer than t
    acc = np.zeros(n, np.float32)
    for t, k in enumerate(longer):
        e = start[:k] + t
        acc[:k] = acc[:k] + vals[e] * x[cols[e]]
    y = np.zeros_like(bufs["y"])
    y[order] = acc
    return {"y": y}


def work_of(bufs):
    """(floating-point operations, bytes moved) of one launch: a
    multiply and an add per nonzero; ``row_ptr``, ``cols``, ``vals``,
    ``x`` and ``y`` each read or written once."""
    n, nnz = len(bufs["x"]), int(bufs["row_ptr"][-1])
    return 2 * nnz, 4 * (n + 1) + 8 * nnz + 8 * n
