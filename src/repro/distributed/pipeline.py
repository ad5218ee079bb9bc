"""Pipeline parallelism (GPipe-style) over a `pipe` mesh axis.

Provided as an optional composition for depth-dominated configs (the
production cells use FSDP+TP, which profile better on the 16x16 pod for
the assigned shapes — see EXPERIMENTS.md §Perf notes).  Implemented with
``shard_map`` + ``jax.lax.ppermute``: each stage holds ``n_layers/P``
layers; microbatches stream through stages; bubbles = (P-1)/(M+P-1).

Tested on a host-device mesh in tests/test_distributed.py.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def gpipe_forward(layer_fn: Callable, n_microbatches: int, axis: str = "pipe",
                  axis_size: Optional[int] = None):
    """Build a pipelined forward: params_stage (L/P, ...), x (M, mb, ...).

    layer_fn(stage_params, x) -> x   (one stage's layers applied)
    Returns fn(stage_params, x_microbatches) -> y_microbatches, evaluated
    under shard_map with the `pipe` axis mapped.

    ``axis_size`` must be the static mesh-axis extent: the schedule length
    and the ppermute ring are Python-level constructs (jax.lax.axis_size
    only exists on newer jax, and a traced size could not drive them
    anyway).  make_pipelined_apply fills it in from the mesh.
    """

    def staged(params_stage, xs):
        # shard_map keeps the mapped axis with local size 1: drop it
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        P_ = axis_size if axis_size is not None \
            else jax.lax.psum(1, axis)
        idx = jax.lax.axis_index(axis)
        M = xs.shape[0]
        T = M + P_ - 1          # schedule length

        def step(carry, t):
            buf, ys = carry
            # which microbatch enters stage 0 at time t
            mb_in = jnp.where(t < M, t, 0)
            x_in = jnp.where((idx == 0) & (t < M),
                             xs[mb_in], buf)
            y = layer_fn(params_stage, x_in)
            # pass to the next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(P_ - 1)])
            # last stage writes output for microbatch t - (P-1)
            out_t = t - (P_ - 1)
            ys = jnp.where(
                (idx == P_ - 1) & (out_t >= 0) & (out_t < M),
                ys.at[jnp.clip(out_t, 0, M - 1)].set(y), ys)
            return (nxt, ys), None

        buf0 = jnp.zeros_like(xs[0])
        ys0 = jnp.zeros_like(xs)
        (_, ys), _ = jax.lax.scan(step, (buf0, ys0), jnp.arange(T))
        # broadcast final outputs from the last stage (ppermute cannot
        # fan out one source; masked psum does)
        ys = jnp.where(idx == P_ - 1, ys, jnp.zeros_like(ys))
        return jax.lax.psum(ys, axis)

    return staged


def make_pipelined_apply(mesh: Mesh, layer_fn: Callable,
                         n_microbatches: int, axis: str = "pipe"):
    staged = gpipe_forward(layer_fn, n_microbatches, axis,
                           axis_size=mesh.shape[axis])
    return shard_map(
        staged, mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(None),
        check_vma=False)
