"""Pipeline parallelism: GPipe-style microbatched execution over the
``pipe`` mesh axis.

The reference has no pipeline parallelism (SURVEY §2.10); like ring
attention and expert parallelism this is first-class TPU-native scope.
Stage s of a homogeneous layer stack lives on device s of the ``pipe``
axis; microbatches flow through the ring with ``lax.ppermute`` over ICI,
so at steady state every stage computes a different microbatch
concurrently — the schedule is the classic GPipe fill/steady/drain
(n_micro + n_stages - 1 steps).

Constraints (the standard homogeneous-pipeline shape):
  * every stage runs the SAME ``stage_fn`` with its own params slice
    (params pytree leaves carry a leading n_stages axis, sharded over
    ``pipe``);
  * activations keep one shape across stages (width-preserving blocks).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(x, params, stage_fn: Callable, n_micro: int,
                    axis_name: str):
    """Per-device body under shard_map.  ``x`` is the full input
    (replicated); ``params`` is this stage's slice (leading axis
    squeezed by the P(axis_name) spec to size 1 -> index [0])."""
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    local_params = jax.tree_util.tree_map(lambda p: p[0], params)

    mb = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
    mb_shape = mb.shape[1:]
    n_steps = n_micro + n_stages - 1
    # receive buffer + output accumulator
    recv0 = jnp.zeros(mb_shape, x.dtype)
    out0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    # ring: stage s sends to s+1 (last stage's send is dropped)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def step(carry, t):
        recv, out = carry
        inp = jnp.where(stage == 0, mb[jnp.minimum(t, n_micro - 1)], recv)
        y = stage_fn(local_params, inp)
        # last stage at step t finished microbatch t - (n_stages - 1)
        idx = t - (n_stages - 1)
        valid = (stage == n_stages - 1) & (idx >= 0)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, out[jnp.maximum(idx, 0)]),
            jnp.maximum(idx, 0), axis=0)
        recv = lax.ppermute(y, axis_name, perm)
        return (recv, out), None

    (_, out), _ = lax.scan(step, (recv0, out0), jnp.arange(n_steps))
    # only the last stage's accumulator is real; broadcast it to every
    # stage so the result is replicated over the pipe axis
    out = out * jnp.where(stage == n_stages - 1, 1.0, 0.0).astype(out.dtype)
    out = lax.psum(out, axis_name)
    return out.reshape(x.shape)


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   axis_name: str = "pipe",
                   n_microbatches: Optional[int] = None):
    """Run ``x`` through ``n_stages`` copies of ``stage_fn`` pipelined
    over the mesh's ``axis_name`` axis.

    ``stage_params``: pytree whose leaves have a leading n_stages axis
    (stage s uses leaf[s]); ``stage_fn(params_slice, x) -> y`` with
    ``y.shape == x.shape``.  Returns the output replicated across the
    pipe axis.  ``n_microbatches`` defaults to the stage count (GPipe's
    minimum for full overlap; more microbatches shrink the bubble).
    """
    n_stages = mesh.shape[axis_name]
    leaves = jax.tree_util.tree_leaves(stage_params)
    if not leaves or leaves[0].shape[0] != n_stages:
        raise ValueError(
            f"stage_params leaves need leading axis {n_stages} "
            f"(the {axis_name!r} mesh axis); got "
            f"{leaves[0].shape if leaves else 'no leaves'}")
    n_micro = n_stages if n_microbatches is None else n_microbatches
    if n_micro < 1:
        raise ValueError(f"n_microbatches must be >= 1, got {n_micro}")
    if x.shape[0] % n_micro:
        raise ValueError(
            f"batch ({x.shape[0]}) is not divisible by n_microbatches "
            f"({n_micro})")
    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params)
    fn = shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          n_micro=n_micro, axis_name=axis_name),
        mesh=mesh, in_specs=(P(), pspec), out_specs=P(),
        check_vma=False)
    return fn(x, stage_params)
