"""Expert parallelism: switch-routed mixture-of-experts over the
``expert`` mesh axis.

The reference has no MoE (SURVEY §2.10 — data parallelism only); like
ring attention this is first-class TPU-native scope: experts live
sharded across devices, tokens travel to their expert via
``lax.all_to_all`` over ICI, and the whole dispatch→compute→combine is
one compiled SPMD program.

Design (Switch-Transformer-style top-1 routing with capacity):
  * gate: logits = x @ Wg over ALL experts; each token picks argmax;
  * capacity C bounds tokens per expert (static shapes under jit);
    tokens beyond capacity are dropped — their output is 0, which a
    residual connection turns into identity pass-through;
  * dispatch/combine are einsums against a (tokens, experts, capacity)
    one-hot — the standard dense-dispatch formulation;
  * expert-parallel path: dispatched blocks all_to_all from
    (token-shard, all experts) layout to (expert-shard, all tokens)
    layout, local experts apply, all_to_all back, combine.

``switch_moe`` is the single-device reference; ``moe_sharded`` runs the
same math with experts sharded over the mesh's ``expert`` axis.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


class MoEParams(NamedTuple):
    """Weights of a switch-MoE FFN block.

    gate:  (d_model, n_experts)
    w1:    (n_experts, d_model, d_hidden)
    b1:    (n_experts, d_hidden)
    w2:    (n_experts, d_hidden, d_model)
    b2:    (n_experts, d_model)
    """

    gate: jnp.ndarray
    w1: jnp.ndarray
    b1: jnp.ndarray
    w2: jnp.ndarray
    b2: jnp.ndarray


def init_moe_params(rng, d_model: int, d_hidden: int, n_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    kg, k1, k2 = jax.random.split(rng, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)
    return MoEParams(
        gate=jax.random.normal(kg, (d_model, n_experts), dtype) * s1,
        w1=jax.random.normal(k1, (n_experts, d_model, d_hidden),
                             dtype) * s1,
        b1=jnp.zeros((n_experts, d_hidden), dtype),
        w2=jax.random.normal(k2, (n_experts, d_hidden, d_model),
                             dtype) * s2,
        b2=jnp.zeros((n_experts, d_model), dtype))


def expert_capacity(n_tokens: int, n_experts: int,
                    capacity_factor: float) -> int:
    return max(1, int(math.ceil(n_tokens / n_experts * capacity_factor)))


def _route(x, gate_w, n_experts: int, capacity: int):
    """Top-1 routing -> (dispatch one-hot (T, E, C), combine weights
    (T, E, C), per-shard expert-load stats for the aux loss).

    Queue positions are computed with an int32 cumsum regardless of
    ``x.dtype`` — a bf16 cumsum is only exact to 256, after which
    colliding capacity slots silently sum multiple tokens into one
    expert row.  Only the final dispatch/combine tensors take x's dtype.
    """
    logits = (x @ gate_w).astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)        # (T,)
    int_1h = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)
    # position of each token within its expert's queue (exact int math)
    pos_in_expert = (jnp.cumsum(int_1h, axis=0) - 1) * int_1h
    keep = (pos_in_expert < capacity) * int_1h     # (T, E) 0/1
    pos = jnp.sum(pos_in_expert * keep, axis=-1)   # (T,)
    pos_1h = jax.nn.one_hot(pos, capacity, dtype=jnp.int32)
    dispatch = (keep[:, :, None] * pos_1h[:, None, :]).astype(x.dtype)
    gate_val = jnp.sum(probs * int_1h, axis=-1)    # (T,) f32
    combine = dispatch * gate_val.astype(x.dtype)[:, None, None]
    # Switch load-balancing stats: fraction routed / mean prob per expert
    f = jnp.mean(int_1h.astype(jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return dispatch, combine, (f, p)


def _apply_experts(blocks, w1, b1, w2, b2):
    """blocks (E, C, d) through each expert's 2-layer relu FFN."""
    h = jnp.einsum("ecd,edh->ech", blocks, w1) + b1[:, None, :]
    h = jax.nn.relu(h)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


def switch_moe(x, params: MoEParams, capacity_factor: float = 1.25,
               capacity: Optional[int] = None):
    """Single-device reference: x (tokens, d_model) -> (out, aux_loss).

    Dropped (over-capacity) tokens produce 0 — add the residual outside.
    """
    t, d = x.shape
    n_experts = params.gate.shape[-1]
    c = capacity if capacity is not None else expert_capacity(
        t, n_experts, capacity_factor)
    dispatch, combine, (f, p) = _route(x, params.gate, n_experts, c)
    aux = n_experts * jnp.sum(f * p)
    blocks = jnp.einsum("tec,td->ecd", dispatch, x)       # (E, C, d)
    outs = _apply_experts(blocks, params.w1, params.b1, params.w2,
                          params.b2)
    return jnp.einsum("tec,ecd->td", combine, outs), aux


def _moe_local(x, params: MoEParams, n_experts: int, capacity: int,
               axis_name: str):
    """Per-device body under shard_map: x is this device's token shard,
    expert weights are this device's expert shard."""
    n = lax.axis_size(axis_name)
    e_local = n_experts // n
    # routing needs ALL experts' gate columns — gate is replicated
    dispatch, combine, (f, p) = _route(x, params.gate, n_experts,
                                       capacity)
    # aux loss over GLOBAL routing stats (pmean f and p BEFORE the
    # product) so sharded and single-device training see the same
    # gate gradients even when routing is uneven across token shards
    f = lax.pmean(f, axis_name)
    p = lax.pmean(p, axis_name)
    aux = n_experts * jnp.sum(f * p)
    blocks = jnp.einsum("tec,td->ecd", dispatch, x)       # (E, C, d)
    # (E, C, d) -> (n, E_local, C, d): send each expert block to its
    # owner; receive every device's blocks for MY experts
    d = blocks.shape[-1]
    blocks = blocks.reshape(n, e_local, capacity, d)
    blocks = lax.all_to_all(blocks, axis_name, split_axis=0,
                            concat_axis=0, tiled=False)
    # now (n, E_local, C, d): axis 0 = SOURCE device.  Fold the source
    # axis into the expert queue: (E_local, n*C, d)
    blocks = jnp.transpose(blocks, (1, 0, 2, 3)).reshape(
        e_local, n * capacity, d)
    outs = _apply_experts(blocks, params.w1, params.b1, params.w2,
                          params.b2)
    # unfold and ship each source's results home
    outs = jnp.transpose(outs.reshape(e_local, n, capacity, d),
                         (1, 0, 2, 3))
    outs = lax.all_to_all(outs, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    # axis 0 = expert-OWNER device; global expert id = owner*E_local + e
    outs = outs.reshape(n_experts, capacity, d)
    y = jnp.einsum("tec,ecd->td", combine, outs)
    return y, aux


def moe_sharded(x, params: MoEParams, mesh: Mesh,
                axis_name: str = "expert",
                capacity_factor: float = 1.25):
    """Expert-parallel switch MoE: tokens sharded over ``axis_name``,
    experts sharded over the same axis (w1/b1/w2/b2 leading dim), gate
    replicated.  x: (tokens, d_model) global.

    Each device routes its token shard against ALL experts, all_to_all
    ships dispatched blocks to the expert owners over ICI, local experts
    run, and a second all_to_all brings results home.
    """
    n = mesh.shape[axis_name]
    t = x.shape[0]
    n_experts = params.gate.shape[-1]
    if n_experts % n:
        raise ValueError(
            f"n_experts ({n_experts}) is not divisible by the "
            f"{axis_name!r} axis size ({n})")
    if t % n:
        raise ValueError(
            f"tokens ({t}) are not divisible by the {axis_name!r} "
            f"axis size ({n})")
    # capacity per LOCAL token shard (same queue depth every device)
    capacity = expert_capacity(t // n, n_experts, capacity_factor)
    espec = P(axis_name)
    fn = shard_map(
        functools.partial(_moe_local, n_experts=n_experts,
                          capacity=capacity, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), MoEParams(P(None, None), espec, espec,
                                          espec, espec)),
        out_specs=(P(axis_name), P()),
        check_vma=False)
    return fn(x, params)
