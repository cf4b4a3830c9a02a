"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

The reference has no sequence parallelism (SURVEY §2.10) — this is the
first-class long-context component of the TPU build.  Design: shard the
sequence axis of q/k/v across devices; each device computes online-softmax
attention of its local q block against the k/v shard it currently holds,
then rotates k/v around the ring with ``lax.ppermute`` over ICI.  After
n_devices steps every q block has seen every k/v block, with peak memory
O(seq/n) per device and communication overlapping compute (the
blockwise-parallel-transformers / ring-attention formulation).

Causality is handled with global positions: shard s of the sequence owns
positions [s·L, (s+1)·L); masks compare global q/k positions, so rotated
blocks that are entirely in the future contribute nothing.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _local_attention_accumulate(q, k_blk, v_blk, q_offset, k_offset,
                                causal, scale, carry, kv_lengths=None):
    """One ring step: accumulate online-softmax stats for local q against
    one rotated k/v shard.  ``kv_lengths``: optional (batch,) GLOBAL
    valid key counts — global key positions >= kv_lengths[b] are masked
    (right-padded batches)."""
    m_prev, l_prev, o_prev = carry
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k_blk)
    sq, sk = q.shape[1], k_blk.shape[1]
    k_pos = k_offset + jnp.arange(sk)
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    if kv_lengths is not None:
        kmask = k_pos[None, :] < kv_lengths[:, None]  # (b, sk)
        scores = jnp.where(kmask[:, None, None, :], scores, -1e30)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    o_new = (o_prev * corr[..., None]
             + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk))
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                   scale: Optional[float] = None, kv_lengths=None,
                   block_k: int = 1024):
    """Call INSIDE shard_map with q/k/v sharded on their seq axis.

    Shapes (local): (batch, seq_local, heads, head_dim).
    ``kv_lengths``: optional (batch,) GLOBAL valid key counts,
    replicated across the ring (each sequence must have >= 1 valid
    token; clamp before calling — the sharded wrapper does).

    ``block_k`` sub-blocks each held K/V shard inside a ring step, so
    per-device peak memory is O(seq_local · block_k) score tiles rather
    than O(seq_local · shard) — without it the score matrix per step is
    (seq/n)², which quietly reintroduces quadratic per-device memory as
    sequences grow at fixed ring size (measured: ring_report r5)."""
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    q_offset = my_idx * sq
    shard = k.shape[1]
    from ..ops.attention import _largest_divisor
    block_k = _largest_divisor(shard, min(block_k, shard))
    if block_k < 8:
        # prime-ish shard: a tiny divisor would degrade each ring step
        # to a per-element scan — keep the whole-shard matmul instead
        # (same guard as the flash path's bwd_bk floor)
        block_k = shard
    n_sub = shard // block_k

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        k_cur, v_cur, stats = carry
        # the shard currently held started at ((my_idx - i) mod n)·L
        src = (my_idx - i) % n
        base = src * shard

        def sub(j, st):
            k_blk = lax.dynamic_slice_in_dim(k_cur, j * block_k,
                                             block_k, axis=1)
            v_blk = lax.dynamic_slice_in_dim(v_cur, j * block_k,
                                             block_k, axis=1)
            return _local_attention_accumulate(
                q, k_blk, v_blk, q_offset, base + j * block_k, causal,
                scale, st, kv_lengths=kv_lengths)

        stats = lax.fori_loop(0, n_sub, sub, stats)
        # rotate for the next step (last rotation is redundant but keeps
        # the loop uniform; XLA overlaps it with the epilogue)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, stats

    m0 = jnp.full((b, h, sq), -1e30)
    l0 = jnp.zeros((b, h, sq))
    o0 = jnp.zeros((b, h, sq, d))
    _, _, (m, l, o) = lax.fori_loop(0, n, step, (k, v, (m0, l0, o0)))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis_name: str = "seq",
                           causal: bool = False, kv_lengths=None):
    """Convenience wrapper: shard (b, s, h, d) arrays on the seq axis and
    run ring attention under shard_map.  ``kv_lengths``: optional
    (batch,) GLOBAL valid key counts (replicated over the ring)."""
    spec = P(None, axis_name, None, None)
    if kv_lengths is None:
        fn = shard_map(
            functools.partial(ring_attention, axis_name=axis_name,
                              causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(q, k, v)
    from ..ops.attention import _clamp_lengths
    lens = _clamp_lengths(kv_lengths, k.shape[1])
    fn = shard_map(
        lambda q_, k_, v_, l_: ring_attention(
            q_, k_, v_, axis_name=axis_name, causal=causal,
            kv_lengths=l_),
        mesh=mesh, in_specs=(spec, spec, spec, P(None)),
        out_specs=spec, check_vma=False)
    return fn(q, k, v, lens)
