"""Attention ops: naive, blockwise (online-softmax), and a pallas TPU
flash-attention kernel, plus a MultiHeadAttention layer.

The reference has NO attention anywhere (SURVEY §5: "attention does not
exist in the layer set") — this is the TPU-era extension the task brief
makes first-class (long-context support).  Three implementations share one
semantics:

* ``naive_attention`` — O(S²) materialized scores; the test oracle.
* ``blockwise_attention`` — lax.scan over key blocks with online softmax
  (running max/denominator), O(S) memory; works on any backend and is the
  building block ring attention reuses per-shard.
* ``flash_attention`` — pallas TPU kernel: grid over (batch·heads,
  q-blocks), VMEM-resident q/k/v blocks, online softmax in f32 accumulators
  feeding the MXU per block pair.

All take (batch, seq, heads, head_dim) and return the same shape.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..observability import profile as _profile
from ..observability.log import get_logger as _get_logger
from ..parallel import mesh as _mesh_lib

NEG_INF = -1e30

_slog = _get_logger("zoo.ops.attention")


def _clamp_lengths(kv_lengths, sk):
    """Normalize per-batch valid key lengths to f32 in [1, sk].

    The floor of 1 keeps fully-masked rows out of every implementation
    (softmax over an all-masked row is 0/0; the flash backward's
    exp(s − lse) replay would cancel the NEG_INF sentinel into phantom
    probabilities) — an "empty" sequence attends to position 0 and its
    output must be masked downstream, which padded batches do anyway."""
    lens = jnp.asarray(kv_lengths)
    if lens.ndim != 1:
        raise ValueError(
            f"kv_lengths must be (batch,), got shape {lens.shape}")
    return jnp.clip(lens.astype(jnp.float32), 1, sk)


def naive_attention(q, k, v, causal: bool = False, scale: float = None,
                    kv_lengths=None):
    """Materialized-scores attention (oracle).

    ``kv_lengths``: optional (batch,) valid key counts — keys at
    positions >= kv_lengths[b] are masked out (right-padded variable-
    length batches; the reference pads text to a fixed sequenceLength,
    TextClassifier.scala:34).  Padded QUERY rows still produce (garbage)
    outputs — mask them downstream, as sequence losses do."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask, scores, NEG_INF)
    if kv_lengths is not None:
        lens = _clamp_lengths(kv_lengths, sk)
        kmask = (jnp.arange(sk)[None, :] < lens[:, None])  # (b, sk)
        scores = jnp.where(kmask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_attention(q, k, v, causal: bool = False,
                        block_k: int = 512, scale: float = None,
                        kv_lengths=None):
    """Online-softmax attention scanning key blocks: O(seq) memory.

    ``kv_lengths``: optional (batch,) valid key counts (see
    ``naive_attention``)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, sk)
    if sk % block_k != 0:
        raise ValueError(
            f"block_k ({block_k}) must divide the key length ({sk})")
    lens = (None if kv_lengths is None
            else _clamp_lengths(kv_lengths, sk))
    n_blocks = sk // block_k
    kb = k.reshape(b, n_blocks, block_k, h, d)
    vb = v.reshape(b, n_blocks, block_k, h, d)
    q_scaled = q * scale
    q_pos = jnp.arange(sq)

    def body(carry, blk):
        m_prev, l_prev, o_prev = carry
        k_blk, v_blk, blk_idx = blk
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_scaled, k_blk)
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        if causal:
            mask = q_pos[:, None] + (sk - sq) >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        if lens is not None:
            kmask = k_pos[None, :] < lens[:, None]  # (b, block_k)
            scores = jnp.where(kmask[:, None, None, :], scores, NEG_INF)
        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(scores - m_new[..., None])
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1)
        o_new = (o_prev * correction[..., None]
                 + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, h, sq), NEG_INF)
    l0 = jnp.zeros((b, h, sq))
    o0 = jnp.zeros((b, h, sq, d))
    (m, l, o), _ = lax.scan(
        body, (m0, l0, o0),
        (jnp.swapaxes(kb, 0, 1), jnp.swapaxes(vb, 0, 1),
         jnp.arange(n_blocks)))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2)  # (b, h, q, d) -> (b, q, h, d)


# ------------------------------------------------------------ pallas kernel

def _score_mask(scores, causal, lens_val, qi, j, block_q, block_k, sq, sk):
    """Compose the causal and key-padding masks onto one score block.
    ``lens_val`` is this (batch·head)'s valid key count (f32 scalar) or
    None when the call has no padding mask."""
    valid = None
    if causal or lens_val is not None:
        k_pos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
    if causal:
        q_pos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + (sk - sq)
        valid = q_pos >= k_pos
    if lens_val is not None:
        kmask = k_pos.astype(jnp.float32) < lens_val
        valid = kmask if valid is None else valid & kmask
    if valid is None:
        return scores
    return jnp.where(valid, scores, NEG_INF)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k: int,
                      sk: int, causal: bool, sq: int, scale: float,
                      block_q: int, masked: bool):
    """One (batch·head, q-block) cell: iterate key blocks in VMEM with
    online softmax.  Matmuls run at the INPUT dtype (bf16 on the MXU's
    native rate) with f32 accumulation via ``preferred_element_type`` —
    casting inputs up to f32 first (the round-2 version) forfeited ~4× of
    MXU throughput.  Softmax statistics stay f32 for stability.

    Also writes the row logsumexp (``lse_ref``, (1, block_q) f32) — the
    residual the custom-VJP backward kernels replay the softmax from
    without re-running the online reduction.

    ``masked=True`` adds a per-(batch·head) valid-key-count operand
    (``lens_ref``, (1, 1) f32): keys at positions >= the count are
    masked, and whole key blocks beyond it are skipped."""
    if masked:
        lens_ref, o_ref, lse_ref = rest
        lens_val = lens_ref[0, 0]
    else:
        (o_ref, lse_ref), lens_val = rest, None
    q = q_ref[...]  # (block_q, d), input dtype
    qi = pl.program_id(1)
    n_kblocks = sk // block_k

    def body(j, carry):
        m_prev, l_prev, o_prev = carry
        k_blk = k_ref[pl.dslice(j * block_k, block_k), :]
        v_blk = v_ref[pl.dslice(j * block_k, block_k), :]
        scores = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        scores = _score_mask(scores, causal, lens_val, qi, j, block_q,
                             block_k, sq, sk)
        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(scores - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        pv = lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_new = o_prev * corr[:, None] + pv
        return m_new, l_new, o_new

    d = q.shape[-1]
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        # skip key blocks strictly after this q block's last position
        last_q = (qi + 1) * block_q - 1 + (sk - sq)
        n_iter = jnp.minimum(last_q // block_k + 1, n_kblocks)
    else:
        n_iter = n_kblocks
    if masked:
        # skip key blocks entirely past the valid length
        n_valid = jnp.ceil(lens_val / block_k).astype(jnp.int32)
        n_iter = jnp.minimum(n_iter, n_valid)
    m, l, o = lax.fori_loop(0, n_iter, body, (m0, l0, o0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, :] = m + jnp.log(l_safe)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, block_k: int, sk: int, causal: bool,
                         sq: int, scale: float, block_q: int,
                         masked: bool):
    """dq for one (batch·head, q-block) cell.  Replays the softmax from
    the saved logsumexp (p = exp(s - lse), exact — no renormalization
    pass), then dq += (p ∘ (do·vᵀ − Δ)) · k per key block, where
    Δ = rowsum(do ∘ o) is precomputed outside the kernel."""
    if masked:
        lens_ref, dq_ref = rest
        lens_val = lens_ref[0, 0]
    else:
        (dq_ref,), lens_val = rest, None
    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[0, :]      # (block_q,) f32
    delta = delta_ref[0, :]  # (block_q,) f32
    qi = pl.program_id(1)
    n_kblocks = sk // block_k
    d = q.shape[-1]

    def body(j, dq_acc):
        k_blk = k_ref[pl.dslice(j * block_k, block_k), :]
        v_blk = v_ref[pl.dslice(j * block_k, block_k), :]
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _score_mask(s, causal, lens_val, qi, j, block_q, block_k,
                        sq, sk)
        p = jnp.exp(s - lse[:, None])  # masked scores underflow to 0
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq_acc + lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last_q = (qi + 1) * block_q - 1 + (sk - sq)
        n_iter = jnp.minimum(last_q // block_k + 1, n_kblocks)
    else:
        n_iter = n_kblocks
    if masked:
        n_valid = jnp.ceil(lens_val / block_k).astype(jnp.int32)
        n_iter = jnp.minimum(n_iter, n_valid)
    dq = lax.fori_loop(0, n_iter, body,
                       jnp.zeros((block_q, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, block_q: int, sq: int,
                          causal: bool, sk: int, scale: float,
                          block_k: int, masked: bool):
    """dk/dv for one (batch·head, k-block) cell: iterate q blocks (full-
    sequence q/do refs resident in VMEM), accumulating dv += pᵀ·do and
    dk += dsᵀ·q.  Causality skips q blocks entirely before this key
    block (start index), mirroring the forward's key-block skip.
    Padding-masked key blocks need no skip: their replayed p underflows
    to exactly 0, so dk/dv of padded keys come out zero."""
    if masked:
        lens_ref, dk_ref, dv_ref = rest
        lens_val = lens_ref[0, 0]
    else:
        (dk_ref, dv_ref), lens_val = rest, None
    k_blk = k_ref[...]
    v_blk = v_ref[...]
    kj = pl.program_id(1)
    n_qblocks = sq // block_q
    d = k_blk.shape[-1]

    def body(i, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[pl.dslice(i * block_q, block_q), :]
        do_blk = do_ref[pl.dslice(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.dslice(i * block_q, block_q)]
        delta_blk = delta_ref[0, pl.dslice(i * block_q, block_q)]
        s = lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _score_mask(s, causal, lens_val, i, kj, block_q, block_k,
                        sq, sk)
        p = jnp.exp(s - lse_blk[:, None])
        dv_acc = dv_acc + lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * scale
        dk_acc = dk_acc + lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    if causal:
        # first q block whose LAST row reaches this key block:
        # i·block_q + block_q − 1 + (sk − sq) ≥ kj·block_k
        start = jnp.maximum(0, (kj * block_k - (sk - sq)) // block_q)
    else:
        start = 0
    end = n_qblocks
    if masked:
        # a key block entirely past the valid length contributes zero
        # dk/dv — write the zeros without iterating (fwd/dq skip's dual)
        end = jnp.where(kj * block_k >= lens_val, start, end)
    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(start, end, body, (z, z))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _mega(interpret: bool) -> dict:
    """Megacore grid partitioning hints (harmless on one core)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


def _flash_fwd_call(qf, kf, vf, lens, sq, sk, causal, masked, block_q,
                    block_k, scale, interpret):
    bh, _, d = qf.shape
    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k, sk=sk,
                               causal=causal, sq=sq, scale=scale,
                               block_q=block_q, masked=masked)
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
    ]
    args = [qf, kf, vf]
    if masked:
        in_specs.append(pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0)))
        args.append(lens)
    # per-row statistics (lse; lens/delta in the backward) carry an
    # explicit singleton dim — (bh, 1, sq) blocked (None, 1, block_q) —
    # because TPU lowering requires each of a block's minor two dims to
    # be tile-divisible (8/128) OR equal to the full array dim.  A 2-D
    # (bh, sq) stat blocked (1, block_q) puts a size-1 sublane against
    # bh and cannot lower (caught on the first live-chip run of the
    # custom-VJP path, r5).
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=interpret,
        name=_profile.KERNEL_FLASH_FWD,
        **_mega(interpret),
    )(*args)


# static config after the four differentiable-position operands (``lens``
# is a traced (bh, 1) f32 operand — lengths vary per batch at runtime —
# whose cotangent is defined as zero)
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_core(qf, kf, vf, lens, sq, sk, causal, masked, block_q,
                block_k, scale, interpret):
    """Flash attention on folded (batch·heads, seq, head_dim) arrays with
    a flash BACKWARD (pallas dq and dk/dv kernels) — plain ``jax.grad``
    of a ``pallas_call`` is unsupported (pallas has no general transpose
    rule), and recomputing through the XLA blockwise path would forfeit
    the kernel's advantage exactly where the training step spends ~2/3 of
    its attention FLOPs."""
    out, _ = _flash_fwd_call(qf, kf, vf, lens, sq, sk, causal, masked,
                             block_q, block_k, scale, interpret)
    return out


def _flash_core_fwd(qf, kf, vf, lens, sq, sk, causal, masked, block_q,
                    block_k, scale, interpret):
    out, lse = _flash_fwd_call(qf, kf, vf, lens, sq, sk, causal, masked,
                               block_q, block_k, scale, interpret)
    return out, (qf, kf, vf, lens, out, lse)


def _flash_core_bwd(sq, sk, causal, masked, block_q, block_k, scale,
                    interpret, res, do):
    qf, kf, vf, lens, out, lse = res
    bh, _, d = qf.shape
    do = do.astype(qf.dtype)
    # Δ_i = Σ_d do_id·o_id  (= Σ_j p_ij·dp_ij) — cheap elementwise, XLA
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (bh, 1, sq), like lse
    # backward blocks: q-chunk stays at the forward's (which divides sq
    # by construction); key-chunk halves when possible — the dkv cell's
    # (block_q × block_k) f32 p/dp/ds live simultaneously — under the
    # same tiling rule as the forward's, whose block it keeps otherwise.
    bwd_bq = block_q
    bwd_bk = _tiled_block(sk, min(block_k, 512)) or block_k

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_k=bwd_bk, sk=sk, causal=causal, sq=sq,
        scale=scale, block_q=bwd_bq, masked=masked)
    dq_specs = [
        pl.BlockSpec((None, bwd_bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, bwd_bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, 1, bwd_bq), lambda i, j: (i, 0, j)),
        pl.BlockSpec((None, 1, bwd_bq), lambda i, j: (i, 0, j)),
    ]
    dq_args = [qf, kf, vf, do, lse, delta]
    if masked:
        dq_specs.append(pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0)))
        dq_args.append(lens)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, sq // bwd_bq),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((None, bwd_bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), qf.dtype),
        interpret=interpret,
        name=_profile.KERNEL_FLASH_BWD_DQ,
        **_mega(interpret),
    )(*dq_args)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_q=bwd_bq, sq=sq, causal=causal,
        sk=sk, scale=scale, block_k=bwd_bk, masked=masked)
    dkv_specs = [
        pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, bwd_bk, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, bwd_bk, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, 1, sq), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, 1, sq), lambda i, j: (i, 0, 0)),
    ]
    dkv_args = [qf, kf, vf, do, lse, delta]
    if masked:
        dkv_specs.append(pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0)))
        dkv_args.append(lens)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, sk // bwd_bk),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((None, bwd_bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bwd_bk, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), vf.dtype),
        ],
        interpret=interpret,
        name=_profile.KERNEL_FLASH_BWD_DKV,
        **_mega(interpret),
    )(*dkv_args)
    return dq, dk, dv, jnp.zeros_like(lens)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 256,
                    block_k: int = 1024, scale: float = None,
                    interpret: bool = False, layout: str = "bshd",
                    kv_lengths=None):
    """Pallas TPU flash attention.

    Default block caps (q 256 × k 1024) date from an earlier round's
    v5e tuning; they have not been re-measured on today's code.

    ``layout`` (VERDICT r3 #8 — the transpose tax):

    - ``"bshd"`` (default, the shared layout contract): q/k/v are
      (batch, seq, heads, head_dim).  The kernel's grid wants heads
      adjacent to batch, so each array is TRANSPOSED to (b, h, s, d) —
      a materialized copy, ~4 × b·s·h·d·2 bytes of HBM traffic per call
      at bf16 (~64 MB at [4, 2048, 8, 128]).  A 4-D BlockSpec over the
      raw (b, s, h, d) layout cannot lower: the block's minor-two dims
      must be (sublane=s, lane=d), but h sits between them, so any
      (block_q, 1, d) tile puts a size-1 h in the sublane slot
      (captured analysis, PERF_NOTES r3/r4).
    - ``"bhsd"``: q/k/v arrive (batch, heads, seq, head_dim).  Folding
      to the kernel's (b·h, s, d) is a pure reshape of two contiguous
      major axes — NO copy.  Transformer stacks should project straight
      into this layout (``einsum("bse,ehd->bhsd", x, W)``) so XLA folds
      the layout into the projection matmul's output and the transpose
      tax disappears end-to-end.

    ``interpret=True`` runs the kernel in the pallas interpreter (CPU
    testing — SURVEY §4's "local device = cluster" trick applied to
    kernels).

    ``kv_lengths``: optional (batch,) valid key counts — keys at
    positions >= kv_lengths[b] are masked INSIDE the kernels (forward
    and both backward kernels), and whole key blocks beyond the length
    are skipped.  See ``naive_attention`` for the padded-query caveat.

    Lengths with no block that meets the TPU tiling rule (see
    ``_tiled_block``) are handled by padding q/k/v up to a
    128-multiple: padded keys ride the same kv_lengths masking, padded
    query rows are sliced off (their dout is zero through the slice's
    VJP, so real dk/dv are exact).  Shapes that still raise
    (``_flash_plan`` names the reason): causal attention at CROSS
    lengths (sq != sk) that would need padding — equal padding would
    break the q_pos = i + sk - sq alignment there; causal sq > sk; and
    sequences past the VMEM bound of the whole-K/V blocking.
    """
    if layout == "bshd":
        b, sq, h, d = q.shape
        sk = k.shape[1]
    elif layout == "bhsd":
        b, h, sq, d = q.shape
        sk = k.shape[2]
    else:
        raise ValueError(f"layout must be 'bshd' or 'bhsd', got {layout!r}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    plan, why = _flash_plan(causal, sq, sk, d, q.dtype, block_q, block_k)
    if plan is None:
        raise ValueError(why)
    block_q, block_k, pad_q, pad_k = plan
    if layout == "bshd":
        # fold batch and heads into the grid's first axis — a materialized
        # transpose (see docstring; pass layout="bhsd" to avoid it)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    else:
        # contiguous major-axis fold: free
        qf = q.reshape(b * h, sq, d)
        kf = k.reshape(b * h, sk, d)
        vf = v.reshape(b * h, sk, d)

    if pad_q or pad_k:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    masked = kv_lengths is not None or pad_k > 0
    if masked:
        # per-(batch·head) lengths, matching the b-major fold order;
        # clamped to the REAL key count so padded keys stay masked
        base = (_clamp_lengths(kv_lengths, sk) if kv_lengths is not None
                else jnp.full((b,), sk, jnp.float32))
        lens = jnp.repeat(base, h)[:, None, None]
    else:
        lens = jnp.zeros((b * h, 1, 1), jnp.float32)  # inert placeholder
    out = _flash_core(qf, kf, vf, lens, sq + pad_q, sk + pad_k, causal,
                      masked, block_q, block_k, scale, interpret)
    out = out[:, :sq] if pad_q else out
    if layout == "bshd":
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out.reshape(b, h, sq, d)


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _tiled_block(n: int, cap: int) -> int:
    """Largest block <= ``cap`` that divides ``n`` AND meets the TPU
    tiling rule of every spec the kernels feed it to; 0 when none does.

    A q block is the sublane dim of the ``(block, d)`` q/o/do tiles
    (multiple of 8) and the LANE dim of the ``(1, block)`` lse/delta
    statistics and of the dkv kernel's dynamic slices into them
    (multiple of 128 — "or the full dim" satisfies the BlockSpec but
    not the slice: Mosaic must prove the lane offset a multiple of
    128); a k block is the sublane dim of the backward's ``(block, d)``
    k/v tiles and of the in-kernel K/V slices, and the lane dim of the
    ``(block_q, block_k)`` score tile.  One rule covers them all: a
    multiple of 128.  The interpreter has no tiles and would accept any
    divisor — the rule is the chip compiler's, applied everywhere so
    CPU tests exercise the blocks the chip gets."""
    for blk in range(cap - cap % 128, 0, -128):
        if n % blk == 0:
            return blk
    return 0


# Scoped VMEM the TPU compiler grants one kernel (v5e, libtpu 0.0.34:
# "limit 16.00M").  Every kernel takes a whole sequence as ONE block —
# K and V (sk, d) in fwd/dq, Q and dO (sq, d) in dkv — and the pipeline
# double-buffers each, with d padded to the 128-lane tile:
#   resident = 2 arrays x 2 buffers x max(sq, sk) x roundup(d, 128)
#              x itemsize
# The boundary is the compiler's, asked at 12 heads, causal, masked and
# unmasked, d 32..256, bf16 and f32: every probe with resident <= 12 MiB
# compiled (fwd, dq and dkv); the first refusals are at 14 MiB (d 128)
# and 15 MiB (d 64), and between 13 and 16 MiB the outcome is irregular
# (what else the kernel keeps varies) — hence a 4 MiB reserve, not a
# derived figure.  So bf16 runs to sk 12288 and f32 to 6144 (d <= 128).
# Streaming K/V would lift the bound (ROADMAP S7).
_VMEM_LIMIT = 16 * 2 ** 20
_VMEM_RESERVE = 4 * 2 ** 20
_VMEM_BUDGET = _VMEM_LIMIT - _VMEM_RESERVE


def _flash_resident_bytes(sq: int, sk: int, d: int, dtype) -> int:
    """VMEM the whole-sequence blocks pin (see ``_VMEM_LIMIT``)."""
    lanes = -(-d // 128) * 128
    return 4 * max(sq, sk) * lanes * jnp.dtype(dtype).itemsize


def _flash_plan(causal: bool, sq: int, sk: int, d: int, dtype,
                cap_q: int = 256, cap_k: int = 1024):
    """The static block/pad decision for one shape: returns
    ``((block_q, block_k, pad_q, pad_k), None)``, or ``(None, reason)``
    when the kernels cannot run it.  The single source of eligibility:
    ``flash_attention`` raises ``reason``, ``_flash_supports`` (the
    dispatchers' predicate) is ``plan is not None``."""
    if causal and sq > sk:
        # rows aligned before the first key are FULLY masked; their
        # backward replay (p = exp(s − lse)) would cancel the finite
        # NEG_INF sentinel into phantom 1/n probabilities and corrupt
        # dk/dv of valid rows — and the forward's "output" for such rows
        # is meaningless anyway.  blockwise/naive keep the where-based
        # autodiff semantics for this degenerate shape.
        return None, (
            f"causal flash attention needs sq <= sk (got sq={sq}, "
            f"sk={sk}): rows before the first key are fully masked — "
            "use blockwise/naive attention")
    block_q, block_k = _tiled_block(sq, cap_q), _tiled_block(sk, cap_k)
    pad_q = pad_k = 0
    if not (block_q and block_k):
        # no tileable divisor: PAD up to a 128-multiple and mask.
        # Padded keys ride the kv_lengths kernel masking (scores
        # masked, whole padded blocks skipped); padded query rows are
        # sliced off the output, and the slice's VJP zero-fills their
        # dout, so they contribute nothing to dk/dv of real keys.
        # Causal alignment (q_pos = i + sk − sq) survives because both
        # sides pad by the SAME amount — which requires sq == sk.
        if causal and sq != sk:
            return None, (
                f"causal flash attention at cross lengths (sq={sq}, "
                f"sk={sk}) needs a tileable block divisor on both — "
                "use blockwise/naive attention")
        if not block_q or (causal and not block_k):
            pad_q = -sq % 128
        if not block_k or (causal and not block_q):
            pad_k = -sk % 128
        block_q = _tiled_block(sq + pad_q, cap_q)
        block_k = _tiled_block(sk + pad_k, cap_k)
    if not (block_q and block_k):
        # only reachable via caller-supplied block caps below 128
        return None, (
            f"flash attention block caps (block_q={cap_q}, "
            f"block_k={cap_k}) admit no block that meets the TPU "
            f"tiling rule for sq={sq}, sk={sk} (a multiple of 128)")
    resident = _flash_resident_bytes(sq + pad_q, sk + pad_k, d, dtype)
    if resident > _VMEM_BUDGET:
        return None, (
            f"flash attention keeps whole (seq, d) K/V and Q/dO blocks "
            f"in VMEM: sq={sq}, sk={sk}, d={d}, "
            f"{jnp.dtype(dtype).name} pins {resident} bytes, past the "
            f"{_VMEM_BUDGET} the kernels may — use "
            "blockwise attention")
    return (block_q, block_k, pad_q, pad_k), None


def _flash_supports(causal: bool, sq: int, sk: int, d: int,
                    dtype) -> bool:
    """Can ``flash_attention`` (at its default block caps) run this
    shape on the chip?  The single eligibility predicate for both
    dispatchers — ``_flash_plan`` is what ``flash_attention`` itself
    raises from, so the two cannot drift."""
    return _flash_plan(causal, sq, sk, d, dtype)[0] is not None


@functools.lru_cache(maxsize=None)
def _log_auto_fallback(causal, sq, sk, d, dtype_name, why):
    """``auto`` routing an ineligible shape off the kernel ON THE CHIP
    is a static decision on shapes — said once per shape (the cache is
    the once), never discovered by catching the compiler."""
    _slog.info("flash_ineligible_auto_blockwise", causal=causal, sq=sq,
               sk=sk, d=d, dtype=dtype_name, reason=why)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _auto_implementation(causal: bool, sq: int, sk: int, d: int,
                         dtype) -> str:
    """``auto``'s choice, from the platform and the shapes alone: the
    pallas kernel on TPU for every shape ``_flash_plan`` admits,
    blockwise otherwise, naive for lengths whose blocks degenerate."""
    if _on_tpu():
        plan, why = _flash_plan(causal, sq, sk, d, dtype)
        if plan is not None:
            return "flash"
        _log_auto_fallback(causal, sq, sk, d, jnp.dtype(dtype).name, why)
    if min(_largest_divisor(sq, 256), _largest_divisor(sk, 1024)) < 8:
        # prime-ish lengths: blocked XLA scans degenerate, use naive
        return "naive"
    return "blockwise"


def _flash_over_mesh(mesh, q, k, v, causal: bool, kv_lengths,
                     interpret: bool):
    """The kernel under a multi-device Trainer mesh.  GSPMD cannot
    partition a Mosaic kernel ("wrap the call in a shard_map"), so each
    device runs the UNCHANGED kernel on its slice: batch over the data
    axes, heads over ``tensor`` — attention has no term across either.
    A dim its axes do not divide stays whole (every device computes
    it), which is also what the Trainer's replicated-batch fallback
    needs."""
    def dividing(n, names):
        use = tuple(a for a in names
                    if a in mesh.axis_names and mesh.shape[a] > 1)
        size = math.prod(mesh.shape[a] for a in use)
        return use if use and n % size == 0 else None

    b_ax = dividing(q.shape[0], ("data", "fsdp"))
    spec = P(b_ax, dividing(q.shape[1], ("tensor",)), None, None)
    args, specs = [q, k, v], [spec, spec, spec]
    if kv_lengths is not None:
        args.append(jnp.asarray(kv_lengths))
        specs.append(P(b_ax))

    def local(q, k, v, lens=None):
        return flash_attention(q, k, v, causal=causal, layout="bhsd",
                               interpret=interpret, kv_lengths=lens)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=spec, check_vma=False)(*args)


def attention_bhsd(q, k, v, causal: bool = False,
                   implementation: str = "auto", kv_lengths=None):
    """(b, h, s, d)-layout dispatch — the transpose-free fast path for
    transformer stacks that project qkv straight into bhsd
    (``einsum("bse,ehd->bhsd", ...)``; see flash_attention's layout
    note).  On TPU the pallas kernel consumes the layout directly; on
    other backends the arrays are transposed to the (b, s, h, d)
    contract around blockwise/naive (cheap on CPU, where this path is
    only a test oracle).  Explicit ``"flash"`` on a shape the kernels
    cannot run RAISES (never a silent fallback); ``"auto"`` decides
    statically from the shapes (``_auto_implementation``).

    ``kv_lengths``: optional (batch,) valid key counts — right-padded
    batches mask keys past their length in every implementation."""
    sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
    if implementation == "auto":
        implementation = _auto_implementation(causal, sq, sk, d, q.dtype)
    if implementation == "flash":
        mesh = _mesh_lib.get_step_mesh()
        if mesh is not None and mesh.size > 1:
            return _flash_over_mesh(mesh, q, k, v, causal, kv_lengths,
                                    interpret=not _on_tpu())
        return flash_attention(q, k, v, causal=causal, layout="bhsd",
                               interpret=not _on_tpu(),
                               kv_lengths=kv_lengths)
    qs, ks, vs = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if implementation == "blockwise":
        out = blockwise_attention(qs, ks, vs, causal=causal,
                                  block_k=_largest_divisor(sk, 1024),
                                  kv_lengths=kv_lengths)
    elif implementation == "naive":
        out = naive_attention(qs, ks, vs, causal=causal,
                              kv_lengths=kv_lengths)
    else:
        raise ValueError(f"Unknown implementation {implementation!r}")
    return out.transpose(0, 2, 1, 3)


def attention(q, k, v, causal: bool = False, implementation: str = "auto",
              kv_lengths=None):
    """Dispatch on the (b, s, h, d) contract: ``"auto"`` is the pallas
    kernel on TPU for every shape it can run and blockwise/naive
    otherwise (``_auto_implementation``)."""
    sq, sk, d = q.shape[1], k.shape[1], q.shape[3]
    if implementation == "auto":
        implementation = _auto_implementation(causal, sq, sk, d, q.dtype)
        if implementation == "blockwise":
            return blockwise_attention(
                q, k, v, causal=causal,
                block_k=_largest_divisor(sk, 1024), kv_lengths=kv_lengths)
    if implementation == "flash":
        return flash_attention(q, k, v, causal=causal,
                               kv_lengths=kv_lengths)
    if implementation == "blockwise":
        return blockwise_attention(q, k, v, causal=causal,
                                   kv_lengths=kv_lengths)
    if implementation == "naive":
        return naive_attention(q, k, v, causal=causal,
                               kv_lengths=kv_lengths)
    raise ValueError(f"Unknown implementation {implementation!r}")
