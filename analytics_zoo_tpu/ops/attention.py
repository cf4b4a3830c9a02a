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
#
# The three kernels tile the (query, key) plane in (block_q, block_k)
# tiles and visit, for a causal call, only the tiles that hold a
# position on or below the diagonal ``q_pos = i + (sk - sq) >= k_pos``.
# Which tiles those are is arithmetic on block indices, the same for a
# Python int (``_flash_tile_counts``, the static account) and for a
# traced ``program_id`` (the kernels' loop bounds): ``_row_tiles`` and
# ``_col_tiles`` are the one place it is written.  Every visited tile
# of a causal or length-masked call builds its mask: running a bare
# body on the tiles wholly below the diagonal was measured and gave
# nothing (under 2 % at s 4096, where 28 of 36 tiles are such; PERF.md
# §6, PR 35), so there is one body.
#
# Every kernel holds its tile TRANSPOSED, (block_k, block_q) = k·qᵀ:
# keys along sublanes, queries along lanes.  The softmax statistics (m,
# l, lse, Δ) are then one lane-dense row a q block, as lse and Δ are
# stored: a reduction over keys is elementwise across vregs, a
# broadcast over keys is a sublane broadcast, and the rescaling of the
# statistics by exp(m_prev − m_new) touches block_q / 128 vregs instead
# of block_q / 8.  With queries along sublanes (the textbook layout,
# measured at the same 512 x 512 blocks: forward 466 us a call against
# 259, dq 384 against 307) every tile paid two cross-lane reductions
# and two lane broadcasts a row group, which made small tiles slower
# than one 1024-wide tile that skips nothing.

def _row_tiles(qi, block_q: int, block_k: int, n_kblocks: int, off: int,
               causal: bool):
    """Key tiles of q block ``qi`` (forward and dq walk a row of tiles):
    tiles ``[0, n)`` hold a visible position, the rest lies above the
    diagonal and is skipped.  ``off`` is ``sk - sq``, where the diagonal
    sits."""
    if not causal:
        return n_kblocks
    # the block's last row sees keys up to (qi + 1)·block_q − 1 + off
    n = ((qi + 1) * block_q - 1 + off) // block_k + 1
    return min(n, n_kblocks) if isinstance(n, int) \
        else jnp.minimum(n, n_kblocks)


def _row_start(qi, block_q: int, block_k: int, off: int, window):
    """First key tile of q block ``qi`` under a window (query ``i`` sees
    keys ``j`` with ``i - j < window``): the tiles before it lie wholly
    outside the window of the block's FIRST row, hence of every row, and
    are skipped.  0 without a window."""
    if window is None:
        return 0
    first = (qi * block_q + off - window + 1) // block_k
    return max(first, 0) if isinstance(first, int) \
        else jnp.maximum(first, 0)


def _col_tiles(kj, block_q: int, block_k: int, off: int, causal: bool):
    """Query tiles of k block ``kj`` (dkv walks a column of tiles):
    the first tile that holds a visible position; those before it lie
    above the diagonal and are skipped."""
    if not causal:
        return 0
    # first q block whose LAST row reaches this key block:
    # i·block_q + block_q − 1 + off ≥ kj·block_k
    first = (kj * block_k - off) // block_q
    return max(first, 0) if isinstance(first, int) \
        else jnp.maximum(first, 0)


def _row_end(qi, lens_val, block_q, block_k, sq, sk, causal):
    """``_row_tiles`` under a valid key count: key blocks entirely past
    the length are skipped too."""
    n = _row_tiles(qi, block_q, block_k, sk // block_k, sk - sq, causal)
    if lens_val is not None:
        n = jnp.minimum(n, jnp.ceil(lens_val / block_k).astype(jnp.int32))
    return n


def _fold_scale(x, scale: float):
    """``(x * scale, 1.0)`` when that is exact in ``x``'s dtype (a power
    of two, as 1/8 at d_head 64), so the softmax scale is paid on a
    (block, d) operand once a grid cell; else ``(x, scale)`` and the
    scale stays on every score tile."""
    if math.frexp(scale)[0] == 0.5:
        return (x.astype(jnp.float32) * scale).astype(x.dtype), 1.0
    return x, scale


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _score_tile(k_blk, q_blk, post: float, causal: bool, q_pos0, k0,
                lens_val, window=None):
    """One transposed score tile ``k·qᵀ`` (keys along axis 0), scaled by
    what ``_fold_scale`` left over and masked: causal where asked, key
    padding where ``lens_val`` (the valid key count, f32) is given.
    The tile's place enters as two scalars: ``q_pos0``, the diagonal
    position of its first query, and ``k0``, its first key.  ``window``
    (causal calls) also masks the keys a query has left behind,
    ``q_pos - k_pos >= window``."""
    st = _dot(k_blk, q_blk, _NT)
    if post != 1.0:
        st = st * post
    if not causal and lens_val is None:
        return st
    k_iota = lax.broadcasted_iota(jnp.int32, st.shape, 0)
    valid = None
    if causal:
        q_iota = lax.broadcasted_iota(jnp.int32, st.shape, 1)
        valid = q_iota - k_iota >= k0 - q_pos0
        if window is not None:
            valid &= q_iota - k_iota < window + (k0 - q_pos0)
    if lens_val is not None:
        kmask = k_iota.astype(jnp.float32) < lens_val - k0
        valid = kmask if valid is None else valid & kmask
    return jnp.where(valid, st, NEG_INF)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k: int,
                      sk: int, causal: bool, sq: int, scale: float,
                      block_q: int, masked: bool, window=None):
    """One (batch·head, q-block) cell: walk the key tiles of this row
    of the (q, k) plane, K and V whole in VMEM, with online softmax.
    Matmuls run at the INPUT dtype (bf16 on the MXU's native rate) with
    f32 accumulation via ``preferred_element_type``.  Softmax
    statistics stay f32 for stability.  The output accumulates
    transposed, (d, block_q), and is turned once a cell.

    Also writes the row logsumexp (``lse_ref``, (1, block_q) f32) — the
    residual the custom-VJP backward kernels replay the softmax from
    without re-running the online reduction.

    ``masked=True`` adds a per-(batch·head) valid-key-count operand
    (``lens_ref``, (1, 1) f32): keys at positions >= the count are
    masked, and whole key blocks beyond it are skipped.  ``window``
    (forward only, causal): the walk starts at the first tile that holds
    a key inside the window (``_row_start``)."""
    if masked:
        lens_ref, o_ref, lse_ref = rest
        lens_val = lens_ref[0, 0]
    else:
        (o_ref, lse_ref), lens_val = rest, None
    q, post = _fold_scale(q_ref[...], scale)  # (block_q, d), input dtype
    qi = pl.program_id(1)
    d = q.shape[-1]

    def tile(j, carry):
        m_prev, l_prev, o_prev = carry  # (1, bq), (1, bq), (d, bq)
        k0 = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[pl.ds(k0, block_k), :]
        v_blk = v_ref[pl.ds(k0, block_k), :]
        st = _score_tile(k_blk, q, post, causal, qi * block_q + sk - sq,
                         k0, lens_val, window)  # (block_k, block_q)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pt, axis=0, keepdims=True)
        o_new = o_prev * corr + _dot(v_blk, pt.astype(v_blk.dtype), _TN)
        return m_new, l_new, o_new

    m, l, o = lax.fori_loop(
        _row_start(qi, block_q, block_k, sk - sq, window),
        _row_end(qi, lens_val, block_q, block_k, sq, sk, causal), tile,
        (jnp.full((1, block_q), NEG_INF, jnp.float32),
         jnp.zeros((1, block_q), jnp.float32),
         jnp.zeros((d, block_q), jnp.float32)))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l_safe).T.astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l_safe)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, block_k: int, sk: int, causal: bool,
                         sq: int, scale: float, block_q: int,
                         masked: bool):
    """dq for one (batch·head, q-block) cell, over the same row of
    tiles as the forward.  Replays the softmax from the saved logsumexp
    (p = exp(s - lse), exact — no renormalization pass), then
    dqᵀ += kᵀ · (pᵀ ∘ (v·doᵀ − Δ)) per key tile, where Δ = rowsum(do ∘ o)
    is precomputed outside the kernel; the softmax scale multiplies the
    (d, block_q) sum once."""
    if masked:
        lens_ref, dq_ref = rest
        lens_val = lens_ref[0, 0]
    else:
        (dq_ref,), lens_val = rest, None
    q, post = _fold_scale(q_ref[...], scale)
    do = do_ref[...]
    lse = lse_ref[...]      # (1, block_q) f32
    delta = delta_ref[...]  # (1, block_q) f32
    qi = pl.program_id(1)
    d = q.shape[-1]

    def tile(j, dq_acc):
        k0 = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[pl.ds(k0, block_k), :]
        v_blk = v_ref[pl.ds(k0, block_k), :]
        st = _score_tile(k_blk, q, post, causal, qi * block_q + sk - sq,
                         k0, lens_val)
        pt = jnp.exp(st - lse)          # masked scores underflow to 0
        dst = pt * (_dot(v_blk, do, _NT) - delta)
        return dq_acc + _dot(k_blk, dst.astype(k_blk.dtype), _TN)

    dq = lax.fori_loop(
        0, _row_end(qi, lens_val, block_q, block_k, sq, sk, causal), tile,
        jnp.zeros((d, block_q), jnp.float32))
    dq_ref[...] = (dq * scale).T.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, block_q: int, sq: int,
                          causal: bool, sk: int, scale: float,
                          block_k: int, masked: bool):
    """dk/dv for one (batch·head, k-block) cell: walk the q tiles of
    this COLUMN of the (q, k) plane (full-sequence q/do/lse/Δ refs
    resident in VMEM), accumulating dv += pᵀ·do and dk += dsᵀ·q: pᵀ
    and dsᵀ are the left operands of plain matmuls.  Causality skips q
    blocks entirely before this key block (start index), mirroring the
    forward's key-block skip.  Padding-masked keys need no more than
    the mask: their replayed p underflows to exactly 0, so dk/dv of
    padded keys come out zero; a key block entirely past the valid
    length is written as zeros without iterating."""
    if masked:
        lens_ref, dk_ref, dv_ref = rest
        lens_val = lens_ref[0, 0]
    else:
        (dk_ref, dv_ref), lens_val = rest, None
    k_blk, post = _fold_scale(k_ref[...], scale)
    v_blk = v_ref[...]
    kj = pl.program_id(1)
    d = k_blk.shape[-1]
    k0 = kj * block_k
    start = _col_tiles(kj, block_q, block_k, sk - sq, causal)
    end = sq // block_q
    if masked:
        end = jnp.where(k0 >= lens_val, start, end)

    def tile(i, carry):
        dk_acc, dv_acc = carry
        q0 = pl.multiple_of(i * block_q, block_q)
        q_blk = q_ref[pl.ds(q0, block_q), :]
        do_blk = do_ref[pl.ds(q0, block_q), :]
        lse_row = lse_ref[:, pl.ds(q0, block_q)]        # (1, block_q)
        delta_row = delta_ref[:, pl.ds(q0, block_q)]
        st = _score_tile(k_blk, q_blk, post, causal, q0 + sk - sq, k0,
                         lens_val)                      # (block_k, block_q)
        pt = jnp.exp(st - lse_row)
        dv_acc = dv_acc + _dot(pt.astype(do_blk.dtype), do_blk, _NN)
        dst = pt * (_dot(v_blk, do_blk, _NT) - delta_row)
        dk_acc = dk_acc + _dot(dst.astype(q_blk.dtype), q_blk, _NN)
        return dk_acc, dv_acc

    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(start, end, tile, (z, z))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _mega(interpret: bool) -> dict:
    """Megacore grid partitioning hints (harmless on one core)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


# Each call is a ``jax.jit`` of its own with the blocks static, as
# ``_decode_attn_call`` is: a model's layers call it with the same
# shapes, so the kernel is traced and lowered once a program, not once
# a layer.
#
# per-row statistics (lse; lens/delta in the backward) carry an
# explicit singleton dim — (bh, 1, sq) blocked (None, 1, block_q) —
# because TPU lowering requires each of a block's minor two dims to
# be tile-divisible (8/128) OR equal to the full array dim.  A 2-D
# (bh, sq) stat blocked (1, block_q) puts a size-1 sublane against
# bh and cannot lower (caught on the first live-chip run of the
# custom-VJP path, r5).

def _row_spec(block, d):
    """A (block, d) tile of a (bh, s, d) array, by grid cell."""
    return pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0))


def _whole_spec(s, d):
    """A head's whole (s, d) (or (1, s)) array, resident across the
    cell's second grid axis."""
    return pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0))


def _stat_spec(block):
    return pl.BlockSpec((None, 1, block), lambda i, j: (i, 0, j))


def _group_spec(s, d, group: int):
    """``_whole_spec`` for keys and values that ``group`` consecutive
    query heads share: head ``i`` of the grid reads array ``i // group``
    (no copy of K and V a query head; the block stays resident while the
    index does not change)."""
    return pl.BlockSpec((None, s, d), lambda i, j: (i // group, 0, 0))


@functools.partial(jax.jit, static_argnames=(
    "sq", "sk", "causal", "masked", "block_q", "block_k", "scale",
    "interpret", "window", "group"))
def _flash_fwd_call(qf, kf, vf, lens, sq, sk, causal, masked, block_q,
                    block_k, scale, interpret, window=None, group=1):
    """``window`` and ``group`` (grouped-query attention: ``qf`` holds
    ``group`` times the heads of ``kf`` / ``vf``) are the forward's
    alone; without them the kernel is traced as it always was."""
    bh, _, d = qf.shape
    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k, sk=sk,
                               causal=causal, sq=sq, scale=scale,
                               block_q=block_q, masked=masked,
                               **({} if window is None
                                  else {"window": window}))
    kv_spec = (_whole_spec(sk, d) if group == 1
               else _group_spec(sk, d, group))
    in_specs = [_row_spec(block_q, d), kv_spec, kv_spec]
    args = [qf, kf, vf]
    if masked:
        in_specs.append(_whole_spec(1, 1))
        args.append(lens)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=in_specs,
        out_specs=[_row_spec(block_q, d), _stat_spec(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=interpret,
        name=_profile.KERNEL_FLASH_FWD,
        **_mega(interpret),
    )(*args)


@functools.partial(jax.jit, static_argnames=(
    "sq", "sk", "causal", "masked", "block_q", "block_k", "scale",
    "interpret"))
def _flash_bwd_dq_call(qf, kf, vf, do, lse, delta, lens, sq, sk, causal,
                       masked, block_q, block_k, scale, interpret):
    bh, _, d = qf.shape
    kernel = functools.partial(
        _flash_bwd_dq_kernel, block_k=block_k, sk=sk, causal=causal, sq=sq,
        scale=scale, block_q=block_q, masked=masked)
    in_specs = [_row_spec(block_q, d), _whole_spec(sk, d),
                _whole_spec(sk, d), _row_spec(block_q, d),
                _stat_spec(block_q), _stat_spec(block_q)]
    args = [qf, kf, vf, do, lse, delta]
    if masked:
        in_specs.append(_whole_spec(1, 1))
        args.append(lens)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=in_specs,
        out_specs=_row_spec(block_q, d),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), qf.dtype),
        interpret=interpret,
        name=_profile.KERNEL_FLASH_BWD_DQ,
        **_mega(interpret),
    )(*args)


@functools.partial(jax.jit, static_argnames=(
    "sq", "sk", "causal", "masked", "block_q", "block_k", "scale",
    "interpret"))
def _flash_bwd_dkv_call(qf, kf, vf, do, lse, delta, lens, sq, sk, causal,
                        masked, block_q, block_k, scale, interpret):
    bh, _, d = qf.shape
    kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_q=block_q, sq=sq, causal=causal,
        sk=sk, scale=scale, block_k=block_k, masked=masked)
    in_specs = [_whole_spec(sq, d), _row_spec(block_k, d),
                _row_spec(block_k, d), _whole_spec(sq, d),
                _whole_spec(1, sq), _whole_spec(1, sq)]
    args = [qf, kf, vf, do, lse, delta]
    if masked:
        in_specs.append(_whole_spec(1, 1))
        args.append(lens)
    return pl.pallas_call(
        kernel,
        grid=(bh, sk // block_k),
        in_specs=in_specs,
        out_specs=[_row_spec(block_k, d), _row_spec(block_k, d)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), vf.dtype),
        ],
        interpret=interpret,
        name=_profile.KERNEL_FLASH_BWD_DKV,
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
    return _flash_core_fwd(qf, kf, vf, lens, sq, sk, causal, masked,
                           block_q, block_k, scale, interpret)[0]


def _flash_core_fwd(qf, kf, vf, lens, sq, sk, causal, masked, block_q,
                    block_k, scale, interpret):
    out, lse = _flash_fwd_call(qf, kf, vf, lens, sq=sq, sk=sk,
                               causal=causal, masked=masked,
                               block_q=block_q, block_k=block_k,
                               scale=scale, interpret=interpret)
    return out, (qf, kf, vf, lens, out, lse)


def _flash_core_bwd(sq, sk, causal, masked, block_q, block_k, scale,
                    interpret, res, do):
    qf, kf, vf, lens, out, lse = res
    do = do.astype(qf.dtype)
    # Δ_i = Σ_d do_id·o_id  (= Σ_j p_ij·dp_ij) — cheap elementwise, XLA
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (bh, 1, sq), like lse
    static = dict(sq=sq, sk=sk, causal=causal, masked=masked,
                  block_q=block_q, block_k=block_k, scale=scale,
                  interpret=interpret)
    dq = _flash_bwd_dq_call(qf, kf, vf, do, lse, delta, lens, **static)
    dk, dv = _flash_bwd_dkv_call(qf, kf, vf, do, lse, delta, lens, **static)
    return dq, dk, dv, jnp.zeros_like(lens)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 512,
                    block_k: int = 512, scale: float = None,
                    interpret: bool = False, layout: str = "bshd",
                    kv_lengths=None):
    """Pallas TPU flash attention.

    ``block_q`` / ``block_k`` are CAPS: each kernel's blocks are the
    largest multiples of 128 under them that divide the (padded)
    lengths (``_flash_plan``), the same pair for forward, dq and dkv.
    The defaults are measured (PR 35, one v5e; a kernel alone, 16
    chained calls a program, which reads about a tenth above a device
    trace; us a call; ``block_q x block_k``):

    ======================================  ====  ====  ====
    64 heads x 1024 x 64, bf16, causal       fwd    dq   dkv
    ======================================  ====  ====  ====
    512 x 512 (3 of 4 tiles)                 259   307   459
    512 x 256                                311   347   447
    1024 x 512 (no tile skipped)             279   326   526
    1024 x 1024                              294   320   481
    256 x 512                                392   382   572
    256 x 256 (10 of 16 tiles)               437   409   561
    128 x 128 (36 of 64 tiles)                 —     —   847
    queries along sublanes, 512 x 512        466   384     —
    the parent's kernels, 256 x 1024 / 512   476   903 (dq + dkv)
    ======================================  ====  ====  ====

    A tile costs a fixed amount beside its area, so 256-blocks lose to
    512-blocks although they skip more of the causal square, and a
    1024-wide block that skips nothing comes second.  The chat
    engine's float32 prefill (16 heads, forward only; the parent's
    kernel at its plan in brackets): s 128: 128 x 128 50 us [45];
    s 256: 256 x 256 49 [55]; s 512: 512 x 512 59, 256 x 256 78 [79];
    s 768: 384 x 384 93, 768 x 768 74, 256 x 256 105 [119].

    ``layout`` (VERDICT r3 #8 — the transpose tax):

    - ``"bshd"`` (default, the shared layout contract): q/k/v are
      (batch, seq, heads, head_dim).  The kernel's grid wants heads
      adjacent to batch, so each array is TRANSPOSED to (b, h, s, d) —
      a materialized copy, ~4 × b·s·h·d·2 bytes of HBM traffic per call
      at bf16 (~64 MB at [4, 2048, 8, 128]).  A 4-D BlockSpec over the
      raw (b, s, h, d) layout cannot lower: the block's minor-two dims
      must be (sublane=s, lane=d), but h sits between them, so any
      (block_q, 1, d) tile puts a size-1 h in the sublane slot
      (found in rounds 3 and 4 of the first chip runs).
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
    _log_tiles(causal, sq + pad_q, sk + pad_k, block_q, block_k)
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


def _flash_tile_counts(causal: bool, sq: int, sk: int, block_q: int,
                       block_k: int):
    """The static account of the tiling: ``{kernel: (tiles computed,
    tiles of the full square)}``, without ``kv_lengths`` (a length only
    takes tiles away).  Python-int runs of the very ``_row_tiles`` /
    ``_col_tiles`` the kernels bound their loops by: the forward and dq
    walk rows of tiles, dkv columns, and the three agree."""
    n_q, n_k, off = sq // block_q, sk // block_k, sk - sq
    by_row = sum(_row_tiles(i, block_q, block_k, n_k, off, causal)
                 for i in range(n_q))
    by_col = sum(n_q - _col_tiles(j, block_q, block_k, off, causal)
                 for j in range(n_k))
    return {"fwd": (by_row, n_q * n_k), "dq": (by_row, n_q * n_k),
            "dkv": (by_col, n_q * n_k)}


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
# Asked again of the kernels as PR 35 left them (key blocks are still
# slices of a resident K/V, not a grid axis, so the bound has not
# moved): the reserve also holds the (block_k, block_q) f32 score tile
# and its companions, 1 MiB each at 512 x 512, and the pipelined
# (block, d) row tiles, which ``_ROW_TILE_BYTES`` keeps at 256 KiB; the
# edges (12288 bf16 and 6144 f32 at d 32..128, 6144 bf16 and 3072 f32 at
# d 256, causal and not, masked and not) compile for fwd, dq and dkv.
# Streaming K/V would lift the bound (ROADMAP S5).
_VMEM_LIMIT = 16 * 2 ** 20
_VMEM_RESERVE = 4 * 2 ** 20
_VMEM_BUDGET = _VMEM_LIMIT - _VMEM_RESERVE
_ROW_TILE_BYTES = 256 * 2 ** 10


def _row_bytes(d: int, dtype) -> int:
    """One row of a (rows, d) block in VMEM: d padded to the 128-lane
    tile."""
    return -(-d // 128) * 128 * jnp.dtype(dtype).itemsize


def _flash_resident_bytes(sq: int, sk: int, d: int, dtype) -> int:
    """VMEM the whole-sequence blocks pin (see ``_VMEM_LIMIT``)."""
    return 4 * max(sq, sk) * _row_bytes(d, dtype)


def _flash_plan(causal: bool, sq: int, sk: int, d: int, dtype,
                cap_q: int = 512, cap_k: int = 512):
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
    # a (block, d) tile of q, o, do (k, v, dk, dv in dkv) is pipelined
    # in two buffers beside the whole-sequence blocks: at d 256 in f32
    # a 512-row tile is 512 KiB and the forward ran out of VMEM at the
    # bound below; 256 KiB (512 rows up to d 128 in f32, d 256 in bf16)
    # compiled in every probe
    rows = _ROW_TILE_BYTES // _row_bytes(d, dtype)
    cap_q, cap_k = (min(cap, max(128, rows - rows % 128))
                    for cap in (cap_q, cap_k))
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


@functools.lru_cache(maxsize=None)
def _log_tiles(causal, sq, sk, block_q, block_k):
    """Which tiles the three kernels run is a static decision on shapes
    too — said once per shape, as ``(computed, of the square)`` a
    kernel."""
    _slog.info("flash_tiles", causal=causal, sq=sq, sk=sk, block_q=block_q,
               block_k=block_k,
               **_flash_tile_counts(causal, sq, sk, block_q, block_k))


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


@jax.named_scope(_profile.SCOPE_ATTN_CORE)
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


# ------------------------------------------- decode: the key/value slab
#
# The decode paths keep each layer's keys and values in a pair of SLABS
# of shape (capacity, max_len, n_heads * d_head): one row is one whole
# position, all heads side by side.  A decode step needs whole positions
# of every head, and a row of n_heads * d_head floats is a multiple of
# the chip's 128 lanes where d_head alone (64) is not: the compiler
# tiles this shape without padding and keeps no padded copy of it.  The
# functions below are the only code that knows the layout; every site
# that allocates, describes, fills, writes or views a slab calls them.

def kv_slab_shape(capacity: int, max_len: int, n_heads: int,
                  d_head: int):
    """Shape of one slab (keys, or values) of one layer."""
    return (capacity, max_len, n_heads * d_head)


def kv_slab_spec(capacity: int, max_len: int, n_heads: int, d_head: int,
                 sharding=None, dtype=jnp.float32):
    """``(k, v)`` ShapeDtypeStructs of one layer's pair."""
    spec = jax.ShapeDtypeStruct(
        kv_slab_shape(capacity, max_len, n_heads, d_head), dtype,
        sharding=sharding)
    return spec, spec


def kv_slab_zeros(capacity: int, max_len: int, n_heads: int, d_head: int,
                  dtype=jnp.float32):
    """One layer's ``(k, v)`` pair, empty."""
    shape = kv_slab_shape(capacity, max_len, n_heads, d_head)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


@jax.named_scope(_profile.SCOPE_INSERT)
def kv_rows(x):
    """Projected keys or values ``(b, heads, s, d_head)``, as the
    prefill's attention takes them, as slab rows ``(b, s, heads *
    d_head)``."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def kv_heads(rows, n_heads: int):
    """Slab rows ``(b, t, heads * d_head)`` with the heads apart,
    ``(b, t, heads, d_head)``: the view a many-query attention contracts
    against (``einsum("bhkd,bthd->bhkt", q, kv_heads(ck, h))``).  A
    reshape, no copy."""
    b, t, hd = rows.shape
    return rows.reshape(b, t, n_heads, hd // n_heads)


@jax.named_scope(_profile.SCOPE_INSERT)
def kv_pad(rows, cache_len: int):
    """Rows of a prompt, zero-padded on the right to a slab's length."""
    return jnp.pad(rows, [(0, 0), (0, cache_len - rows.shape[1]), (0, 0)])


def kv_insert(slab, rows, slot, start=0):
    """Write ``rows`` (1, s, heads * d_head) of one sequence into slot
    ``slot`` of the slab, at positions ``[start, start + s)``.  A slab
    with a pass axis, ``(capacity, passes, rows, heads * d_head)``, takes
    every pass's rows at once: ``(1, passes, s, heads * d_head)``."""
    return lax.dynamic_update_slice(
        slab, rows.astype(slab.dtype),
        (slot,) + (0,) * (slab.ndim - 3) + (start, 0))


def kv_write_row(slab, row, pos):
    """Write one position's row ``(b, heads * d_head)`` of every
    sequence at ``pos``: a position all share, or ``(b,)`` positions of
    their own."""
    new = row[:, None, :].astype(slab.dtype)
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice_in_dim(slab, new, pos, axis=1)
    return jax.vmap(
        lambda sb, nb, pb: lax.dynamic_update_slice_in_dim(
            sb, nb, pb, axis=0))(slab, new, pos)


# -------------------------------------------- decode: the attention op

#: positions a grid step of the decode kernel takes from a slab.  At a
#: mean of 280 live positions a slot, 128 reads 80 % of what it fetches
#: and 256 reads 68 %.
_DECODE_BLOCK = 128
#: rows of the tile in which the kernel rewrites a slab's new row
_ROW_TILE = 8


def _decode_plan(slots: int, max_len: int, row_width: int, n_heads: int,
                 dtype):
    """The key block of the decode kernel for one slab shape, or
    ``(None, reason)``: the single source of eligibility, as
    ``_flash_plan`` is for flash.  The kernel wants whole blocks
    (``max_len`` a multiple of the block), a lane-dense row
    (``heads * d_head`` a multiple of 128, ``d_head`` a divisor or a
    multiple of 128 so that a lane tile holds whole heads, at most 128
    heads: their scores share one lane tile), float32 (the 8-row write
    tile is the float32 one) and its per-slot operands in VMEM."""
    if jnp.dtype(dtype) != jnp.float32:
        return None, f"the slab is {jnp.dtype(dtype).name}, not float32"
    d = row_width // max(n_heads, 1)
    if (row_width % 128 or row_width % n_heads or n_heads > 128
            or (128 % d and d % 128)):
        return None, (f"a row of {row_width} floats in {n_heads} heads is "
                      "not lane-dense (a multiple of 128, d_head a "
                      "divisor or a multiple of 128, at most 128 heads)")
    if max_len % _DECODE_BLOCK:
        return None, (f"max_len {max_len} is not a multiple of the key "
                      f"block ({_DECODE_BLOCK})")
    # double-buffered: q, the two new rows and the accumulator of every
    # slot, their per-head scalars, two blocks of each slab, the 0/1
    # matrix; the same reserve as the flash kernels keep
    slots8 = -(-slots // 8) * 8
    resident = 2 * 4 * (4 * slots8 * row_width + 2 * slots8 * 128
                        + 2 * _DECODE_BLOCK * row_width) \
        + 2 * 2 * row_width * 128
    if resident > _VMEM_BUDGET:
        return None, (f"{slots} slots of {row_width} floats pin {resident} "
                      f"bytes of VMEM, past the {_VMEM_BUDGET} the kernel "
                      "may")
    return _DECODE_BLOCK, None


def _kernel_block(slots: int, max_len: int, row_width: int, n_heads: int,
                  dtype):
    """The kernel's key block where the kernel runs (a TPU, a shape the
    plan admits), else ``None``."""
    if not _on_tpu():
        return None
    return _decode_plan(slots, max_len, row_width, n_heads, dtype)[0]


def decode_read_block(slots: int, max_len: int, row_width: int,
                      n_heads: int, dtype=jnp.float32) -> int:
    """Positions of a slot's slab that one decode step reads at a time:
    the kernel's key block where :func:`decode_attention` runs the
    kernel (it then reads the blocks up to the slot's length and no
    more), else ``max_len`` (the masked full-length softmax reads the
    whole slab).  What the engine's ``kv_positions_read`` counts by."""
    return _kernel_block(slots, max_len, row_width, n_heads,
                         dtype) or max_len


@functools.lru_cache(maxsize=None)
def _segments(row_width: int, n_heads: int):
    """``(row_width, 128)`` 0/1 matrix ``S[i, h] = (i // d_head == h)``
    (numpy, made once per shape): ``x @ S`` sums a row's lanes head by
    head.  bfloat16 holds 0 and 1 exactly."""
    d = row_width // n_heads
    return (np.arange(row_width)[:, None] // d
            == np.arange(128)[None, :]).astype(np.float32)


def _dot_exact(a, seg):
    """``a @ seg`` in float32 for a 0/1 ``seg``: ``a`` split into three
    bfloat16 terms that add up to it (8 + 8 + 8 bits of mantissa), one
    MXU pass each, summed in float32.  The 0/1 operand is exact in
    bfloat16, so nothing is lost but the float32 sums' own rounding:
    the products of queries and keys stay float32, as in the masked
    full-softmax this kernel replaces."""
    a1 = a.astype(jnp.bfloat16)
    r = a - a1.astype(jnp.float32)
    a2 = r.astype(jnp.bfloat16)
    a3 = (r - a2.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(x):
        return jnp.dot(x, seg, preferred_element_type=jnp.float32)

    return dot(a1) + dot(a2) + dot(a3)


def _spread(x, row_width: int, d_head: int):
    """``x (rows, 128)``, a value per head in lane ``h``, over the lanes
    of the heads: ``(rows, row_width)`` with ``x[:, i // d_head]`` in
    lane ``i``.  Lane broadcasts and selects, one lane tile at a time:
    exact, and cheaper here than a product with the transposed 0/1
    matrix."""
    rows = x.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    def of_head(h):
        return jnp.broadcast_to(x[:, h:h + 1], (rows, 128))

    tiles = []
    for c in range(row_width // 128):
        if d_head >= 128:
            tiles.append(of_head(128 * c // d_head))
            continue
        per = 128 // d_head             # heads in this lane tile
        tile = of_head(per * c + per - 1)
        for i in range(per - 2, -1, -1):
            tile = jnp.where(lane < (i + 1) * d_head, of_head(per * c + i),
                             tile)
        tiles.append(tile)
    return jnp.concatenate(tiles, axis=1)


def _decode_attn_kernel(pos_ref, slot_ref, blk_ref, q_ref, kn_ref, vn_ref,
                        m0_ref, ck_ref, cv_ref, seg_ref,
                        acc_out, l_out, cko_ref, cvo_ref,
                        m_ref, l_ref, acc_ref, *, block: int, d_head: int):
    """One LIVE key block of one slot.  The grid is flat and runs over
    the live blocks only, slot after slot (``slot_ref`` / ``blk_ref``,
    scalar prefetch, say which; ``pos_ref`` holds every slot's position:
    the new row's index, so positions ``< pos`` of the slab are live).
    The softmax state (running max and sum per head, accumulator per
    lane) starts from the NEW row, which never leaves VMEM; each block
    then adds its positions ``< pos`` (online softmax); a slot's last
    block, the one that holds row ``pos``, also rewrites that row's
    8-row tile in place and hands out the slot's sum and accumulator.
    The per-slot operands (query, new rows, the new row's scores) and
    results are whole-array blocks that stay in VMEM: nothing small is
    fetched or written back between slots, so one slot's blocks stream
    in behind the last one's."""
    g = pl.program_id(0)
    s, j = slot_ref[g], blk_ref[g]
    pos = pos_ref[s]
    mine = pl.ds(s, 1)                  # this slot's row of the operands
    row = ck_ref.shape[-1]

    @pl.when(j == 0)
    def _start():
        m_ref[...] = m0_ref[mine, :]
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = vn_ref[mine, :].astype(jnp.float32)

    live = (j * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            < pos)
    scores = jnp.where(
        live, _dot_exact(ck_ref[...] * q_ref[mine, :], seg_ref[...]),
        NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)         # a dead row: exp(-1e30 - m) = 0
    l_new = l_ref[...] * corr + jnp.sum(p, axis=0, keepdims=True)
    l_ref[...] = l_new
    m_ref[...] = m_new
    # a dead row may hold anything (NaN times 0 is NaN): select it out
    acc = (acc_ref[...] * _spread(corr, row, d_head)
           + jnp.sum(_spread(p, row, d_head)
                     * jnp.where(live, cv_ref[...], 0.0),
                     axis=0, keepdims=True))
    acc_ref[...] = acc

    @pl.when(j == pos // block)
    def _finish():
        tile = pl.multiple_of((pos % block) // _ROW_TILE * _ROW_TILE,
                              _ROW_TILE)
        new = (lax.broadcasted_iota(jnp.int32, (_ROW_TILE, 1), 0)
               == pos % _ROW_TILE)
        cko_ref[...] = jnp.where(new, kn_ref[mine, :],
                                 ck_ref[pl.ds(tile, _ROW_TILE), :])
        cvo_ref[...] = jnp.where(new, vn_ref[mine, :],
                                 cv_ref[pl.ds(tile, _ROW_TILE), :])
        acc_out[mine, :] = acc
        l_out[mine, :] = l_new


def _live_blocks(pos, block: int, n_blocks: int):
    """The kernel's grid: slot ``b`` takes ``pos[b] // block + 1`` steps
    (the blocks up to the one that holds its new row), slot after slot.
    Returns ``(slot of step, block of step, steps in all)``; entries
    past the last step are never read."""
    b = pos.shape[0]
    mine = pos // block + 1
    ends = jnp.cumsum(mine)
    steps = jnp.arange(b * n_blocks, dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.sum(ends[None, :] <= steps[:, None], axis=1), b - 1)
    blk_of = steps - (ends - mine)[slot_of]
    return slot_of.astype(jnp.int32), blk_of.astype(jnp.int32), ends[-1]


@functools.partial(jax.jit, static_argnames=("n_heads", "block",
                                             "interpret"))
def _decode_attn_call(q, k_new, v_new, ck, cv, pos, n_heads: int,
                      block: int, interpret: bool):
    """The kernel over ``(b, row)`` q / new rows, ``(b, max_len, row)``
    slabs and ``(b,)`` int32 positions.  Around it, in plain jax: the
    new row's own scores (where the softmax state starts), the list of
    live blocks, and the division by the softmax's sum.

    A jit of its own inside the step: a model's layers all call it with
    the same shapes, so it is traced once and lowered once a program (a
    private function the layers call) instead of once a layer; lowering
    a Mosaic kernel 24 times over took seconds of every plan's build and
    made each executable carry 24 copies of it.  XLA inlines the calls,
    so the slabs are still updated in place."""
    b, t, row = ck.shape
    d = row // n_heads
    qs = q * (1.0 / math.sqrt(d))
    m0 = jnp.pad((qs * k_new).reshape(b, n_heads, d).sum(-1),
                 ((0, 0), (0, 128 - n_heads)))
    slot_of, blk_of, n_steps = _live_blocks(pos, block, t // block)
    seg = jnp.asarray(_segments(row, n_heads), jnp.bfloat16)

    def whole(*shape):      # fetched once, stays in VMEM
        return pl.BlockSpec(shape, lambda g, pos, so, bo: (0,) * len(shape))

    slab = pl.BlockSpec((None, block, row),
                        lambda g, pos, so, bo: (so[g], bo[g], 0))
    tile = pl.BlockSpec(
        (None, _ROW_TILE, row),
        lambda g, pos, so, bo: (so[g], pos[so[g]] // _ROW_TILE, 0))
    acc, l, ck, cv = pl.pallas_call(
        functools.partial(_decode_attn_kernel, block=block, d_head=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_steps,),        # as many steps as blocks are live
            in_specs=[whole(b, row), whole(b, row), whole(b, row),
                      whole(b, 128), slab, slab, whole(*seg.shape)],
            out_specs=[whole(b, row), whole(b, 128), tile, tile],
            scratch_shapes=[pltpu.VMEM((1, 128), jnp.float32),
                            pltpu.VMEM((1, 128), jnp.float32),
                            pltpu.VMEM((1, row), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, row), jnp.float32),
                   jax.ShapeDtypeStruct((b, 128), jnp.float32),
                   jax.ShapeDtypeStruct(ck.shape, ck.dtype),
                   jax.ShapeDtypeStruct(cv.shape, cv.dtype)],
        # operands count from the scalar prefetch: the slabs are 7 and 8
        input_output_aliases={7: 2, 8: 3},
        interpret=interpret,
        name=_profile.KERNEL_DECODE_ATTN,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}),
    )(pos, slot_of, blk_of, qs, k_new, v_new, m0, ck, cv, seg)
    out = acc / jnp.repeat(l[:, :n_heads], d, axis=-1)
    return out.astype(q.dtype), ck, cv


def _decode_attention_reference(q, k_new, v_new, ck, cv, pos,
                                n_heads: int):
    """The masked full-length softmax in ``jax.numpy``: what runs off
    the chip and for shapes the kernel refuses, and what the kernel is
    tested against.  ``pos`` may be a scalar all rows share."""
    ck = kv_write_row(ck, k_new, pos)
    cv = kv_write_row(cv, v_new, pos)
    b, t, row = ck.shape
    d = row // n_heads
    scores = jnp.einsum("bhd,bthd->bht", q.reshape(b, n_heads, d),
                        kv_heads(ck, n_heads)) / math.sqrt(d)
    posv = jnp.broadcast_to(pos, (b,))
    valid = jnp.arange(t)[None, None, :] <= posv[:, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bht,bthd->bhd", probs.astype(cv.dtype),
                   kv_heads(cv, n_heads))
    return o.reshape(b, row), ck, cv


def decode_attention(q, k_new, v_new, ck, cv, pos, n_heads: int,
                     mesh=None):
    """One decode step's attention of every sequence over its own slab.

    ``q``, ``k_new``, ``v_new``: ``(b, heads * d_head)``, the step's
    query and the new position's key and value; ``ck``, ``cv``:
    ``(b, max_len, heads * d_head)`` slabs (``kv_slab_*``); ``pos``: the
    new position, a scalar all sequences share or ``(b,)`` of their
    own, in ``[0, max_len)``.  Writes the new row at ``pos`` and attends
    to positions ``<= pos``.  Returns ``(o (b, heads * d_head), ck,
    cv)``.

    On a TPU, for a shape ``_decode_plan`` admits, this is the pallas
    kernel ``zoo_decode_attn``: its grid runs over the live key blocks
    only (a slot's slab up to the block that holds ``pos``) and it
    rewrites the new row in place, so a step moves the live positions
    and nothing else.  Otherwise it is the
    masked full-length softmax.  ``mesh``: the mesh over whose axes the
    caller has sharded the sequences (a mesh-sharded decode engine); the
    kernel then runs inside a ``shard_map`` over them, each device on
    its own sequences (GSPMD cannot partition a Mosaic kernel)."""
    b, t, row = ck.shape
    block = _kernel_block(b, t, row, n_heads, ck.dtype)
    if block is None:
        return _decode_attention_reference(q, k_new, v_new, ck, cv, pos,
                                           n_heads)
    pos = jnp.clip(jnp.broadcast_to(pos, (b,)).astype(jnp.int32), 0, t - 1)
    call = functools.partial(_decode_attn_call, n_heads=n_heads,
                             block=block, interpret=False)
    if mesh is None or mesh.size == 1:
        return call(q, k_new, v_new, ck, cv, pos)
    axes = tuple(mesh.axis_names)
    rows, slabs = P(axes, None), P(axes, None, None)
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(rows, rows, rows, slabs, slabs, P(axes)),
        out_specs=(rows, slabs, slabs), check_vma=False)(
            q, k_new, v_new, ck, cv, pos)


# ------------------------- grouped queries, a window, a bfloat16 slab
#
# The ``cohere2_moe`` family's attention: ``n_heads`` query heads over
# ``n_kv_heads`` cached heads (query head h reads cached head
# ``h // group``), causal, on some layers inside a window (query i sees
# keys j with ``i - j < window``).  Forward only: the prefill runs the
# flash forward kernel with the key/value block index mapped through
# the group and the window turned into tile bounds; the decode step has
# a kernel of its own over a bfloat16 slab whose rows may be a RING.

def rope_interleaved(x, pos, theta: float):
    """Rotary positions over interleaved pairs (``rope_gptj``): the pair
    ``(x[2i], x[2i + 1])`` of the last axis turns by ``pos * theta **
    (-2i / d)``.  ``pos`` broadcasts against ``x.shape[:-1]``.
    Float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def rope_half(x, pos, theta: float):
    """Rotary positions by rotate-half (the Llama / Qwen lineage's): the
    pair ``(x[i], x[i + d/2])`` of the last axis turns by ``pos * theta
    ** (-2i / d)``.  ``pos`` broadcasts against ``x.shape[:-1]``.
    Float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


#: how a layer pairs the dimensions its rotary positions turn
ROPES = {"interleaved": rope_interleaved, "half": rope_half}


@jax.named_scope(_profile.SCOPE_ATTN_PROJ)
def gqa_qkv(p, h, pos, rope_theta=None, rope="interleaved"):
    """``h (b, s, d_model)`` through ``Wq (d_model, heads, d)``, ``Wk``,
    ``Wv (d_model, kv_heads, d)`` into ``(b, heads, s, d)`` layout, the
    products in the weights' dtype with float32 accumulation; ``q`` and
    ``k`` turned at ``pos`` (``(s,)`` or ``(b, s)``) by :func:`rope_
    interleaved` or, for ``rope="half"``, :func:`rope_half`, where
    ``rope_theta`` is given (a layer without it has no positions at
    all).  Returns the three in the weights' dtype: keys are cached as
    they come out, positions applied."""
    hb = h.astype(p["Wq"].dtype)

    def proj(w):
        return jnp.einsum("bse,ehd->bhsd", hb, w,
                          preferred_element_type=jnp.float32)

    q, k, v = proj(p["Wq"]), proj(p["Wk"]), proj(p["Wv"])
    if rope_theta is not None:
        at = jnp.asarray(pos)
        at = at[None, None, :] if at.ndim == 1 else at[:, None, :]
        turn = ROPES[rope]
        q = turn(q, at, rope_theta)
        k = turn(k, at, rope_theta)
    dt = p["Wq"].dtype
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _attention_gqa_reference(q, k, v, window=None):
    """The masked softmax in ``jax.numpy`` over ``(b, heads, s, d)``
    queries and ``(b, kv_heads, s, d)`` keys and values: what runs off
    the chip and what the kernel is tested against."""
    b, h, s, d = q.shape
    g = h // k.shape[1]
    qg = q.reshape(b, k.shape[1], g, s, d)
    scores = jnp.einsum("bngsd,bntd->bngst", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), axis=-1)
    o = jnp.einsum("bngst,bntd->bngsd", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, s, d).astype(q.dtype)


@jax.named_scope(_profile.SCOPE_ATTN_CORE)
def attention_gqa_bhsd(q, k, v, window=None, interpret=None):
    """Causal self-attention of ``(b, heads, s, d)`` queries over
    ``(b, kv_heads, s, d)`` keys and values, ``heads`` a multiple of
    ``kv_heads``, inside ``window`` where one is given.  On a TPU, for a
    shape ``_flash_plan`` admits, the flash forward kernel
    (``zoo_flash_fwd``) with no copy of K and V a query head and only
    the tiles that hold a visible position; otherwise the masked softmax
    in ``jax.numpy``.  ``interpret``: force the kernel (True: in the
    pallas interpreter), as the tests do."""
    b, h, s, d = q.shape
    n_kv = k.shape[1]
    if h % n_kv:
        raise ValueError(f"{h} query heads do not divide over {n_kv} "
                         "key/value heads")
    plan = _flash_plan(True, s, s, d, q.dtype)[0]
    run = (_on_tpu() and plan is not None and not plan[2]) \
        if interpret is None else True
    if not run:
        return _attention_gqa_reference(q, k, v, window)
    block_q, block_k = plan[:2]
    out, _ = _flash_fwd_call(
        q.reshape(b * h, s, d), k.reshape(b * n_kv, s, d),
        v.reshape(b * n_kv, s, d), jnp.zeros((b * h, 1, 1), jnp.float32),
        sq=s, sk=s, causal=True, masked=False, block_q=block_q,
        block_k=block_k, scale=1.0 / math.sqrt(d),
        interpret=bool(interpret), window=window, group=h // n_kv)
    return out.reshape(b, h, s, d)


#: rows a grid step of the grouped decode kernel takes from a slab: the
#: largest of these that divides the slab's length.  A row is kv_heads x
#: d_head x 2 bytes: 2 KiB at 8 cached heads of 128, so 512 rows are a
#: 1 MiB transfer a slab; 1 KiB at 8 of 64, where 1280 rows take 256.
_GQA_BLOCKS = (512, 256, 128)
#: rows of the bfloat16 tile in which the kernel rewrites the new row
_GQA_ROW_TILE = 16
#: lanes of the slab's tile, the kernel's unit: one cached head of 128,
#: or ``128 / d_head`` narrower ones side by side
_GQA_LANES = 128


def _decode_gqa_plan(rows: int, n_heads: int, n_kv_heads: int, d_head: int,
                     dtype):
    """The row block of the grouped decode kernel for one slab shape, or
    ``(None, reason)``.  It wants a bfloat16 slab (the 16-row write
    tile is the bfloat16 one), heads of 128, 64 or 32 (a lane tile holds
    one, two or four cached heads), whole groups, a row of whole lane
    tiles, and a length that a block divides."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return None, f"the slab is {jnp.dtype(dtype).name}, not bfloat16"
    if d_head not in (128, 64, 32) or n_heads % n_kv_heads \
            or n_kv_heads * d_head % _GQA_LANES:
        return None, (f"{n_heads} query heads over {n_kv_heads} cached "
                      f"heads of {d_head}: not whole groups of heads of "
                      "128, 64 or 32 in whole lane tiles")
    for block in _GQA_BLOCKS:
        if rows % block == 0:
            return block, None
    return None, f"no block of {_GQA_BLOCKS} divides {rows} rows"


def decode_gqa_read_block(rows: int, n_heads: int, n_kv_heads: int,
                          d_head: int, dtype) -> int:
    """Rows of a slot's slab that one step of
    :func:`decode_attention_gqa` reads at a time: the kernel's block
    where the kernel runs, else all ``rows``."""
    if not _on_tpu():
        return rows
    return _decode_gqa_plan(rows, n_heads, n_kv_heads, d_head,
                            dtype)[0] or rows


def _decode_gqa_kernel(row_ref, live_ref, slot_ref, blk_ref, q_ref, kn_ref,
                       vn_ref, ck_ref, cv_ref, o_ref, cko_ref, cvo_ref,
                       m_ref, l_ref, acc_ref, *, block: int, n_tiles: int,
                       group: int, scale: float):
    """One live row block of one slot; the grid is flat over the live
    blocks, slot after slot, as ``_decode_attn_kernel``'s.  Per 128-lane
    tile of the slab (one cached head of 128, or 128 / d narrower ones
    side by side), the tile's ``(group, 128)`` query rows against the
    block's ``(block, 128)`` keys on the MXU, an online softmax whose
    state (``m``, ``l`` lane-broadcast, ``acc``: a row a query head)
    starts from the NEW row, which never leaves VMEM.  A query row holds
    its head's values in its own cached head's lanes and zeros in the
    tile's others (``_decode_gqa_call`` packs them), so its score is its
    own head's, and of its ``acc`` row only its own head's lanes are
    its result.  ``row_ref``: where the new row goes (``pos`` mod the
    slab's length: a windowed layer's slab is a ring); ``live_ref``: how
    many rows are live, the new one included.  Row ``row`` of the slab
    itself is stale (the ring's oldest position, or nothing yet) and
    masked; the block that holds it rewrites its 16-row tile in place,
    and the slot's last block hands out the normalised result."""
    g = pl.program_id(0)
    s, j = slot_ref[g], blk_ref[g]
    row, n_live = row_ref[s], live_ref[s]
    d = _GQA_LANES
    heads = [(slice(h * group, (h + 1) * group), slice(h * d, (h + 1) * d))
             for h in range(n_tiles)]

    @pl.when(j == 0)
    def _start():
        for qs, ls in heads:
            s0 = jnp.sum(q_ref[qs, :].astype(jnp.float32)
                         * kn_ref[:, ls].astype(jnp.float32),
                         axis=1, keepdims=True) * scale
            m_ref[qs, :] = jnp.broadcast_to(s0, (group, d))
            acc_ref[qs, :] = jnp.broadcast_to(
                vn_ref[:, ls].astype(jnp.float32), (group, d))
        l_ref[...] = jnp.ones_like(l_ref)

    idx = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
    live = (idx < n_live) & (idx != row)
    for qs, ls in heads:
        st = jnp.where(live, _dot(q_ref[qs, :], ck_ref[:, ls], _NT) * scale,
                       NEG_INF)                         # (group, block)
        m_prev = m_ref[qs, :]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=1, keepdims=True))
        p = jnp.exp(st - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_ref[qs, :] = l_ref[qs, :] * corr + jnp.sum(p, axis=1,
                                                     keepdims=True)
        m_ref[qs, :] = m_new
        acc_ref[qs, :] = acc_ref[qs, :] * corr + _dot(
            p.astype(cv_ref.dtype), cv_ref[:, ls], _NN)

    @pl.when(j == row // block)
    def _write():
        tile = pl.multiple_of(
            (row % block) // _GQA_ROW_TILE * _GQA_ROW_TILE, _GQA_ROW_TILE)
        new = (lax.broadcasted_iota(jnp.int32, (_GQA_ROW_TILE, 1), 0)
               == row % _GQA_ROW_TILE)
        cko_ref[...] = jnp.where(new, kn_ref[...],
                                 ck_ref[pl.ds(tile, _GQA_ROW_TILE), :])
        cvo_ref[...] = jnp.where(new, vn_ref[...],
                                 cv_ref[pl.ds(tile, _GQA_ROW_TILE), :])

    @pl.when(j == (n_live - 1) // block)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _own_lanes(n_heads: int, group: int, d: int):
    """``(n_heads, 128)``: True in the lanes of a query head's own cached
    head within its lane tile.  Head ``h`` reads cached head ``h //
    group``, which sits ``(h // group) % (128 // d)`` heads into its
    tile; query heads are ordered by cached head, so a tile's query rows
    are consecutive."""
    per = _GQA_LANES // d
    return (np.arange(n_heads)[:, None] // group % per
            == np.arange(_GQA_LANES)[None, :] // d)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "block", "interpret"))
def _decode_gqa_call(q, k_new, v_new, ck, cv, pos, n_heads: int,
                     n_kv_heads: int, block: int, interpret: bool,
                     pass_index=None):
    """The kernel over ``(b, heads * d)`` queries, ``(b, kv_heads * d)``
    new rows, ``(b, rows, kv_heads * d)`` bfloat16 slabs and ``(b,)``
    int32 positions.  A jit of its own inside the step, as
    ``_decode_attn_call`` is: traced and lowered once a program.  A head
    narrower than a lane tile goes in with its values in its own cached
    head's lanes of the tile and zeros in the others (``_own_lanes``),
    and its result comes out of those lanes; heads of 128 go in and come
    out as they are.

    Slabs with a pass axis, ``(b, passes, rows, kv_heads * d)``, take
    ``pass_index`` (an int32 scalar, a value at run time): one more
    prefetched scalar that the slab's and the write tile's index maps
    read, so the kernel reads that pass's live row blocks and writes its
    new row in place, and the other passes' rows are not touched."""
    b, rows, width = ck.shape[0], ck.shape[-2], ck.shape[-1]
    d = width // n_kv_heads
    lanes, per = _GQA_LANES, _GQA_LANES // d   # cached heads a lane tile
    row = (pos % rows).astype(jnp.int32)
    n_live = jnp.minimum(pos + 1, rows).astype(jnp.int32)
    slot_of, blk_of, n_steps = _live_blocks(n_live - 1, block,
                                            rows // block)
    scalars = [row, n_live, slot_of, blk_of]
    kernel = functools.partial(_decode_gqa_kernel, block=block,
                               n_tiles=width // lanes,
                               group=per * n_heads // n_kv_heads,
                               scale=1.0 / math.sqrt(d))
    # an index map takes the grid step and the prefetched scalars
    if pass_index is None:
        lead = ()

        def at(index):
            return lambda g, r, n, so, bo: index(g, r, so, bo)
    else:
        lead = (None,)
        scalars.append(jnp.reshape(pass_index, (1,)).astype(jnp.int32))

        def at(index):      # the pass between the slot and the rows
            def with_pass(g, r, n, so, bo, t):
                slot, *rest = index(g, r, so, bo)
                return (slot, t[0], *rest)
            return with_pass

        def kernel(row_ref, live_ref, slot_ref, blk_ref, pass_ref, *refs,
                   _inner=kernel):      # the pass is the index maps' alone
            _inner(row_ref, live_ref, slot_ref, blk_ref, *refs)

    def mine(*shape):       # one slot's block of a per-slot operand
        return pl.BlockSpec((None,) + shape,
                            lambda g, r, n, so, *_: (so[g], 0, 0))

    slab = pl.BlockSpec((None,) + lead + (block, width),
                        at(lambda g, r, so, bo: (so[g], bo[g], 0)))
    tile = pl.BlockSpec(
        (None,) + lead + (_GQA_ROW_TILE, width),
        at(lambda g, r, so, bo: (so[g], r[so[g]] // _GQA_ROW_TILE, 0)))
    q = q.astype(ck.dtype).reshape(b, n_heads, d)
    if per > 1:     # each head into its own cached head's lanes
        own = _own_lanes(n_heads, n_heads // n_kv_heads, d)
        q = jnp.where(own, jnp.tile(q, (1, 1, per)), 0)
    # operands count from the scalar prefetch: the slabs follow q and the
    # two new rows
    first_slab = len(scalars) + 3
    o, ck, cv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_steps,),        # as many steps as blocks are live
            in_specs=[mine(n_heads, lanes), mine(1, width), mine(1, width),
                      slab, slab],
            out_specs=[mine(n_heads, lanes), tile, tile],
            scratch_shapes=[pltpu.VMEM((n_heads, lanes), jnp.float32)] * 3),
        out_shape=[jax.ShapeDtypeStruct((b, n_heads, lanes), ck.dtype),
                   jax.ShapeDtypeStruct(ck.shape, ck.dtype),
                   jax.ShapeDtypeStruct(cv.shape, cv.dtype)],
        input_output_aliases={first_slab: 1, first_slab + 1: 2},
        interpret=interpret,
        name=_profile.KERNEL_DECODE_ATTN_GQA,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}),
    )(*scalars, q,
      k_new.astype(ck.dtype)[:, None, :], v_new.astype(cv.dtype)[:, None, :],
      ck, cv)
    if per > 1:     # a head's result is its own lanes; the rest are zeros
        o = jnp.where(own, o, 0).reshape(b, n_heads, per, d).sum(axis=2)
    return o.reshape(b, n_heads * d), ck, cv


def _decode_attention_gqa_reference(q, k_new, v_new, ck, cv, pos,
                                    n_heads: int, n_kv_heads: int,
                                    pass_index=None):
    """The masked full-length softmax in ``jax.numpy``: the new row
    written at ``pos`` mod the slab's length, then every query head over
    the live rows of its cached head.  Slabs with a pass axis: pass
    ``pass_index``'s slabs, written back in their place."""
    if pass_index is not None:
        part = [lax.dynamic_index_in_dim(c, pass_index, axis=1,
                                         keepdims=False) for c in (ck, cv)]
        o, k1, v1 = _decode_attention_gqa_reference(
            q, k_new, v_new, *part, pos, n_heads, n_kv_heads)
        return (o, lax.dynamic_update_index_in_dim(ck, k1, pass_index, 1),
                lax.dynamic_update_index_in_dim(cv, v1, pass_index, 1))
    b, rows, width = ck.shape
    d = width // n_kv_heads
    posv = jnp.broadcast_to(pos, (b,))
    ck = kv_write_row(ck, k_new, posv % rows)
    cv = kv_write_row(cv, v_new, posv % rows)
    qg = q.astype(ck.dtype).reshape(b, n_kv_heads, n_heads // n_kv_heads, d)
    scores = jnp.einsum("bngd,btnd->bngt", qg, kv_heads(ck, n_kv_heads),
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    live = jnp.arange(rows)[None, :] < jnp.minimum(posv + 1, rows)[:, None]
    probs = jax.nn.softmax(
        jnp.where(live[:, None, None, :], scores, NEG_INF), axis=-1)
    o = jnp.einsum("bngt,btnd->bngd", probs.astype(cv.dtype),
                   kv_heads(cv, n_kv_heads),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, n_heads * d).astype(ck.dtype), ck, cv


def decode_attention_gqa(q, k_new, v_new, ck, cv, pos, n_heads: int,
                         n_kv_heads: int, pass_index=None):
    """One decode step's grouped-query attention of every sequence over
    its own slab.  ``q``: ``(b, heads * d)``; ``k_new``, ``v_new``:
    ``(b, kv_heads * d)``; ``ck``, ``cv``: ``(b, rows, kv_heads * d)``
    slabs, or ``(b, passes, rows, kv_heads * d)`` with ``pass_index``
    (an int32 scalar) saying which pass's rows this step reads and
    writes (a layer run several times a token keeps a cache a pass);
    ``pos``: ``(b,)`` positions of the new token.  The slab's
    length says what it holds: position p lives in row ``p mod rows``,
    so a slab as long as the sequence may grow holds every position, and
    a slab of ``window`` rows is a ring that holds exactly the window
    once it is full (keys are cached with their rotary positions
    applied, so a row needs no position of its own).  Writes the new row
    and attends to the ``min(pos + 1, rows)`` live rows.  Returns
    ``(o (b, heads * d), ck, cv)``.

    On a TPU, for a slab ``_decode_gqa_plan`` admits (heads of 128, 64
    or 32), the pallas kernel ``zoo_decode_attn_gqa`` over the live row
    blocks only, a 128-lane tile of cached heads at a time; otherwise the
    masked full-length softmax, which reads every row of every slab."""
    b, rows, width = ck.shape[0], ck.shape[-2], ck.shape[-1]
    pos = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
    block = None
    if _on_tpu():
        block = _decode_gqa_plan(rows, n_heads, n_kv_heads,
                                 width // n_kv_heads, ck.dtype)[0]
    if block is None:
        return _decode_attention_gqa_reference(q, k_new, v_new, ck, cv, pos,
                                               n_heads, n_kv_heads,
                                               pass_index)
    return _decode_gqa_call(q, k_new, v_new, ck, cv, pos, n_heads=n_heads,
                            n_kv_heads=n_kv_heads, block=block,
                            interpret=False, pass_index=pass_index)


@jax.named_scope(_profile.SCOPE_ATTN_PROJ)
def scale_queries(q, scale: float):
    """Queries ``(..., d)`` for a softmax scaled by ``scale`` where the
    attention ops scale by ``1 / sqrt(d)``: ``q * scale * sqrt(d)`` in
    float32, back in ``q``'s dtype (exact where that factor is a power of
    two, as ``attention_multiplier`` 1/64 over heads of 64 gives 1/8)."""
    factor = float(scale) * math.sqrt(q.shape[-1])
    return (q.astype(jnp.float32) * factor).astype(q.dtype)
