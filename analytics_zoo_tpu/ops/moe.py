"""Top-k, dropless mixture of experts over the experts HELD here.

One implementation for the keras layer (``layers.TopKMoE``), the prefill
and the decode step of the ``cohere2_moe`` family (``models/
generation_cohere2moe.py``).  The layer routes over ALL of the model's
experts (the router keeps its published width) and is told which of
them it holds, ``experts_held = (first, count)``: it computes

    sum over e in top-k(token), first <= e < first + count of g_e E_e(h)

with ``g`` normalised over the whole top-k, i.e. one chip's part of an
expert-parallel layer's result; with ``(0, n_experts)`` it is the whole
layer.  What the absent experts would add is left out: no code stands
in for the other chips or their exchange.

    s   = sigmoid(h Wr)            float32, the product at ``highest``:
                                   routing must not flip on rounding
                                   more than it has to
    I   = top-k of s,  g_e = s_e / sum_{e' in I} s_e'
    E(h) = (silu(h Wgate) * (h Wup)) Wdown

No ``(tokens, experts, capacity)`` tensor and no capacity: every routed
pair whose expert is held is computed.  Two plans, chosen from the
shapes alone (``_DENSE_ROWS``):

* few tokens (a decode step): every held expert over all tokens, the
  results weighted by ``g`` (0 where not chosen).  At 3 tokens an expert
  the step is bound by the experts' bytes either way, and this plan has
  no sort, gather or scatter in it (measured at 48 tokens: 1.14 ms a
  layer against the sorted plan's 1.46).
* many tokens (a prefill): the routed pairs sorted by expert, those of
  absent experts last, and walked in chunks of ``_CHUNK_ROWS`` rows by
  a loop that runs as many chunks as hold a held pair; each chunk is
  three grouped products (``lax.ragged_dot``) over the held experts
  and a scatter-add.  The work follows what was routed here (about
  ``tokens * k * count / n_experts`` rows), not the worst case.

Matrix products take bfloat16 operands where the weights are bfloat16
and accumulate in float32; scores, gates and the sum over experts are
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import profile as _profile

#: up to this many tokens every held expert runs over all of them.
#: Measured on a v5e, one layer of the published widths, 8 held of 128
#: experts, top-8 (PERF.md section 6, PR 36), dense / sorted in ms: 48
#: tokens 1.14 / 1.46 (the weights' bytes need 0.98), 256 tokens 1.35 /
#: 3.03, 512 tokens 2.41 / 3.60.  The dense plan is bound by the
#: weights' bytes up to the chip's ridge (2 T flops for each 2-byte
#: weight: T = 240) and does n_experts / top_k = 16 x the sorted plan's
#: flops beyond it; the sorted plan pays its sort, gather, scatter and a
#: whole chunk of rows whatever it holds, about 3 ms, so it wins only
#: from several hundred tokens on (a 5k prefill: about 4 ms against 20)
_DENSE_ROWS = 512
#: routed pairs a step of the sorted plan's loop takes
_CHUNK_ROWS = 4096


def route(h, w_router, top_k: int):
    """``(top_i (T, k) int32, g (T, k) float32)``: the experts a token
    chose among ALL the router's outputs and their gates, normalised
    over the k chosen.  Equal scores go to the lower index
    (``lax.top_k``)."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               w_router.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    top_s, top_i = lax.top_k(s, top_k)
    return top_i.astype(jnp.int32), top_s / jnp.sum(top_s, axis=-1,
                                                     keepdims=True)


def held_gates(top_i, g, experts_held):
    """``(T, count)`` float32: a token's gate for each held expert, 0
    where it did not choose it."""
    first, count = experts_held
    hit = (top_i[..., None] - first) == jnp.arange(count)
    return jnp.sum(jnp.where(hit, g[..., None], 0.0), axis=1)


def _mm(spec, a, w):
    return jnp.einsum(spec, a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def experts_all(h, w_gate, w_up, w_down):
    """Every expert of a stack ``(E, d, f)`` / ``(E, f, d)`` over all
    tokens: ``(E, T, d)`` float32."""
    act = jax.nn.silu(_mm("td,edf->etf", h, w_gate)) \
        * _mm("td,edf->etf", h, w_up)
    return _mm("etf,efd->etd", act, w_down)


def _experts_dense(h, top_i, g, experts_held, w_gate, w_up, w_down):
    y = experts_all(h, w_gate, w_up, w_down)
    return jnp.einsum("te,etd->td", held_gates(top_i, g, experts_held), y)


def _experts_sorted(h, top_i, g, experts_held, w_gate, w_up, w_down):
    first, count = experts_held
    t, k = top_i.shape
    rows = t * k
    chunk = min(_CHUNK_ROWS, rows)
    local = (top_i - first).reshape(rows)
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    n_held = jnp.sum(held.astype(jnp.int32))
    pad = (0, -rows % chunk)        # so that every chunk is whole
    key_sorted = jnp.pad(key[order], pad, constant_values=count)
    gate_sorted = jnp.pad(jnp.where(held, g.reshape(rows), 0.0)[order], pad)
    order = jnp.pad(order, pad)
    hb = h.astype(w_gate.dtype)

    def body(i, out):
        at = i * chunk
        rows_i = lax.dynamic_slice(order, (at,), (chunk,))
        key_i = lax.dynamic_slice(key_sorted, (at,), (chunk,))
        gate_i = lax.dynamic_slice(gate_sorted, (at,), (chunk,))
        tok = rows_i // k
        sizes = jnp.sum(key_i[:, None] == jnp.arange(count)[None, :],
                        axis=0).astype(jnp.int32)
        xs = hb[tok]

        def gdot(a, w):
            return lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

        act = jax.nn.silu(gdot(xs, w_gate)) * gdot(xs, w_up)
        y = gdot(act.astype(w_down.dtype), w_down)
        # rows past the held pairs belong to no group: whatever the
        # grouped product left there is selected out, not multiplied
        y = jnp.where((key_i < count)[:, None], y * gate_i[:, None], 0.0)
        return out.at[tok].add(y)

    return lax.fori_loop(0, -(-n_held // chunk), body,
                         jnp.zeros((t, h.shape[-1]), jnp.float32))


def moe_experts(h, top_i, g, experts_held, w_gate, w_up, w_down):
    """The held experts' part of the routed sum, ``(T, d)`` float32.
    ``w_gate``, ``w_up``: ``(count, d, f)``; ``w_down``: ``(count, f,
    d)``: the stacked weights of experts ``first .. first + count``."""
    plan = _experts_dense if h.shape[0] <= _DENSE_ROWS else _experts_sorted
    return plan(h, top_i, g, experts_held, w_gate, w_up, w_down)


def shared_mean(h, w_gate, w_up, w_down):
    """The mean of the shared experts' outputs, ``(T, d)`` float32."""
    return jnp.mean(experts_all(h, w_gate, w_up, w_down), axis=0)


def moe_sublayer(p, h, top_k: int, experts_held):
    """``m = sum_{e in I, held} g_e E_e(h) + mean_j S_j(h)`` of ``h
    (T, d)`` (already normalised), float32, and the experts each token
    chose ``(T, k)`` (what the engine's counters count from).  ``p``:
    ``router (d, n_experts)``, ``w_gate`` / ``w_up`` / ``w_down`` (the
    held experts, stacked), ``s_gate`` / ``s_up`` / ``s_down`` (the
    shared ones, where the layer has any)."""
    with jax.named_scope(_profile.SCOPE_MOE):
        with jax.named_scope(_profile.SCOPE_MOE_ROUTER):
            top_i, g = route(h, p["router"], top_k)
        with jax.named_scope(_profile.SCOPE_MOE_EXPERTS):
            m = moe_experts(h, top_i, g, experts_held, p["w_gate"],
                            p["w_up"], p["w_down"])
        if "s_gate" in p:
            with jax.named_scope(_profile.SCOPE_MOE_SHARED):
                m = m + shared_mean(h, p["s_gate"], p["s_up"], p["s_down"])
    return m, top_i
