"""The ``ouro`` family's generation functions (OuroLM): what the decode
engine asks a family for through ``generation.family_of``, registered
under the family's name when this module is imported (``ouro.py``
imports it).

A looped decoder: ONE stack of layers, its weights shared, runs
``total_ut_steps`` times a token (``ouro.py`` builds the same from keras
layers; ``benchmark/reference/ouro.py`` is the plain reference):

    x = Emb[tok]
    for t in 1..passes:                  the same layers each pass
      for each layer:
        x = x + RMSNorm(Attn(RMSNorm(x); cache[t]))     sandwich norms
        x = x + RMSNorm(MLP(RMSNorm(x)))                SwiGLU
      x = RMSNorm_f(x)                   closes every pass, feeds the next
    logits = x W_head                    untied head, on the last pass

Attention is grouped-query with rotary positions by rotate-half
(``ops.attention.rope_half``).  At pass t a layer reads and writes ITS
OWN keys and values, those the same layer made at pass t for earlier
positions: a layer's slabs are ``(capacity, passes, rows, kv_heads *
d_head)``, the pass inside a slot, and a position holds ``passes x
layers`` key/value rows.

**One pass program, looped.**  The step and the prefill hold the layers
once, in the body of a ``lax.fori_loop`` over the passes, whose index
picks that pass's part of each layer's slabs: the decode kernel
(``decode_attention_gqa(..., pass_index=t)``) reads that pass's live
rows and writes its new row in place, and the prefill lays each pass's
rows into its part of the prompt's rows.  Weights, slabs and products as
the other served families: stored in the weights' dtype, products in it
with float32 accumulation, norms, softmax and the residual stream in
float32.

``early_exit_threshold`` 1 (as published) runs every pass for every
token; the exit gate's weights are held and not evaluated.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import profile as _profile
from ..ops.attention import (attention_gqa_bhsd, decode_attention_gqa,
                             decode_gqa_read_block, gqa_qkv, kv_insert,
                             kv_rows, kv_slab_shape)
from ..pipeline.api.keras.layers.normalization import rms_norm
from ..pipeline.api.keras.layers.ssm import gated_mlp
from .generation import _residual, register_family

NAME = "ouro"
#: how the layers pair the dimensions their rotary positions turn
ROPE = "half"


def passes(hyper):
    """How many times a token runs the stack."""
    return int(hyper["total_ut_steps"])


def _attn_in(params, hyper, i, x):
    """The norm before layer ``i``'s attention."""
    return rms_norm(params[f"ln_attn_{i}"]["gamma"], x, hyper["rms_norm_eps"])


def _after_attn(params, hyper, i, x, a):
    """Layer ``i`` past its attention's output ``a``: the norm after it
    and the residual, then the MLP between its two norms."""
    eps = hyper["rms_norm_eps"]
    x = _residual(x, rms_norm(params[f"ln_attn_out_{i}"]["gamma"], a, eps))
    m = gated_mlp(params[f"mlp_{i}"],
                  rms_norm(params[f"ln_mlp_{i}"]["gamma"], x, eps))
    return _residual(x, rms_norm(params[f"ln_mlp_out_{i}"]["gamma"], m, eps))


def _close_pass(params, hyper, x):
    """The final norm, which closes every pass and feeds the next."""
    return rms_norm(params["ln_final"]["gamma"], x, hyper["rms_norm_eps"])


@jax.named_scope(_profile.SCOPE_EMBED)
def embed(params, tok, pos):
    """The table's rows of one decode step's tokens (no positions: they
    enter in the rotary turn)."""
    return jnp.take(params["tok_embed"]["embeddings"],
                    tok.astype(jnp.int32), axis=0).astype(jnp.float32)


@jax.named_scope(_profile.SCOPE_HEAD)
def head(params, hyper, hidden):
    """The untied head over ``(b, d)`` hidden states of the last pass,
    which its final norm has already closed: float32 logits."""
    w = params["lm_head"]["W"]
    return jnp.einsum("be,ev->bv", hidden.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def prefill(params, hyper, prompt, cache_len, length=None):
    """Batched prompt pass ``(b, s)`` ids -> ``(x (b, s, d), rows)``: the
    last pass's closed hidden states, and each layer's keys and values
    of every pass as slab rows ``(b, passes, s, kv_heads * d_head)`` in
    the weights' dtype, which ``insert`` lays into a slot.  Causal, so
    the padding past ``length`` reaches no row before it."""
    del cache_len, length
    b, s = prompt.shape
    theta, n = float(hyper["rope_theta"]), int(hyper["n_layers"])
    width = int(hyper["n_kv_heads"]) * int(hyper["head_dim"])
    dtype = slab_dtype(params)
    with jax.named_scope(_profile.SCOPE_EMBED):
        x = jnp.take(params["tok_embed"]["embeddings"],
                     prompt.astype(jnp.int32), axis=0).astype(jnp.float32)
    at = jnp.arange(s)

    def one_pass(t, carry):
        x, rows = carry
        out = []
        for i in range(n):
            ap = params[f"attn_{i}"]
            q, k, v = gqa_qkv(ap, _attn_in(params, hyper, i, x), at, theta,
                              ROPE)
            o = attention_gqa_bhsd(q, k, v)
            with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                a = jnp.einsum("bhsd,hde->bse", o, ap["Wo"],
                               preferred_element_type=jnp.float32)
            x = _after_attn(params, hyper, i, x, a)
            with jax.named_scope(_profile.SCOPE_INSERT):
                out.append(tuple(
                    lax.dynamic_update_index_in_dim(r, kv_rows(new), t, 1)
                    for r, new in zip(rows[i], (k, v))))
        return _close_pass(params, hyper, x), out

    empty = [(jnp.zeros((b, passes(hyper), s, width), dtype),) * 2
             for _ in range(n)]
    return lax.fori_loop(0, passes(hyper), one_pass, (x, empty))


def decode_step(params, hyper, caches, x_tok, pos, mesh=None):
    """One cached decode step over ``(b, d)`` token rows (``embed``'s) at
    ``(b,)`` positions, every pass of it.  Returns ``(logits, caches)``."""
    del mesh
    theta, n = float(hyper["rope_theta"]), int(hyper["n_layers"])
    n_heads, n_kv = int(hyper["n_heads"]), int(hyper["n_kv_heads"])
    b = x_tok.shape[0]
    pos = jnp.broadcast_to(pos, (b,))

    def one_pass(t, carry):
        x, caches = carry
        out = []
        for i in range(n):
            ap, (ck, cv) = params[f"attn_{i}"], caches[i]
            h = _attn_in(params, hyper, i, x)
            with jax.named_scope(_profile.SCOPE_DECODE_ATTENTION):
                q, k, v = gqa_qkv(ap, h[:, None, :], pos[:, None], theta,
                                  ROPE)
                o, ck, cv = decode_attention_gqa(
                    q.reshape(b, -1), k.reshape(b, -1), v.reshape(b, -1),
                    ck, cv, pos, n_heads, n_kv, pass_index=t)
                wo = ap["Wo"]
                with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                    a = jnp.dot(o.astype(wo.dtype),
                                wo.reshape(-1, wo.shape[-1]),
                                preferred_element_type=jnp.float32)
            x = _after_attn(params, hyper, i, x, a)
            out.append((ck, cv))
        return _close_pass(params, hyper, x), out

    x, caches = lax.fori_loop(0, passes(hyper), one_pass,
                              (x_tok, list(caches)))
    return head(params, hyper, x), caches


# ---------------------------------------------------- the per-slot state
def state_shapes(hyper, capacity, max_len, dtype):
    """Each layer's key and value slabs, a pass inside a slot:
    ``(capacity, passes, max_len, kv_heads * d_head)``."""
    slab = ((capacity, passes(hyper)) + kv_slab_shape(
        capacity, max_len, int(hyper["n_kv_heads"]),
        int(hyper["head_dim"]))[1:], dtype)
    return [(slab, slab)] * int(hyper["n_layers"])


def slab_dtype(params):
    return params["tok_embed"]["embeddings"].dtype


@jax.named_scope(_profile.SCOPE_INSERT)
def insert(hyper, caches, prompt_rows, slot, length):
    """A prefilled prompt's rows of every pass into slot ``slot`` (rows
    past ``length`` are not live until a step writes them)."""
    return [(kv_insert(ck, pk, slot), kv_insert(cv, pv, slot))
            for (ck, cv), (pk, pv) in zip(caches, prompt_rows)]


def kv_kinds(hyper, capacity, max_len, dtype):
    """What the engine's ``kv_positions_*`` counters count by: one kind
    of slab, ``passes x layers`` of it (each pass reads its own)."""
    return [(max_len, decode_gqa_read_block(
        max_len, int(hyper["n_heads"]), int(hyper["n_kv_heads"]),
        int(hyper["head_dim"]), dtype),
        passes(hyper) * int(hyper["n_layers"]))]


FAMILY = register_family(SimpleNamespace(
    name=NAME, embed=embed, prefill=prefill, decode_step=decode_step,
    head=head, state_shapes=state_shapes, slab_dtype=slab_dtype,
    insert=insert, kv_kinds=kv_kinds, passes=passes,
    routed=False,
    #: what the engine cannot do for this family yet: a prefix block or
    #: a draft would need every pass's rows, slot sharding a rule for the
    #: pass axis
    refuses=("prefix_pool", "draft", "mesh")))
