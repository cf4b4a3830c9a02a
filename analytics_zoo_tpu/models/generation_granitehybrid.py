"""The ``granitemoehybrid`` family's generation functions
(GraniteHybridLM): what the decode engine asks a family for through
``generation.family_of``, registered under the family's name when this
module is imported (``granitehybrid.py`` imports it).

The layer (``granitehybrid.py`` builds the same from keras layers;
``benchmark/reference/granitehybrid.py`` is the plain reference):

    x = x + m * Mixer(RMSNorm(x))     Mamba-2 (ops/ssm.py), or attention
    x = x + m * MLP(RMSNorm(x))       without positions, softmax scale
                                      ``attention_multiplier``

**A slot's state is of two kinds.**  An attention layer holds its
``(keys, values)`` slabs of ``max_len`` rows in the weights' dtype, as
the other families' full layers do.  A Mamba layer holds a fixed-size
RECURRENT state: the convolution's last ``d_conv - 1`` inputs (in the
weights' dtype) and the float32 state ``(heads, head_dim, d_state)``.
A step overwrites it instead of appending a row, so an admission lays it
down at the prompt's OWN length (the scan's ``dt`` is 0 past it, the
window is cut there) and overwrites the slot's state whole: a free slot
keeps stepping on tokens nobody reads, so what it holds is garbage."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import profile as _profile
from ..ops.attention import (attention_gqa_bhsd, decode_attention_gqa,
                             decode_gqa_read_block, gqa_qkv, kv_insert,
                             kv_rows, kv_slab_shape, scale_queries)
from ..ops.ssm import mamba2_mixer, mamba2_mixer_step
from ..pipeline.api.keras.layers.normalization import rms_norm
from ..pipeline.api.keras.layers.ssm import gated_mlp
from .generation import register_family

NAME = "granitemoehybrid"


def layer_kinds(hyper):
    """``"mamba"`` / ``"attention"`` of each layer."""
    return list(hyper["layer_types"])[:int(hyper["n_layers"])]


def _mlp(params, hyper, i, x):
    h = rms_norm(params[f"ln_mlp_{i}"]["gamma"], x, hyper["rms_norm_eps"])
    m = gated_mlp(params[f"mlp_{i}"], h)
    with jax.named_scope(_profile.SCOPE_NORM):
        return x + hyper["residual_multiplier"] * m


@jax.named_scope(_profile.SCOPE_EMBED)
def embed(params, tok, pos):
    """The table's rows of one decode step's tokens; ``decode_step``
    multiplies them by ``embedding_multiplier`` (no positions)."""
    return jnp.take(params["tok_embed"]["embeddings"],
                    tok.astype(jnp.int32), axis=0).astype(jnp.float32)


@jax.named_scope(_profile.SCOPE_HEAD)
def head(params, hyper, hidden):
    """Final norm + the tied head over ``(b, d)`` hidden states:
    ``RMSNorm_f(x) Emb^T / logits_scaling``, float32 logits."""
    x = rms_norm(params["ln_final"]["gamma"], hidden, hyper["rms_norm_eps"])
    table = params["tok_embed"]["embeddings"]
    return jnp.einsum("be,ve->bv", x.astype(table.dtype), table,
                      preferred_element_type=jnp.float32) \
        * (1.0 / hyper["logits_scaling"])


def prefill(params, hyper, prompt, cache_len, length=None):
    """Batched prompt pass ``(b, s)`` ids -> ``(x (b, s, d), states)``: an
    attention layer's keys and values as slab rows ``(b, s, kv_heads *
    d_head)``, a Mamba layer's convolution window and float32 state AT
    ``length`` (at ``s`` where not given): ``insert`` lays them into a
    slot."""
    del cache_len
    s = prompt.shape[1]
    eps, res = hyper["rms_norm_eps"], hyper["residual_multiplier"]
    with jax.named_scope(_profile.SCOPE_EMBED):
        x = jnp.take(params["tok_embed"]["embeddings"],
                     prompt.astype(jnp.int32), axis=0).astype(jnp.float32) \
            * hyper["embedding_multiplier"]
    states = []
    for i, kind in enumerate(layer_kinds(hyper)):
        h = rms_norm(params[f"ln_{i}"]["gamma"], x, eps)
        if kind == "mamba":
            a, state = mamba2_mixer(params[f"mamba_{i}"], h, eps,
                                    int(hyper["mamba_chunk"]), length)
        else:
            ap = params[f"attn_{i}"]
            q, k, v = gqa_qkv(ap, h, jnp.arange(s))
            o = attention_gqa_bhsd(
                scale_queries(q, hyper["attention_multiplier"]), k, v)
            with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                a = jnp.einsum("bhsd,hde->bse", o, ap["Wo"],
                               preferred_element_type=jnp.float32)
            state = (kv_rows(k), kv_rows(v))
        with jax.named_scope(_profile.SCOPE_NORM):
            x = x + res * a
        x = _mlp(params, hyper, i, x)
        states.append(state)
    return x, states


def decode_step(params, hyper, caches, x_tok, pos, mesh=None):
    """One cached decode step over ``(b, d)`` token rows (``embed``'s) at
    ``(b,)`` positions.  Returns ``(logits, states)``."""
    del mesh
    eps, res = hyper["rms_norm_eps"], hyper["residual_multiplier"]
    n_heads, n_kv = int(hyper["n_heads"]), int(hyper["n_kv_heads"])
    with jax.named_scope(_profile.SCOPE_EMBED):
        x = x_tok * hyper["embedding_multiplier"]
    pos = jnp.broadcast_to(pos, x.shape[:1])
    new = []
    for i, kind in enumerate(layer_kinds(hyper)):
        h = rms_norm(params[f"ln_{i}"]["gamma"], x, eps)
        if kind == "mamba":
            window, state = caches[i]
            a, window, state = mamba2_mixer_step(params[f"mamba_{i}"], h,
                                                 window, state, eps)
            new.append((window, state))
        else:
            ap, (ck, cv) = params[f"attn_{i}"], caches[i]
            with jax.named_scope(_profile.SCOPE_DECODE_ATTENTION):
                q, k, v = gqa_qkv(ap, h[:, None, :], pos[:, None])
                q = scale_queries(q, hyper["attention_multiplier"])
                o, ck, cv = decode_attention_gqa(
                    q.reshape(q.shape[0], -1), k.reshape(k.shape[0], -1),
                    v.reshape(v.shape[0], -1), ck, cv, pos, n_heads, n_kv)
                wo = ap["Wo"]
                with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                    a = jnp.dot(o.astype(wo.dtype),
                                wo.reshape(-1, wo.shape[-1]),
                                preferred_element_type=jnp.float32)
            new.append((ck, cv))
        with jax.named_scope(_profile.SCOPE_NORM):
            x = x + res * a
        x = _mlp(params, hyper, i, x)
    return head(params, hyper, x), new


# ---------------------------------------------------- the per-slot state
def _mamba_dims(hyper):
    heads, hd = int(hyper["mamba_n_heads"]), int(hyper["mamba_head_dim"])
    n = int(hyper["mamba_d_state"])
    return heads, hd, n, heads * hd + 2 * n, int(hyper["mamba_d_conv"])


def state_shapes(hyper, capacity, max_len, dtype):
    """``((shape, dtype), (shape, dtype))`` of each layer's state: an
    attention layer's key and value slabs, a Mamba layer's convolution
    window ``(capacity, d_conv - 1, conv_dim)`` and float32 state
    ``(capacity, heads, head_dim, d_state)``."""
    heads, hd, n, conv_dim, k = _mamba_dims(hyper)
    slab = (kv_slab_shape(capacity, max_len, int(hyper["n_kv_heads"]),
                          int(hyper["head_dim"])), dtype)
    mamba = (((capacity, k - 1, conv_dim), dtype),
             ((capacity, heads, hd, n), jnp.float32))
    return [mamba if kind == "mamba" else (slab, slab)
            for kind in layer_kinds(hyper)]


def slab_dtype(params):
    return params["tok_embed"]["embeddings"].dtype


@jax.named_scope(_profile.SCOPE_INSERT)
def insert(hyper, caches, prompt_states, slot, length):
    """A prefilled prompt's states into slot ``slot``: an attention
    layer's rows as they are (rows past ``length`` are not live until a
    step writes them); a Mamba layer's window and state, which the
    prefill took at ``length``, overwrite the slot's whole."""
    out = []
    for kind, (c0, c1), (p0, p1) in zip(layer_kinds(hyper), caches,
                                        prompt_states):
        if kind == "mamba":
            out.append(tuple(
                lax.dynamic_update_slice(c, p.astype(c.dtype),
                                         (slot,) + (0,) * (c.ndim - 1))
                for c, p in ((c0, p0), (c1, p1))))
        else:
            out.append((kv_insert(c0, p0, slot), kv_insert(c1, p1, slot)))
    return out


def kv_kinds(hyper, capacity, max_len, dtype):
    """What the engine's ``kv_positions_*`` counters count by: the
    attention layers' one kind of slab (the Mamba layers have none)."""
    return [(max_len, decode_gqa_read_block(
        max_len, int(hyper["n_heads"]), int(hyper["n_kv_heads"]),
        int(hyper["head_dim"]), dtype), layer_kinds(hyper).count("attention"))]


FAMILY = register_family(SimpleNamespace(
    name=NAME, embed=embed, prefill=prefill, decode_step=decode_step,
    head=head, state_shapes=state_shapes, slab_dtype=slab_dtype,
    insert=insert, kv_kinds=kv_kinds,
    routed=False,
    #: what the engine cannot do for this family yet: a prefix block or
    #: a draft would need the recurrent state at the block's end, slot
    #: sharding a rule for it
    refuses=("prefix_pool", "draft", "mesh")))
