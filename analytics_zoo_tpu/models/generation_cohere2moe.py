"""The ``cohere2_moe`` family's generation functions (CommandAPlusLM):
what the decode engine asks a family for through
``generation.family_of`` (embed / prefill / decode step / head, and how
its cache is laid out), registered under the family's name when this
module is imported (``commandaplus.py`` imports it).

The layer, float32 where not said otherwise (``commandaplus.py`` builds
the same from keras layers; ``benchmark/reference/cohere2moe.py`` is the
plain reference):

    h = LN(x)                                  one norm feeds both branches
    a = Wo attention(rope(h Wq), rope(h Wk), h Wv)
    m = sum_{e in top-k, held} g_e E_e(h) + mean_j S_j(h)     (ops/moe.py)
    x' = x + a + m                             parallel block

``sliding_attention`` layers turn q and k by rotary positions and see
the last ``sliding_window`` keys; ``full_attention`` layers have no
positions at all and see every earlier key.  Weights and cache are
stored in the weights' dtype (bfloat16 when served as published),
products take that dtype with float32 accumulation.

**The cache has two kinds of slab.**  A full layer's slab has
``max_len`` rows, position p in row p.  A sliding layer's slab has
``min(sliding_window, max_len)`` rows used as a RING: position p lives
in row ``p mod rows``, so after the write at ``pos`` the live rows are
the first ``min(pos + 1, rows)`` and, once the ring is full, all of
them are exactly the window.  Keys are cached with their rotary
positions applied, so a row needs no position of its own
(``ops.attention.decode_attention_gqa``).
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import profile as _profile
from ..ops.attention import (attention_gqa_bhsd, decode_attention_gqa,
                             decode_gqa_read_block, gqa_qkv, kv_insert,
                             kv_rows)
from ..ops.moe import moe_sublayer
from .generation import register_family, slab_state_shapes

NAME = "cohere2_moe"


def layer_kinds(hyper):
    """``"sliding_attention"`` / ``"full_attention"`` of each layer."""
    return list(hyper["layer_types"])[:int(hyper["n_layers"])]


@jax.named_scope(_profile.SCOPE_NORM)
def _norm(p, x, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"].astype(jnp.float32)


def _attn_cfg(hyper, kind):
    """``(rope_theta, window)`` of a layer kind."""
    if kind == "sliding_attention":
        return float(hyper["rope_theta"]), int(hyper["sliding_window"])
    return None, None


def _moe(hyper, p, h):
    return moe_sublayer(p, h, int(hyper["top_k"]),
                        tuple(hyper["experts_held"]))


@jax.named_scope(_profile.SCOPE_EMBED)
def embed(params, tok, pos):
    """Token embedding of one decode step (no positional table: the
    positions enter in the sliding layers' rotary turn)."""
    return jnp.take(params["tok_embed"]["embeddings"],
                    tok.astype(jnp.int32), axis=0).astype(jnp.float32)


@jax.named_scope(_profile.SCOPE_HEAD)
def head(params, hyper, hidden):
    """Final norm + the tied head over ``(b, d)`` hidden states:
    ``LN_f(x) Emb^T * logit_scale``, float32 logits."""
    x = _norm(params["ln_final"], hidden, hyper["layer_norm_eps"])
    table = params["tok_embed"]["embeddings"]
    return jnp.einsum("be,ve->bv", x.astype(table.dtype), table,
                      preferred_element_type=jnp.float32) \
        * float(hyper.get("logit_scale", 1.0))


def prefill(params, hyper, prompt, cache_len, length=None):
    """Batched prompt pass ``(b, s)`` ids -> ``(x (b, s, d), caches)``:
    every layer's keys and values of the prompt as slab rows ``(b, s,
    kv_heads * d_head)`` in the weights' dtype, NOT padded: ``insert``
    lays them into the slabs by layer kind (and takes ``length`` there)."""
    del cache_len, length
    b, s = prompt.shape
    eps = hyper["layer_norm_eps"]
    x = embed(params, prompt, None)
    caches = []
    for i, kind in enumerate(layer_kinds(hyper)):
        theta, window = _attn_cfg(hyper, kind)
        ap = params[f"attn_{i}"]
        h = _norm(params[f"ln_{i}"], x, eps)
        with jax.named_scope(_profile.SCOPE_ATTN_PROJ):     # and positions
            q, k, v = gqa_qkv(ap, h, jnp.arange(s), theta)
        o = attention_gqa_bhsd(q, k, v, window=window)
        with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
            a = jnp.einsum("bhsd,hde->bse", o, ap["Wo"],
                           preferred_element_type=jnp.float32)
        with jax.named_scope(_profile.SCOPE_NORM):
            h = h.reshape(b * s, -1)
        m, _ = _moe(hyper, params[f"moe_{i}"], h)
        with jax.named_scope(_profile.SCOPE_NORM):
            x = x + a + m.reshape(x.shape)
        caches.append((kv_rows(k), kv_rows(v)))
    return x, caches


def decode_step(params, hyper, caches, x_tok, pos, mesh=None):
    """One cached decode step over ``(b, d)`` token embeddings at ``(b,)``
    positions.  Returns ``(logits, caches, chosen)``; ``chosen (b,
    layers, top_k)`` int32 are the experts each row's token was routed
    to in each layer, which ride the engine's token fetch and feed its
    ``moe_*`` counters."""
    del mesh
    eps = hyper["layer_norm_eps"]
    n_heads, n_kv = int(hyper["n_heads"]), int(hyper["n_kv_heads"])
    x = x_tok
    pos = jnp.broadcast_to(pos, x.shape[:1])
    new_caches, chosen = [], []
    for i, kind in enumerate(layer_kinds(hyper)):
        theta, _ = _attn_cfg(hyper, kind)
        ap = params[f"attn_{i}"]
        ck, cv = caches[i]
        h = _norm(params[f"ln_{i}"], x, eps)
        with jax.named_scope(_profile.SCOPE_DECODE_ATTENTION):
            q, k, v = gqa_qkv(ap, h[:, None, :], pos[:, None], theta)
            o, ck, cv = decode_attention_gqa(
                q.reshape(q.shape[0], -1), k.reshape(k.shape[0], -1),
                v.reshape(v.shape[0], -1), ck, cv, pos, n_heads, n_kv)
            wo = ap["Wo"]
            with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                a = jnp.dot(o.astype(wo.dtype),
                            wo.reshape(-1, wo.shape[-1]),
                            preferred_element_type=jnp.float32)
        m, top_i = _moe(hyper, params[f"moe_{i}"], h)
        with jax.named_scope(_profile.SCOPE_NORM):
            x = x + a + m
        new_caches.append((ck, cv))
        chosen.append(top_i)
    return head(params, hyper, x), new_caches, jnp.stack(chosen, axis=1)


# ------------------------------------------------------- the cache layout
def slab_dims(hyper, capacity, max_len):
    """``(capacity, rows, kv_heads, d_head)`` of each layer's slabs."""
    ring = min(int(hyper["sliding_window"]), max_len)
    return [(capacity, ring if kind == "sliding_attention" else max_len,
             int(hyper["n_kv_heads"]), int(hyper["head_dim"]))
            for kind in layer_kinds(hyper)]


def slab_dtype(params):
    return params["tok_embed"]["embeddings"].dtype


def _ring_rows(rows, ring: int, length):
    """The last ``ring`` positions of a prompt's ``rows (1, s, w)`` (s >
    ring) laid out as the ring holds them: position p in row ``p mod
    ring``.  Positions ``[length - ring, length)`` where the prompt is
    that long, else ``[0, ring)``, whose rows past ``length`` are not
    live until a step writes them."""
    start = jnp.maximum(length - ring, 0)
    part = lax.dynamic_slice_in_dim(rows, start, ring, axis=1)
    return jnp.roll(part, start % ring, axis=1)


@jax.named_scope(_profile.SCOPE_INSERT)
def insert(hyper, caches, prompt_caches, slot, length):
    """A prefilled prompt's keys and values into slot ``slot`` of every
    layer's slabs.  A slab at least as long as the prompt bucket takes
    the rows as they are; a shorter ring takes the last ``rows``
    positions before ``length`` (``_ring_rows``)."""
    out = []
    for (ck, cv), (pk, pv) in zip(caches, prompt_caches):
        ring = ck.shape[1]
        if pk.shape[1] > ring:
            pk, pv = (_ring_rows(r, ring, length) for r in (pk, pv))
        out.append((kv_insert(ck, pk, slot), kv_insert(cv, pv, slot)))
    return out


def kv_kinds(hyper, capacity, max_len, dtype):
    """What the engine's ``kv_positions_*`` counters count by: ``(rows,
    read block, layers)`` of each kind of slab."""
    kinds = {}
    for _, rows, n_kv, d in slab_dims(hyper, capacity, max_len):
        kinds[rows] = kinds.get(rows, 0) + 1
    return [(rows, decode_gqa_read_block(rows, int(hyper["n_heads"]),
                                         int(hyper["n_kv_heads"]),
                                         int(hyper["head_dim"]), dtype), n)
            for rows, n in sorted(kinds.items())]


FAMILY = register_family(SimpleNamespace(
    name=NAME, embed=embed, prefill=prefill, decode_step=decode_step,
    head=head, state_shapes=slab_state_shapes(slab_dims),
    slab_dtype=slab_dtype, insert=insert, kv_kinds=kv_kinds,
    #: the step hands back the chosen experts beside the tokens
    routed=True,
    #: what the engine cannot do for this family yet
    refuses=("prefix_pool", "draft", "mesh")))
