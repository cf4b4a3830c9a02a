"""Autoregressive decoding with a static-shape KV cache (VERDICT r4 #3).

Every reference zoo family ships usable inference
(``ObjectDetector.predictImageSet``, ``Recommender.recommendForUser`` —
zoo/.../models/image/objectdetection/ObjectDetector.scala,
recommendation/Recommender.scala:36-86); the LM flagship's analogue is
``TransformerLM.generate``: prefill the prompt in ONE batched causal
forward (MXU-sized matmuls, the pallas path), then decode token-by-token
against per-layer K/V caches under one ``jit`` — a ``lax.scan`` over
steps with static shapes (cache length = prompt + max_new), so the whole
generation is a single compiled computation with no per-token dispatch.

The decode math mirrors ``TransformerLM.build_model`` exactly (pre-norm
blocks, gelu MLP or Switch-MoE sublayer, final LN + lm_head); the
prefix-consistency tests in ``tests/test_generate.py`` pin the two paths
together position-by-position.
"""

from __future__ import annotations

import math
from functools import partial
from types import SimpleNamespace
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..observability import profile as _profile
from ..ops.attention import (attention_bhsd, decode_attention,
                             decode_read_block, kv_heads, kv_insert, kv_pad,
                             kv_rows, kv_slab_shape, kv_write_row)
from ..parallel.expert import MoEParams, expert_capacity, switch_moe
from ..pipeline.api.keras.activations import get as get_activation

_gelu = get_activation("gelu")


def _block_params(params, i, moe):
    """Collect layer-i block params from the TransformerLM param tree."""
    bp = {"ln_a": params[f"ln_attn_{i}"], "attn": params[f"attn_{i}"],
          "ln_m": params[f"ln_mlp_{i}"]}
    if moe:
        bp["moe"] = params[f"moe_{i}"]
    else:
        bp["up"] = params[f"mlp_up_{i}"]
        bp["down"] = params[f"mlp_down_{i}"]
    return bp


@jax.named_scope(_profile.SCOPE_NORM)
def _layer_norm(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


@jax.named_scope(_profile.SCOPE_MLP)
def _mlp(bp, f):
    if "moe" in bp:
        d = f.shape[-1]
        flat = f.reshape(-1, d)
        p = MoEParams(**{k: bp["moe"][k] for k in MoEParams._fields})
        # decode runs DROP-FREE (capacity = token count): with a handful
        # of tokens per step, train-time capacity limits would silently
        # zero sublayer outputs and degrade generation for nothing — the
        # Switch recipe raises capacity at inference
        out, _ = switch_moe(flat, p, capacity=flat.shape[0])
        return out.reshape(f.shape)
    return _gelu(f @ bp["up"]["W"] + bp["up"]["b"]) @ bp["down"]["W"] \
        + bp["down"]["b"]


@jax.named_scope(_profile.SCOPE_HEAD)
def _head_logits(params, hidden):
    """Final LN + lm_head over a (b, d) hidden state — the one logits
    head both the sampling and beam builders share."""
    x = _layer_norm(params["ln_final"], hidden)
    return x @ params["lm_head"]["W"] + params["lm_head"]["b"]


@jax.named_scope(_profile.SCOPE_EMBED)
def _embed_token(params, tok, pos):
    """Token + positional embedding for one decode step (tok: (rows,)
    int ids; pos: scalar shared position, or (rows,) per-row positions
    for ragged prompts)."""
    emb = jnp.take(params["tok_embed"]["embeddings"],
                   tok.astype(jnp.int32), axis=0)
    table = params["pos_embed"]["table"]
    if jnp.ndim(pos) == 0:
        p = lax.dynamic_index_in_dim(table, pos, keepdims=False)
    else:
        p = jnp.take(table, pos, axis=0)  # (rows, d)
    return emb + p.astype(emb.dtype)


@jax.named_scope(_profile.SCOPE_NORM)
def _residual(x, y):
    """A sublayer's output added to the stream."""
    return x + y


def _prefill(params, hyper, prompt, cache_len, length=None):
    """Batched prompt pass: causal attention over the whole prompt in one
    forward (the training-shaped compute), writing each layer's K/V into
    position [0, s_p) of a (b, cache_len, heads * d) slab (the layout is
    ``ops.attention.kv_*``'s) and returning the last position's hidden
    state.  ``length`` (the prompt's own, inside the padded width) is not
    needed: causal attention keeps the padding out of every row before
    it."""
    n_layers, moe_every = hyper["n_layers"], hyper["moe_every"]
    s_p = prompt.shape[1]
    with jax.named_scope(_profile.SCOPE_EMBED):
        x = jnp.take(params["tok_embed"]["embeddings"],
                     prompt.astype(jnp.int32), axis=0)
        x = x + params["pos_embed"]["table"][:s_p].astype(
            x.dtype)
    caches = []
    for i in range(n_layers):
        moe = bool(moe_every) and (i + 1) % moe_every == 0
        bp = _block_params(params, i, moe)
        a = _layer_norm(bp["ln_a"], x)
        with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
            q = jnp.einsum("bse,ehd->bhsd", a, bp["attn"]["Wq"])
            k = jnp.einsum("bse,ehd->bhsd", a, bp["attn"]["Wk"])
            v = jnp.einsum("bse,ehd->bhsd", a, bp["attn"]["Wv"])
        o = attention_bhsd(q, k, v, causal=True)
        with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
            o = jnp.einsum("bhsd,hde->bse", o, bp["attn"]["Wo"])
        x = _residual(x, o)
        f = _layer_norm(bp["ln_m"], x)
        x = _residual(x, _mlp(bp, f))
        caches.append((kv_pad(kv_rows(k), cache_len),
                       kv_pad(kv_rows(v), cache_len)))
    return x, caches


@jax.named_scope(_profile.SCOPE_ATTN_PROJ)
def _rows_proj(a, w):
    """``a (..., e)`` through a ``(e, heads, d)`` projection, straight
    into slab rows ``(..., heads * d)``."""
    return a @ w.reshape(w.shape[0], -1)


def _decode_step(params, hyper, caches, x_tok, pos, mesh=None):
    """One cached decode step: ``x_tok`` is the (b, d_model) embedding of
    the current token (token + positional), ``pos`` its position —
    scalar, or (b,) per-row for ragged prompts.  ``mesh``: the mesh a
    sharded engine has laid the rows out on (``decode_attention``).
    Returns (logits, updated caches)."""
    n_layers, moe_every = hyper["n_layers"], hyper["moe_every"]
    n_heads = hyper["n_heads"]
    x = x_tok
    new_caches = []
    for i in range(n_layers):
        moe = bool(moe_every) and (i + 1) % moe_every == 0
        bp = _block_params(params, i, moe)
        ck, cv = caches[i]
        with jax.named_scope(_profile.SCOPE_DECODE_ATTENTION):
            a = _layer_norm(bp["ln_a"], x)
            o, ck, cv = decode_attention(
                _rows_proj(a, bp["attn"]["Wq"]),
                _rows_proj(a, bp["attn"]["Wk"]),
                _rows_proj(a, bp["attn"]["Wv"]), ck, cv, pos, n_heads,
                mesh=mesh)
            wo = bp["attn"]["Wo"]
            with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                o = o @ wo.reshape(-1, wo.shape[-1])
            x = _residual(x, o)
        f = _layer_norm(bp["ln_m"], x)
        x = _residual(x, _mlp(bp, f))
        new_caches.append((ck, cv))
    return _head_logits(params, x), new_caches


def _decode_window(params, hyper, caches, x_toks, pos):
    """k-query cached decode — the speculative-verify compute.

    ``x_toks`` is (b, k, d_model): the embeddings of k consecutive
    tokens per row, whose positions are ``pos + j`` (``pos``: (b,)).
    Each token's K/V is written at its OWN clamped position (per-entry
    ``min(pos + j, t - 1)``, never a block write — a block's clamp
    would SHIFT early entries and corrupt live cache lines), then all
    k queries attend in one batched einsum with a per-query causal
    mask.  Returns ((b, k, V) logits, updated caches).

    Numerics note: the k-query matmul shapes differ from
    :func:`_decode_step`'s single-query shapes, so logits agree with k
    sequential steps to ~1 ulp, not bit-for-bit — which is why the
    speculative plan derives each window's FIRST token from the exact
    single-query body and uses this window only to certify draft
    proposals (decode.py §speculative)."""
    n_layers, moe_every = hyper["n_layers"], hyper["moe_every"]
    n_heads = hyper["n_heads"]
    k = x_toks.shape[1]
    t = caches[0][0].shape[1]
    x = x_toks
    qpos = jnp.minimum(pos[:, None] + jnp.arange(k)[None, :], t - 1)
    new_caches = []
    for i in range(n_layers):
        moe = bool(moe_every) and (i + 1) % moe_every == 0
        bp = _block_params(params, i, moe)
        ck, cv = caches[i]
        with jax.named_scope(_profile.SCOPE_DECODE_ATTENTION):
            a = _layer_norm(bp["ln_a"], x)
            with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                q = jnp.einsum("bke,ehd->bhkd", a, bp["attn"]["Wq"])
            kk = _rows_proj(a, bp["attn"]["Wk"])
            vv = _rows_proj(a, bp["attn"]["Wv"])
            for j in range(k):
                ck = kv_write_row(ck, kk[:, j], qpos[:, j])
                cv = kv_write_row(cv, vv[:, j], qpos[:, j])
            d = q.shape[-1]
            scores = jnp.einsum("bhkd,bthd->bhkt", q,
                                kv_heads(ck, n_heads)) / math.sqrt(d)
            valid = (jnp.arange(t)[None, None, None, :]
                     <= qpos[:, None, :, None])
            scores = jnp.where(valid, scores, -1e30)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            o = jnp.einsum("bhkt,bthd->bhkd", probs.astype(cv.dtype),
                           kv_heads(cv, n_heads))
            with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
                o = jnp.einsum("bhkd,hde->bke", o, bp["attn"]["Wo"])
            x = _residual(x, o)
        f = _layer_norm(bp["ln_m"], x)
        x = _residual(x, _mlp(bp, f))
        new_caches.append((ck, cv))
    b = x.shape[0]
    logits = _head_logits(params, x.reshape(b * k, -1))
    return logits.reshape(b, k, -1), new_caches


def _prefill_ext(params, hyper, tail, prefix_kv, p_len: int):
    """Prefix-conditioned tail prefill — the prefix-KV-pool admit
    compute.  ``tail`` is (1, s_t) token ids occupying positions
    ``[p_len, p_len + s_t)``; ``prefix_kv`` the per-layer (k, v)
    blocks of the first ``p_len`` positions, each slab rows (1, p_len,
    heads * d_head) — pooled (a memcpy) or freshly computed by the same
    prefix-prefill plan (bit-identical either way, which is what makes
    pool hit vs miss streams indistinguishable).  Causal attention of
    the tail queries over prefix + tail in one batched forward.
    Returns (tail hidden states (1, s_t, d_model), per-layer tail
    (k, v) blocks, slab rows (1, s_t, heads * d_head))."""
    n_layers, moe_every = hyper["n_layers"], hyper["moe_every"]
    n_heads = hyper["n_heads"]
    s_t = tail.shape[1]
    with jax.named_scope(_profile.SCOPE_EMBED):
        x = jnp.take(params["tok_embed"]["embeddings"],
                     tail.astype(jnp.int32), axis=0)
        x = x + params["pos_embed"]["table"][p_len:p_len + s_t].astype(
            x.dtype)
    tail_caches = []
    # tail query j (position p_len + j) sees the whole prefix plus
    # tail positions <= j
    causal = (jnp.arange(s_t)[None, None, :, None]
              >= jnp.arange(s_t)[None, None, None, :])
    for i in range(n_layers):
        moe = bool(moe_every) and (i + 1) % moe_every == 0
        bp = _block_params(params, i, moe)
        pk, pv = (kv_heads(c, n_heads) for c in prefix_kv[i])
        a = _layer_norm(bp["ln_a"], x)
        with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
            q = jnp.einsum("bse,ehd->bhsd", a, bp["attn"]["Wq"])
            k = jnp.einsum("bse,ehd->bhsd", a, bp["attn"]["Wk"])
            v = jnp.einsum("bse,ehd->bhsd", a, bp["attn"]["Wv"])
        with jax.named_scope(_profile.SCOPE_ATTN_CORE):
            d = q.shape[-1]
            sp = jnp.einsum("bhsd,bthd->bhst", q, pk) / math.sqrt(d)
            st = jnp.einsum("bhsd,bhtd->bhst", q, k) / math.sqrt(d)
            st = jnp.where(causal, st, -1e30)
            scores = jnp.concatenate([sp, st], axis=-1)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            vall = jnp.concatenate([pv, jnp.swapaxes(v, 1, 2)], axis=1)
            o = jnp.einsum("bhst,bthd->bhsd", probs.astype(vall.dtype),
                           vall)
        with jax.named_scope(_profile.SCOPE_ATTN_PROJ):
            o = jnp.einsum("bhsd,hde->bse", o, bp["attn"]["Wo"])
        x = _residual(x, o)
        f = _layer_norm(bp["ln_m"], x)
        x = _residual(x, _mlp(bp, f))
        tail_caches.append((kv_rows(k), kv_rows(v)))
    return x, tail_caches


# ----------------------------------------------------- the family seam
#
# The decode engine serves more than one decoder family.  It asks the
# model's family for its functions here instead of importing
# TransformerLM's: ``embed(params, tok, pos)``, ``prefill(params, hyper,
# prompt, cache_len)``, ``decode_step(params, hyper, caches, x_tok, pos,
# mesh)`` and ``head(params, hyper, hidden)``, plus the state a slot
# holds: ``state_shapes(hyper, capacity, max_len, dtype)`` (each layer's
# tuple of ``(shape, dtype)``, capacity first: key/value slabs, or a
# recurrent layer's fixed-size state), ``slab_dtype(params)``,
# ``insert(hyper, caches, prompt_caches, slot, length)`` and
# ``kv_kinds(hyper, capacity, max_len, dtype)`` (``(rows, read block,
# layers counted)`` of each kind of slab).  A family that runs its
# layers more than once a token says how often, ``passes(hyper)`` (the
# engine's spans and ``pass_steps`` count by it; one where it is not
# given).  The engine makes the state's
# zeros, specs, placement and donation from ``state_shapes`` alone.  The
# prefill is called with the prompt's ``length`` as a keyword; a family
# whose state is slabs alone does not need it.  TransformerLM's are the
# functions above, untouched and called as they always were, so its plans
# lower to the same programs; a family that needs more (slabs of two
# kinds, a step that hands back the chosen experts, recurrent state)
# brings a module of its own and registers its namespace here: this
# module names no other family.

def slab_state_shapes(slab_dims):
    """``state_shapes`` of a family whose every layer holds a key and a
    value slab, from its ``slab_dims(hyper, capacity, max_len)`` (one
    ``(capacity, rows, heads, d_head)`` a layer)."""
    def state_shapes(hyper, capacity, max_len, dtype):
        return [((kv_slab_shape(*dims), dtype),) * 2
                for dims in slab_dims(hyper, capacity, max_len)]
    return state_shapes


def _slab_dims(hyper, capacity, max_len):
    """Every layer alike: ``max_len`` rows of ``n_heads`` heads."""
    n_heads = int(hyper["n_heads"])
    return [(capacity, max_len, n_heads, int(hyper["d_model"]) // n_heads)
            ] * int(hyper["n_layers"])


@jax.named_scope(_profile.SCOPE_INSERT)
def _insert(hyper, caches, prompt_caches, slot, length):
    """A prefilled prompt's (padded) rows into slot ``slot``."""
    return [(kv_insert(ck, pk, slot), kv_insert(cv, pv, slot))
            for (ck, cv), (pk, pv) in zip(caches, prompt_caches)]


def _kv_kinds(hyper, capacity, max_len, dtype):
    """One kind of slab, and ONE layer of it counted (all are alike)."""
    return [(max_len, decode_read_block(
        capacity, max_len, int(hyper["d_model"]), int(hyper["n_heads"]),
        dtype), 1)]


TRANSFORMER_LM = SimpleNamespace(
    name="transformer_lm", embed=_embed_token, prefill=_prefill,
    decode_step=_decode_step,
    head=lambda params, hyper, hidden: _head_logits(params, hidden),
    state_shapes=slab_state_shapes(_slab_dims),
    slab_dtype=lambda params: jnp.float32, insert=_insert,
    kv_kinds=_kv_kinds, routed=False, refuses=())


#: family name -> its namespace; a family's module adds itself
#: (``register_family``) when the model that names it is imported
FAMILIES = {TRANSFORMER_LM.name: TRANSFORMER_LM}


def register_family(family):
    """A decoder family hands over its generation functions (a
    namespace as ``TRANSFORMER_LM``'s) under ``family.name``."""
    FAMILIES[family.name] = family
    return family


def family_of(hyper):
    """The generation functions of the family a model's ``hyper`` names
    (``hyper["family"]``; a ``TransformerLM`` names none)."""
    name = hyper.get("family") or TRANSFORMER_LM.name
    if name not in FAMILIES:
        raise ValueError(
            f"no generation functions for model family {name!r}: the "
            "module of the model that names it registers them "
            f"(known: {sorted(FAMILIES)})")
    return FAMILIES[name]


def _sample(logits, rng, temperature, top_k: Optional[int] = None,
            top_p: Optional[float] = None):
    """Greedy when temperature == 0, else temperature softmax with
    optional top-k and/or top-p (nucleus) truncation.

    The ONE sampling implementation both decode paths share: the
    compiled-scan path (``build_generate_fn``) passes Python values
    (static branch — greedy compiles to a bare argmax, exactly the
    pre-sampling plan), while the slot-array ``DecodeEngine`` passes
    traced per-slot scalars (``top_k <= 0`` / ``top_p >= 1`` disable),
    in which case greedy-vs-sampled is an in-graph select — a
    ``temperature == 0`` slot still yields the bit-exact argmax token,
    which is what keeps the engine's greedy streams identical to this
    function's static-greedy plan.

    The sampled path is ONE descending ``top_k(V)`` (values + source
    indices), both truncation thresholds off the same sorted array,
    and an inverse-CDF draw from ONE uniform per row — deliberately
    not V gumbels + two sorts: this runs per decode step (and per
    speculative window position).  With traced knobs a greedy row
    computes all of it and keeps none, so the engine calls this only
    from the branch a dispatch takes when one of its live slots samples
    (``decode._pick_tokens``); an all-greedy dispatch never gets
    here."""
    greedy = jnp.argmax(logits, axis=-1)
    static_t = isinstance(temperature, (int, float))
    if static_t and float(temperature) == 0.0:
        return greedy
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    scaled = logits.astype(jnp.float32) / t
    V = scaled.shape[-1]
    srt, src = lax.top_k(scaled, V)  # descending values + indices
    # top-k threshold: the k-th sorted value (disabled -> -inf)
    if top_k is None:
        kth = -jnp.inf
    elif isinstance(top_k, int):
        kth = srt[..., top_k - 1:top_k]
    else:
        idx = jnp.clip(top_k - 1, 0, V - 1).astype(jnp.int32)
        kth = lax.dynamic_index_in_dim(srt, idx, axis=-1,
                                       keepdims=True)
        kth = jnp.where(top_k > 0, kth, -jnp.inf)
    # unnormalized sorted probabilities (shared by top-p + the draw)
    e = jnp.exp(srt - srt[..., :1])
    csum = jnp.cumsum(e, axis=-1)
    # nucleus threshold: keep the sorted prefix whose mass STRICTLY
    # BEFORE each entry is < p of the total — the top token's before-
    # mass is 0, so at least one entry always survives
    if top_p is None:
        pth = -jnp.inf
    else:
        keep = (csum - e) < jnp.asarray(top_p,
                                        jnp.float32) * csum[..., -1:]
        pth = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                      keepdims=True)
    thr = jnp.maximum(kth, pth)
    ek = jnp.where(srt >= thr, e, 0.0)
    ck = jnp.cumsum(ek, axis=-1)
    u = jax.random.uniform(rng, logits.shape[:-1],
                           jnp.float32)[..., None] * ck[..., -1:]
    pick = jnp.sum((ck <= u).astype(jnp.int32), axis=-1)
    # u can round up to exactly ck[-1] (uniform near 1 x the total),
    # making every cumsum entry <= u — clamp to the KEPT prefix so a
    # truncation-excluded token can never be drawn
    kept = jnp.sum((ek > 0.0).astype(jnp.int32), axis=-1)
    pick = jnp.clip(pick, 0, jnp.maximum(kept - 1, 0))
    sampled = jnp.take_along_axis(src, pick[..., None],
                                  axis=-1)[..., 0]
    if static_t:
        return sampled
    return jnp.where(jnp.asarray(temperature) > 0.0, sampled, greedy)


def build_generate_fn(hyper, s_p: int, max_new: int, temperature: float,
                      top_k: Optional[int], top_p: Optional[float] = None,
                      ragged: bool = False):
    """Compile one generation plan: (params, prompt, rng) -> (b, max_new)
    sampled token ids — or, with ``ragged``, (params, prompt, lengths,
    rng) where right-padded rows decode from their own (b,) prompt
    lengths (per-row positions and cache slots).  Static: prompt width,
    step count, sampling config.  The scan carries the caches, so the
    whole decode is one XLA while-loop — no per-token host dispatch."""
    cache_len = s_p + max_new

    def run(params, prompt, lengths, rng):
        x, caches = _prefill(params, hyper, prompt, cache_len)
        if lengths is None:
            last_hidden = x[:, -1, :]
        else:
            # ragged (right-padded) prompts: each row's last REAL token
            last_hidden = x[jnp.arange(x.shape[0]), lengths - 1]
        logits0 = _head_logits(params, last_hidden)
        rng0, rng_loop = jax.random.split(rng)
        tok0 = _sample(logits0, rng0, temperature, top_k, top_p)

        def step(carry, i):
            tok, caches, r = carry
            r, r_step = jax.random.split(r)
            pos = (s_p + i) if lengths is None else (lengths + i)
            emb = _embed_token(params, tok, pos)
            logits, caches = _decode_step(params, hyper, caches, emb, pos)
            nxt = _sample(logits, r_step, temperature, top_k, top_p)
            return (nxt, caches, r), tok

        (_, _, _), toks = lax.scan(
            step, (tok0, caches, rng_loop), jnp.arange(max_new))
        return jnp.swapaxes(toks, 0, 1)  # (steps, b) -> (b, steps)

    if ragged:
        return jax.jit(run)
    # jit the 3-arg closure (not a bare lambda over a jitted fn) so the
    # returned callable keeps .lower(), for lowering the plan unrun
    return jax.jit(lambda params, prompt, rng: run(params, prompt, None,
                                                   rng))


def build_beam_fn(hyper, s_p: int, max_new: int, beam_width: int):
    """Compile one beam-search plan: (params, prompt) -> (tok0, toks,
    parents, scores) for post-scan backtracking.  Deterministic (no
    rng); beams ride the batch dimension (row b·W + w), so every decode
    step stays one batched MXU computation, and each step's surviving
    beams gather their parents' KV caches."""
    cache_len = s_p + max_new
    W = beam_width

    @jax.jit
    def run(params, prompt):
        b = prompt.shape[0]
        x, caches = _prefill(params, hyper, prompt, cache_len)
        logits0 = _head_logits(params, x[:, -1, :])
        logp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)
        cum, tok0 = lax.top_k(logp0, W)  # (b, W)
        # broadcast each cache row to its W beams (b-major: row b·W + w)
        caches = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, W, axis=0), caches)

        def step(carry, i):
            tok, cum_lp, caches = carry  # (b, W), (b, W), (b·W, ...)
            pos = s_p + i
            emb = _embed_token(params, tok.reshape(b * W), pos)
            logits, caches = _decode_step(params, hyper, caches, emb,
                                          pos)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                      axis=-1)
            V = logp.shape[-1]
            total = cum_lp[:, :, None] + logp.reshape(b, W, V)
            cum2, idx = lax.top_k(total.reshape(b, W * V), W)
            parent = idx // V  # (b, W) surviving beams' ancestors
            tok2 = idx % V
            brow = jnp.arange(b)[:, None]
            caches = jax.tree_util.tree_map(
                lambda c: c.reshape(b, W, *c.shape[1:])[brow, parent]
                .reshape(b * W, *c.shape[1:]), caches)
            return (tok2, cum2, caches), (tok2, parent)

        (_, cum, _), (toks, parents) = lax.scan(
            step, (tok0, cum, caches), jnp.arange(max_new - 1))
        return tok0, toks, parents, cum

    return run


def _backtrack_beams(tok0, toks, parents, scores):
    """Reassemble (b, W, max_new) sequences from per-step (token,
    parent) records — walk each final beam's ancestry backwards."""
    tok0, toks, parents, scores = (np.asarray(jax.device_get(a))
                                   for a in (tok0, toks, parents,
                                             scores))
    steps, b, W = toks.shape
    seqs = np.zeros((b, W, steps + 1), np.int32)
    rows = np.arange(b)[:, None]
    beam = np.tile(np.arange(W), (b, 1))  # final beams, in score order
    for t in range(steps - 1, -1, -1):
        seqs[:, :, t + 1] = toks[t][rows, beam]
        beam = parents[t][rows, beam]
    seqs[:, :, 0] = tok0[rows, beam]
    return seqs, scores


def _plan_cache(model, key, build):
    """LRU-bounded compiled-plan cache: every distinct (prompt_len,
    max_new, sampling/beam) tuple is its own XLA executable —
    chat-style callers should pad prompts to a few bucket lengths, and
    the bound keeps a long-lived server from accumulating executables
    forever."""
    cache = getattr(model, "_generate_fns", None)
    if cache is None:
        import collections
        cache = model._generate_fns = collections.OrderedDict()
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = build()
        while len(cache) > 8:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return fn


def generate(model, prompt_ids, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             seed: int = 0, num_beams: int = 1,
             prompt_lengths=None) -> np.ndarray:
    """Generate continuations for a batch of equal-length prompts.

    Args:
        model: a (trained or loaded) :class:`TransformerLM`.
        prompt_ids: (batch, prompt_len) int token ids; prompt_len +
            max_new_tokens must fit ``max_len``.
        max_new_tokens: number of tokens to decode.
        temperature: 0.0 = greedy argmax; > 0 samples from the
            temperature-scaled distribution.
        top_k: optional truncation to the k most likely tokens before
            sampling (ignored when greedy).
        top_p: optional nucleus truncation — sample from the smallest
            descending-probability set reaching mass ``top_p``
            (ignored when greedy; composable with top_k, which is
            applied first).
        num_beams: > 1 runs deterministic beam search over that many
            beams (temperature/top_k must be unset) and returns each
            batch row's highest-log-prob sequence.
        prompt_lengths: optional (batch,) true lengths of RIGHT-padded
            ragged prompts.  Each row decodes from its own last real
            token with per-row positions; its continuation lands at
            ``[lengths[b], lengths[b] + max_new_tokens)`` in the
            returned array (positions past that keep value 0).  Not
            combinable with beam search.
    Returns:
        (batch, prompt_len + max_new_tokens) int32 ids — prompt
        followed by the generated continuation (right-aligned per row
        when ``prompt_lengths`` is given, see above).
    """
    prompt = np.asarray(prompt_ids)
    if prompt.ndim != 2:
        raise ValueError(f"prompt_ids must be (batch, prompt_len), got "
                         f"shape {prompt.shape}")
    h = model.hyper
    s_p = int(prompt.shape[1])
    total = s_p + int(max_new_tokens)
    if total > h["max_len"]:
        raise ValueError(
            f"prompt ({s_p}) + max_new_tokens ({max_new_tokens}) = "
            f"{total} exceeds max_len ({h['max_len']})")
    # the decode path is implementation-agnostic: it reads params by
    # layer name and computes its own cached attention, so a model
    # TRAINED with ring (sequence-parallel) attention decodes here
    # unchanged — the KV cache for one sequence fits one device, which
    # is why there is no ring decode.  (Params under any strategy are
    # replicated or resharded by the jit on first call.)
    trainer = model.ensure_inference_ready()
    if prompt_lengths is not None:
        lengths = np.asarray(prompt_lengths)
        if lengths.shape != (prompt.shape[0],):
            raise ValueError(
                f"prompt_lengths must be ({prompt.shape[0]},), got "
                f"shape {lengths.shape}")
        if (lengths < 1).any() or (lengths > s_p).any():
            raise ValueError(
                f"prompt_lengths must lie in [1, {s_p}]")
        if num_beams > 1:
            raise ValueError(
                "prompt_lengths is not supported with beam search — "
                "pad prompts to equal length for num_beams > 1")
    if num_beams <= 1 and int(max_new_tokens) == 0:
        # nothing to decode — same (b, s_p) result on both sampling
        # paths without building a plan (beam keeps its >= 1 raise)
        return prompt.astype(np.int32)
    if num_beams > 1:
        if temperature != 0.0 or top_k is not None or top_p is not None:
            raise ValueError(
                "beam search (num_beams > 1) is deterministic — "
                "temperature/top_k/top_p do not apply")
        if max_new_tokens < 1:
            # the beam plan always scores at least the first token, so
            # a 0-token request cannot keep the output-shape contract
            raise ValueError("beam search needs max_new_tokens >= 1")
        if num_beams > h["vocab_size"]:
            raise ValueError(f"num_beams ({num_beams}) exceeds "
                             f"vocab_size ({h['vocab_size']})")
        fn = _plan_cache(model, ("beam", s_p, int(max_new_tokens),
                                 int(num_beams)),
                         lambda: build_beam_fn(h, s_p,
                                               int(max_new_tokens),
                                               int(num_beams)))
        seqs, _ = _backtrack_beams(
            *fn(trainer.state.params, jnp.asarray(prompt)))
        # beams come out in descending cumulative log-prob order; all
        # beams share one length, so raw log-prob IS the ranking
        return np.concatenate([prompt.astype(np.int32), seqs[:, 0]],
                              axis=1)
    ragged = prompt_lengths is not None
    key = (s_p, int(max_new_tokens), float(temperature),
           None if top_k is None else int(top_k),
           None if top_p is None else float(top_p), ragged)
    fn = _plan_cache(model, key,
                     lambda: build_generate_fn(
                         h, s_p, int(max_new_tokens), float(temperature),
                         None if top_k is None else int(top_k),
                         None if top_p is None else float(top_p),
                         ragged=ragged))
    if ragged:
        toks = fn(trainer.state.params, jnp.asarray(prompt),
                  jnp.asarray(lengths, jnp.int32),
                  jax.random.PRNGKey(seed))
        toks = np.asarray(jax.device_get(toks), np.int32)
        out = np.zeros((prompt.shape[0], s_p + int(max_new_tokens)),
                       np.int32)
        out[:, :s_p] = prompt
        rows = np.arange(prompt.shape[0])[:, None]
        cols = lengths[:, None] + np.arange(int(max_new_tokens))[None]
        out[rows, cols] = toks
        # anything past each row's continuation is not real content
        mask = np.arange(out.shape[1])[None] >= cols[:, -1:] + 1
        out[mask] = 0
        return out
    toks = fn(trainer.state.params, jnp.asarray(prompt),
              jax.random.PRNGKey(seed))
    return np.concatenate([prompt.astype(np.int32),
                           np.asarray(jax.device_get(toks),
                                      np.int32)], axis=1)
