"""Plain reference for the ``cohere2_moe`` family (Command A+, text
side), one chip's share of its experts and vocabulary as the
configuration's file states it.

    h      = LN(x)          (x - mean) / sqrt(var + eps) * g, no bias;
                            ONE norm feeds both branches
    q,k,v  = h Wq, h Wk, h Wv            heads x 128, kv_heads x 128
    sliding: q,k = rope(q,k, pos);  key j visible iff j <= i, i - j < window
    full:    no positions at all;   key j visible iff j <= i
    a      = softmax(q k^T / sqrt(128)) v -> concat heads -> Wo
             (query head h reads key/value head h // group)
    s      = sigmoid(h Wr);  I = top-k of s;  g_e = s_e / sum_{I} s
    E(h)   = (silu(h Wgate) * (h Wup)) Wdown
    m      = sum_{e in I, e held} g_e E_e(h)  +  mean_j S_j(h)
    x'     = x + a + m
    logits = LN_f(x_L) Emb^T * logit_scale

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernel, no cache, no batching, no sort: every held expert
runs over every token and is weighted by its gate (0 where the token
did not choose it).  It imports nothing of the program and takes nothing
the program made: the weights come from ``make_params`` (this file, from
the seed), which the drivers also hand to the program.

Departures from a textbook forward, for memory and time (the check asks
for 8 rows x n_positions x vocabulary float32 logits at once, 6.44 GB,
beside these weights; a first version took 190 s a run on the chip):
rows go one at a time (``lax.map``), attention a cached head and a
block of queries at a time against the keys up to that block, and a
product with a bfloat16 weight is made from exact bfloat16 terms
(``_mm``: the same products ``highest`` makes, no float32 copy of a
weight).  The weights are exactly what the program is given (bfloat16
leaves), so the reference and the program differ in arithmetic alone.

``mode`` is the precision the forward runs in:
  "f32"   the reference proper
  "bf16"  the control of this bfloat16 configuration, the nearest
          precision below what it states: every product's result, the
          residual stream, norms, softmax and router scores in bfloat16
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import common

MODES = ("f32", "bf16")
_Q_BLOCK = 512


def _kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def param_spec(cfg):
    """{layer: {leaf: (shape, kind)}} from the configuration's widths,
    under the names CommandAPlusLM gives its layers; experts stacked."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    held, shared = cfg["experts_held"][1], cfg["num_shared_experts"]
    spec = {"tok_embed": {"embeddings": ((v, d), "normal")},
            "ln_final": {"gamma": ((d,), "ones")}}
    for i in range(cfg["num_hidden_layers"]):
        spec[f"ln_{i}"] = {"gamma": ((d,), "ones")}
        spec[f"attn_{i}"] = {"Wq": ((d, h, hd), "normal"),
                             "Wk": ((d, kv, hd), "normal"),
                             "Wv": ((d, kv, hd), "normal"),
                             "Wo": ((h, hd, d), "normal")}
        spec[f"moe_{i}"] = {
            "router": ((d, cfg["num_experts_published"]), "normal"),
            "w_gate": ((held, d, f), "normal"),
            "w_up": ((held, d, f), "normal"),
            "w_down": ((held, f, d), "normal"),
            "s_gate": ((shared, d, f), "normal"),
            "s_up": ((shared, d, f), "normal"),
            "s_down": ((shared, f, d), "normal")}
    return spec


def n_params(cfg):
    return sum(int(np.prod(shape)) for layer in param_spec(cfg).values()
               for shape, _ in layer.values())


def make_params(cfg, seed, dtype=jnp.bfloat16):
    """The whole tree on the device from the seed, a leaf at a time (a
    float32 draft of one leaf, never of the tree)."""
    spec = param_spec(cfg)
    std = cfg.get("initializer_range", 0.02)
    key = common.seed_key(seed)
    out, n = {}, 0
    for layer in sorted(spec):
        out[layer] = {}
        for leaf in sorted(spec[layer]):
            shape, kind = spec[layer][leaf]
            out[layer][leaf] = _leaf_fn(shape, kind, std, jnp.dtype(dtype))(
                jax.random.fold_in(key, n))
            n += 1
    return out


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape, kind, std, dtype):
    def build(key):
        if kind == "normal":
            return (std * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)
        return jnp.ones(shape, dtype)
    return jax.jit(build)


# ------------------------------------------------------------------ math
def _mm(spec, a, w, mode):
    """``einsum(spec, a, w)``: float32 at ``highest`` ("f32"), or
    bfloat16 operands with the result rounded to bfloat16 ("bf16").

    Where ``w`` is a bfloat16 WEIGHT (as the seed's weights are), the
    float32 product is made from three bfloat16 terms of ``a`` that add
    up to it exactly (8 + 8 + 8 bits of mantissa), each against ``w``
    as it is, summed in float32: every product is exact in float32, as
    ``highest``'s are, and these are the only ones ``highest`` would
    keep (it splits both operands and the weight's lower terms are
    zero).  It runs at the MXU's bfloat16 rate and holds no float32
    copy of a weight; a float32 ``w`` takes ``highest`` itself."""
    if mode != "f32":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)
    if w.dtype != jnp.bfloat16:
        return jnp.einsum(spec, a, w.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
    a1 = a.astype(jnp.bfloat16)
    r = a - a1.astype(jnp.float32)
    a2 = r.astype(jnp.bfloat16)
    a3 = (r - a2.astype(jnp.float32)).astype(jnp.bfloat16)
    return sum(jnp.einsum(spec, t, w, preferred_element_type=jnp.float32)
               for t in (a1, a2, a3))


def _mm_act(spec, a, b, mode):
    """A product of two activations (scores, probabilities x values)."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32
                      ).astype(jnp.bfloat16)


def _norm(gamma, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma.astype(x.dtype)


def _rope(x, theta):
    """Interleaved pairs (rope_gptj) over all of the last axis; ``x``:
    (s, heads, d), position = row."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, window, mode):
    """``q (s, heads, d)`` over ``k, v (s, kv_heads, d)``: a cached head
    at a time (``lax.map``), ``_Q_BLOCK`` queries at a time against the
    keys up to the block's last row (what lies above the diagonal for
    every row of a block is never computed; the mask does the rest),
    scores materialised."""
    s, h, d = q.shape
    n_kv = k.shape[1]
    g = h // n_kv
    block = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    qg = q.reshape(s, n_kv, g, d).transpose(1, 2, 0, 3)    # (n_kv, g, s, d)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # (n_kv, s, d)

    def head(args):
        qh, kh, vh = args               # (g, s, d), (s, d), (s, d)
        out = []
        for b0 in range(0, s, block):
            hi = b0 + block
            i = jnp.arange(b0, hi)[:, None]
            j = jnp.arange(hi)[None, :]
            seen = j <= i
            if window is not None:
                seen &= i - j < window
            sc = _mm_act("gqd,td->gqt", qh[:, b0:hi], kh[:hi], mode) \
                / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            out.append(_mm_act("gqt,td->gqd", p, vh[:hi], mode))
        return jnp.concatenate(out, axis=1)                 # (g, s, d)

    o = lax.map(head, (qg, kt, vt))                         # (n_kv, g, s, d)
    return o.transpose(2, 0, 1, 3).reshape(s, h * d)


def _expert(h, wg, wu, wd, mode):
    act = jax.nn.silu(_mm("te,ef->tf", h, wg, mode)) \
        * _mm("te,ef->tf", h, wu, mode)
    return _mm("tf,fe->te", act, wd, mode)


def _moe(p, h, cfg, mode):
    first, held = cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("te,en->tn", h, p["router"], mode))
    # the k largest, equal scores to the lower index
    top = jnp.argsort(-s, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], top].set(True)
    gate = jnp.where(chosen, s, 0)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    m = jnp.zeros_like(h)
    for e in range(held):
        m = m + gate[:, first + e, None] * _expert(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e], mode)
    shared = cfg["num_shared_experts"]
    for e in range(shared):
        m = m + _expert(h, p["s_gate"][e], p["s_up"][e], p["s_down"][e],
                        mode) / shared
    return m


def _row_logits(params, row, cfg, mode):
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    eps = cfg["layer_norm_eps"]
    h_n, kv_n, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    table = params["tok_embed"]["embeddings"]
    x = jnp.take(table, row, axis=0).astype(dt)
    for i, kind in enumerate(_kinds(cfg)):
        sliding = kind == "sliding_attention"
        ap = params[f"attn_{i}"]
        h = _norm(params[f"ln_{i}"]["gamma"], x, eps)
        q = _mm("se,ehd->shd", h, ap["Wq"], mode)
        k = _mm("se,ehd->shd", h, ap["Wk"], mode)
        v = _mm("se,ehd->shd", h, ap["Wv"], mode)
        if sliding:
            q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        o = _attention(q, k, v, cfg["sliding_window"] if sliding else None,
                       mode)
        a = _mm("sf,fe->se", o, ap["Wo"].reshape(h_n * hd, -1), mode)
        x = x + a + _moe(params[f"moe_{i}"], h, cfg, mode)
    x = _norm(params["ln_final"]["gamma"], x, eps)
    return (_mm("se,ve->sv", x, table, mode) * cfg["logit_scale"]
            ).astype(jnp.float32)


def logits_fn(params, tokens, cfg, mode="f32"):
    """(b, s) token ids -> (b, s, vocab) float32 logits, a row at a
    time."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    return lax.map(lambda row: _row_logits(params, row, cfg, mode), tokens)
