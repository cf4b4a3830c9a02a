"""Plain reference for the ``ouro`` family (Ouro-2.6B), whole, as the
configuration's file states it.  Position p, pass t = 1..T, layer l:

    x = Emb[ids]                                     no positions added
    for t in 1..T:                                   the SAME layers
      for l in 1..L:
        a = Attn_l(RMSNorm_in1(x))                   causal, rotate-half
        x = x + RMSNorm_in2(a)                       rotary positions
        x = x + RMSNorm_post2(MLP_l(RMSNorm_post1(x)))
      x = RMSNorm_f(x)                               closes every pass
    logits = x W_head                                on the last pass
    RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w
    MLP(h)     = (silu(h W_in[:, :f]) * (h W_in[:, f:])) W_out
    Attn(h)    = softmax(q k^T / sqrt(d)) v; query head j reads key/value
                 head j // group; -> Wo

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernel, no cache, no batching.  Each pass runs over the
whole sequence with full causal attention, so what a pass attends to is
the keys and values that pass itself made for the earlier positions:
the cache-free form of the per-pass cache, independent of the program's
slabs.  It imports nothing of the program and takes nothing the program
made: the weights come from ``make_params`` (this file, from the seed),
which the drivers also hand to the program.

Departures from a textbook forward, for memory and time (the check asks
for 8 rows x n_positions x vocabulary float32 logits at once beside 5.3
GB of bfloat16 weights): rows go one at a time (``lax.map``), the passes
are a ``lax.fori_loop`` over one unrolled stack, and a product with a
bfloat16 weight is made from exact bfloat16 terms (``cohere2moe._mm``:
the same products ``highest`` makes, with no float32 copy of a weight at
all, where upcasting a layer's leaves to float32 would be one copy a
layer).  The weights are exactly what the program is given (bfloat16
leaves), so the two differ in arithmetic alone.  The exit gate is made
and held and not evaluated (``early_exit_threshold`` 1: every token runs
every pass).

``mode`` is the precision the forward runs in:
  "f32"   the reference proper
  "bf16"  the control of this bfloat16 configuration, the nearest
          precision below what it states: every product's result, the
          residual stream, norms, rotary turn and softmax in bfloat16
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import common
# the exact bfloat16-term products, shared with the other family's reference
from benchmark.reference.cohere2moe import _mm, _mm_act

MODES = ("f32", "bf16")


def dims(cfg):
    """The widths the layers take from the configuration's keys."""
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "layers": cfg["num_hidden_layers"],
            "passes": cfg["total_ut_steps"]}


def param_spec(cfg):
    """{layer: {leaf: (shape, kind)}} from the configuration's widths,
    under the names OuroLM gives its layers."""
    c = dims(cfg)
    d, f, hd = c["d"], c["f"], c["hd"]
    spec = {"tok_embed": {"embeddings": ((c["vocab"], d), "normal")},
            "lm_head": {"W": ((d, c["vocab"]), "normal")},
            "ln_final": {"gamma": ((d,), "gain")},
            "exit_gate": {"W": ((d, 1), "normal"), "b": ((1,), "zeros")}}
    for i in range(c["layers"]):
        for norm in ("ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"):
            spec[f"{norm}_{i}"] = {"gamma": ((d,), "gain")}
        spec[f"attn_{i}"] = {"Wq": ((d, c["heads"], hd), "normal"),
                             "Wk": ((d, c["kv"], hd), "normal"),
                             "Wv": ((d, c["kv"], hd), "normal"),
                             "Wo": ((c["heads"], hd, d), "normal")}
        spec[f"mlp_{i}"] = {"input_linear": ((d, 2 * f), "normal"),
                            "output_linear": ((f, d), "normal")}
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
    """One leaf's draw: ``normal`` N(0, std); ``gain`` U[0.8, 1.2] (a
    norm's gain: not 1, so that no two of a layer's four norms are
    interchangeable); ``zeros``."""
    def build(key):
        if kind == "normal":
            x = std * jax.random.normal(key, shape, jnp.float32)
        elif kind == "gain":
            x = jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
        else:
            x = jnp.zeros(shape, jnp.float32)
        return x.astype(dtype)
    return jax.jit(build)


# ------------------------------------------------------------------ math
def _rms(w, x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w.astype(x.dtype)


def _rope_half(x, theta):
    """Rotate-half over all of the last axis; ``x``: (s, heads, d),
    position = row: the pair ``(x[i], x[i + d/2])`` turns by ``p *
    theta ** (-2i / d)``."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _attention(q, k, v, mode):
    """``q (s, heads, d)`` over ``k, v (s, kv_heads, d)``, causal, scores
    materialised."""
    s, h, d = q.shape
    n_kv = k.shape[1]
    qg = q.reshape(s, n_kv, h // n_kv, d)
    sc = _mm_act("qngd,tnd->ngqt", qg, k, mode) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    o = _mm_act("ngqt,tnd->qngd", p, v, mode)
    return o.reshape(s, h * d)


def _layer(params, i, x, cfg, mode, dt_):
    """Layer ``i`` over one row ``x (s, d)``: the row after it, and the
    keys (turned) and values it attended to, ``(s, kv_heads, d)``."""
    c = dims(cfg)
    eps = cfg["rms_norm_eps"]
    ap = params[f"attn_{i}"]
    h = _rms(params[f"ln_attn_{i}"]["gamma"], x, eps)
    q, k, v = (_mm("se,ehd->shd", h, ap[w], mode).astype(dt_)
               for w in ("Wq", "Wk", "Wv"))
    theta = float(cfg["rope_theta"])
    k = _rope_half(k, theta)
    o = _attention(_rope_half(q, theta), k, v, mode)
    a = _mm("sf,fe->se", o, ap["Wo"].reshape(c["heads"] * c["hd"], -1),
            mode).astype(dt_)
    x = x + _rms(params[f"ln_attn_out_{i}"]["gamma"], a, eps)
    mp = params[f"mlp_{i}"]
    u = _mm("se,ef->sf", _rms(params[f"ln_mlp_{i}"]["gamma"], x, eps),
            mp["input_linear"], mode).astype(dt_)
    m = jax.nn.silu(u[:, :c["f"]]) * u[:, c["f"]:]
    m = _mm("sf,fe->se", m, mp["output_linear"], mode).astype(dt_)
    return x + _rms(params[f"ln_mlp_out_{i}"]["gamma"], m, eps), (k, v)


def _row_logits(params, row, cfg, mode):
    dt_ = jnp.float32 if mode == "f32" else jnp.bfloat16
    c = dims(cfg)
    x = jnp.take(params["tok_embed"]["embeddings"], row, axis=0).astype(dt_)

    def one_pass(t, x):
        for i in range(c["layers"]):
            x, _ = _layer(params, i, x, cfg, mode, dt_)
        return _rms(params["ln_final"]["gamma"], x, cfg["rms_norm_eps"])

    x = lax.fori_loop(0, c["passes"], one_pass, x)
    return _mm("se,ev->sv", x, params["lm_head"]["W"], mode
               ).astype(jnp.float32)


def logits_fn(params, tokens, cfg, mode="f32"):
    """(b, s) token ids -> (b, s, vocab) float32 logits, a row at a
    time."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    with jax.default_matmul_precision("highest"):
        return lax.map(lambda row: _row_logits(params, row, cfg, mode),
                       tokens)


def pass_kv(params, row, cfg):
    """The keys and values every layer makes at every pass over one row
    of ids ``(s,)``, float32: ``(passes, layers, 2, s, kv_heads *
    d_head)``, keys turned by their positions.  What a pass's part of a
    layer's cache holds (the tests' look into the slabs)."""
    c = dims(cfg)
    x = jnp.take(params["tok_embed"]["embeddings"], row, axis=0).astype(
        jnp.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for _ in range(c["passes"]):
            layers = []
            for i in range(c["layers"]):
                x, (k, v) = _layer(params, i, x, cfg, "f32", jnp.float32)
                layers.append(jnp.stack([k.reshape(len(row), -1),
                                         v.reshape(len(row), -1)]))
            out.append(jnp.stack(layers))
            x = _rms(params["ln_final"]["gamma"], x, cfg["rms_norm_eps"])
    return jnp.stack(out)
