"""Plain reference for the ``granitemoehybrid`` family without experts
(Granite 4.0-H Micro), whole, as the configuration's file states it.

    x_0    = Emb[ids] * embedding_multiplier
    layer: h = RMSNorm(x)                 x * rsqrt(mean(x^2) + eps) * w
           x = x + m * (Mamba(h) or Attn(h))          m = residual_multiplier
           x = x + m * MLP(RMSNorm(x))
    MLP(h) = (silu(h W_in[:, :f]) * (h W_in[:, f:])) W_out
    Attn(h)= softmax(q k^T * attention_multiplier) v, causal, no
             positions; query head j reads key/value head j // group; -> Wo
    Mamba(h):
      [z | xBC | dt] = h W_in
      xBC = silu(causal depthwise conv1d(xBC, kernel d_conv) + b)
      [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T  (per head; S_{-1} = 0)
      y_t = S_t C_t + D x_t
      out = (w * rmsnorm(y * silu(z))) W_out        the norm over d_inner
    logits = RMSNorm_f(x_L) Emb^T / logits_scaling

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernel, no cache, no batching, no chunking.  The
recurrence is the published definition itself, a ``lax.scan`` over
positions, independent of the chunked dual form the program computes a
prompt with, so it is what checks that algorithm.  It imports nothing of
the program and takes nothing the program made: the weights come from
``make_params`` (this file, from the seed), which the drivers also hand
to the program.

Departures from a textbook forward, for memory and time (the check asks
for 8 rows x n_positions x vocabulary float32 logits at once, 4.1 GB,
beside 6.4 GB of weights): rows go one at a time (``lax.map``),
attention a cached head at a time, and a product with a bfloat16 weight
is made from exact bfloat16 terms (``cohere2moe._mm``: the same products
``highest`` makes, no float32 copy of a weight).  The weights are exactly
what the program is given (bfloat16 leaves, per-head ones included), so
the two differ in arithmetic alone.  The program rounds the projected
``xBC`` to the weights' dtype before the convolution (it is what the
convolution's window keeps); the reference does not.

``mode`` is the precision the forward runs in:
  "f32"   the reference proper
  "bf16"  the control of this bfloat16 configuration, the nearest
          precision below what it states: every product's result, the
          residual stream, norms, softmax, the convolution and the
          recurrence's state in bfloat16
"""

import functools
import math

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
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n = cfg["mamba_d_state"]
    return {"d": cfg["hidden_size"], "f": cfg["shared_intermediate_size"],
            "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
            "m_heads": heads, "m_hd": hd, "inner": heads * hd, "n": n,
            "conv_dim": heads * hd + 2 * n, "k": cfg["mamba_d_conv"]}


def _kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def param_spec(cfg):
    """{layer: {leaf: (shape, kind)}} from the configuration's widths,
    under the names GraniteHybridLM gives its layers."""
    c = dims(cfg)
    d, f = c["d"], c["f"]
    spec = {"tok_embed": {"embeddings": ((c["vocab"], d), "normal")},
            "ln_final": {"gamma": ((d,), "ones")}}
    for i, kind in enumerate(_kinds(cfg)):
        spec[f"ln_{i}"] = {"gamma": ((d,), "ones")}
        spec[f"ln_mlp_{i}"] = {"gamma": ((d,), "ones")}
        spec[f"mlp_{i}"] = {"input_linear": ((d, 2 * f), "normal"),
                            "output_linear": ((f, d), "normal")}
        if kind == "mamba":
            spec[f"mamba_{i}"] = {
                "in_proj": ((d, c["inner"] + c["conv_dim"] + c["m_heads"]),
                            "normal"),
                "conv_w": ((c["k"], c["conv_dim"]), "conv"),
                "conv_b": ((c["conv_dim"],), "conv"),
                "A_log": ((c["m_heads"],), "A_log"),
                "D": ((c["m_heads"],), "ones"),
                "dt_bias": ((c["m_heads"],), "dt_bias"),
                "norm": ((c["inner"],), "ones"),
                "out_proj": ((c["inner"], d), "normal")}
        else:
            spec[f"attn_{i}"] = {"Wq": ((d, c["heads"], c["hd"]), "normal"),
                                 "Wk": ((d, c["kv"], c["hd"]), "normal"),
                                 "Wv": ((d, c["kv"], c["hd"]), "normal"),
                                 "Wo": ((c["heads"], c["hd"], d), "normal")}
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
            out[layer][leaf] = _leaf_fn(shape, kind, std, cfg["mamba_d_conv"],
                                        jnp.dtype(dtype))(
                jax.random.fold_in(key, n))
            n += 1
    return out


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape, kind, std, d_conv, dtype):
    """One leaf's draw: ``normal`` N(0, std); ``conv`` U(+-1/sqrt(d_conv))
    (a depthwise convolution's fan-in); ``A_log`` log U[1, 16];
    ``dt_bias`` the inverse softplus of a log-uniform dt in [0.001, 0.1]
    (Mamba-2's initialisation); ``ones``."""
    def build(key):
        if kind == "normal":
            x = std * jax.random.normal(key, shape, jnp.float32)
        elif kind == "conv":
            bound = 1.0 / math.sqrt(d_conv)
            x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif kind == "A_log":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                           16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                            math.log(1e-3), math.log(0.1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        else:
            x = jnp.ones(shape, jnp.float32)
        return x.astype(dtype)
    return jax.jit(build)


# ------------------------------------------------------------------ math
def _rms(w, x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w.astype(x.dtype)


def _attention(q, k, v, scale, mode):
    """``q (s, heads, d)`` over ``k, v (s, kv_heads, d)``, causal, a cached
    head at a time (``lax.map``), scores materialised."""
    s, h, d = q.shape
    n_kv = k.shape[1]
    qg = q.reshape(s, n_kv, h // n_kv, d).transpose(1, 2, 0, 3)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def head(args):
        qh, kh, vh = args                  # (g, s, d), (s, d), (s, d)
        sc = _mm_act("gqd,td->gqt", qh, kh, mode) * scale
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return _mm_act("gqt,td->gqd", p, vh, mode)

    o = lax.map(head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(2, 0, 1, 3).reshape(s, h * d)


def _mamba(p, h, cfg, mode, dt_):
    """The mixer over one row ``h (s, d)``: the recurrence position by
    position."""
    c = dims(cfg)
    inner, n, k = c["inner"], c["n"], c["k"]
    s = h.shape[0]
    zxd = _mm("se,ef->sf", h, p["in_proj"], mode).astype(dt_)
    z, xbc = zxd[:, :inner], zxd[:, inner:inner + c["conv_dim"]]
    dt = zxd[:, inner + c["conv_dim"]:]
    w = p["conv_w"].astype(dt_)
    xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(xp[i:i + s] * w[i] for i in range(k)) + p["conv_b"].astype(dt_)
    act = jax.nn.silu(conv)
    xs = act[:, :inner].reshape(s, c["m_heads"], c["m_hd"])
    B, C = act[:, inner:inner + n], act[:, inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(dt_))
    A = -jnp.exp(p["A_log"].astype(dt_))
    D = p["D"].astype(dt_)

    def step(S, t):
        xt, dtt, bt, ct = t
        S = S * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[..., None] * bt[None, None, :]
        return S, _mm_act("hpn,n->hp", S, ct, mode) + D[:, None] * xt

    _, y = lax.scan(step, jnp.zeros((c["m_heads"], c["m_hd"], n), dt_),
                    (xs, dt, B, C))
    g = y.reshape(s, inner) * jax.nn.silu(z)
    g = _rms(p["norm"], g, cfg["rms_norm_eps"])
    return _mm("sf,fe->se", g, p["out_proj"], mode)


def _row_logits(params, row, cfg, mode):
    dt_ = jnp.float32 if mode == "f32" else jnp.bfloat16
    c = dims(cfg)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    table = params["tok_embed"]["embeddings"]
    x = jnp.take(table, row, axis=0).astype(dt_) * cfg["embedding_multiplier"]
    for i, kind in enumerate(_kinds(cfg)):
        h = _rms(params[f"ln_{i}"]["gamma"], x, eps)
        if kind == "mamba":
            a = _mamba(params[f"mamba_{i}"], h, cfg, mode, dt_)
        else:
            ap = params[f"attn_{i}"]
            q, k, v = (_mm("se,ehd->shd", h, ap[w], mode)
                       for w in ("Wq", "Wk", "Wv"))
            o = _attention(q, k, v, cfg["attention_multiplier"], mode)
            a = _mm("sf,fe->se", o, ap["Wo"].reshape(c["heads"] * c["hd"], -1),
                    mode)
        x = x + res * a.astype(dt_)
        mp = params[f"mlp_{i}"]
        u = _mm("se,ef->sf", _rms(params[f"ln_mlp_{i}"]["gamma"], x, eps),
                mp["input_linear"], mode)
        m = jax.nn.silu(u[:, :c["f"]]) * u[:, c["f"]:]
        x = x + res * _mm("sf,fe->se", m, mp["output_linear"], mode
                          ).astype(dt_)
    x = _rms(params["ln_final"]["gamma"], x, eps)
    return (_mm("se,ve->sv", x, table, mode) / cfg["logits_scaling"]
            ).astype(jnp.float32)


def logits_fn(params, tokens, cfg, mode="f32"):
    """(b, s) token ids -> (b, s, vocab) float32 logits, a row at a
    time."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    return lax.map(lambda row: _row_logits(params, row, cfg, mode), tokens)
