"""Plain reference for the GPT-2 family as this repo's TransformerLM
builds it: pre-norm blocks, learned positions, causal attention without
q/k/v/o biases, gelu (tanh form) MLP, final LayerNorm, an UNTIED head
with bias, log-softmax, mean next-token NLL, Adam.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernel, no cache, no batching tricks.  It imports nothing
of the program and takes nothing the program made: the weights come from
``make_params`` (this file, from the seed), which the drivers also hand
to the program.  The layers run under ``lax.scan`` with one
``jax.checkpoint`` per block so that the full-size check fits beside
nothing else on a 16 GB chip and compiles in seconds; rows go through in
blocks.

``mode`` is the precision the forward runs in:
  "f32"   the reference proper
  "bf16"  parameters and activations in bfloat16 (the control of a
          float32 configuration)
  "fp8"   bfloat16, and both operands of every matmul rounded to
          float8_e4m3 with a per-tensor scale (the control of a
          bfloat16 configuration)
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import common

MODES = ("f32", "bf16", "fp8")
_BLOCK_LEAVES = ("ln_attn", "attn", "ln_mlp", "mlp_up", "mlp_down")


def param_spec(cfg):
    """{layer: {leaf: (shape, kind)}} from the configuration's widths,
    under the names TransformerLM gives its layers.  kind: "normal",
    "zeros" or "ones"."""
    d, h, v = cfg["n_embd"], cfg["n_head"], cfg["vocab_size"]
    ff = cfg.get("n_inner") or 4 * d
    hd = d // h
    spec = {
        "tok_embed": {"embeddings": ((v, d), "normal")},
        "pos_embed": {"table": ((cfg["n_positions"], d), "normal")},
        "ln_final": {"gamma": ((d,), "ones"), "beta": ((d,), "zeros")},
        "lm_head": {"W": ((d, v), "normal"), "b": ((v,), "zeros")},
    }
    for i in range(cfg["n_layer"]):
        spec[f"ln_attn_{i}"] = {"gamma": ((d,), "ones"),
                                "beta": ((d,), "zeros")}
        spec[f"attn_{i}"] = {"Wq": ((d, h, hd), "normal"),
                             "Wk": ((d, h, hd), "normal"),
                             "Wv": ((d, h, hd), "normal"),
                             "Wo": ((h, hd, d), "normal")}
        spec[f"ln_mlp_{i}"] = {"gamma": ((d,), "ones"),
                               "beta": ((d,), "zeros")}
        spec[f"mlp_up_{i}"] = {"W": ((d, ff), "normal"),
                               "b": ((ff,), "zeros")}
        spec[f"mlp_down_{i}"] = {"W": ((ff, d), "normal"),
                                 "b": ((d,), "zeros")}
    return spec


def n_params(cfg):
    return sum(int(np.prod(shape)) for layer in param_spec(cfg).values()
               for shape, _ in layer.values())


def make_params(cfg, seed, dtype=jnp.float32):
    """The whole tree on the device in ONE jitted call from the seed."""
    spec = param_spec(cfg)
    std = cfg.get("initializer_range", 0.02)

    def build(key):
        out, n = {}, 0
        for layer in sorted(spec):
            out[layer] = {}
            for leaf in sorted(spec[layer]):
                shape, kind = spec[layer][leaf]
                if kind == "normal":
                    val = std * jax.random.normal(
                        jax.random.fold_in(key, n), shape, jnp.float32)
                else:
                    val = (jnp.ones if kind == "ones" else jnp.zeros)(
                        shape, jnp.float32)
                out[layer][leaf] = val.astype(dtype)
                n += 1
        return out

    return jax.jit(build)(common.seed_key(seed))


# ------------------------------------------------------------------ math
def _mm(spec, a, b, mode):
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    if mode == "fp8":
        a, b = common.fp8(a), common.fp8(b)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32
                      ).astype(a.dtype)


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, eps, mode):
    b, s, d = x.shape
    a = _layer_norm(p["ln_attn"], x, eps)
    q = _mm("bse,ehd->bhsd", a, p["attn"]["Wq"], mode)
    k = _mm("bse,ehd->bhsd", a, p["attn"]["Wk"], mode)
    v = _mm("bse,ehd->bhsd", a, p["attn"]["Wv"], mode)
    scores = _mm("bhsd,bhtd->bhst", q, k, mode) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _mm("bhst,bhtd->bhsd", probs, v, mode)
    x = x + _mm("bhsd,hde->bse", o, p["attn"]["Wo"], mode)
    f = _layer_norm(p["ln_mlp"], x, eps)
    f = _gelu(_mm("bse,ef->bsf", f, p["mlp_up"]["W"], mode)
              + p["mlp_up"]["b"])
    return x + _mm("bsf,fe->bse", f, p["mlp_down"]["W"], mode) \
        + p["mlp_down"]["b"]


def logits_fn(params, tokens, cfg, mode="f32"):
    """(b, s) token ids -> (b, s, vocab) float32 logits."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if mode != "f32":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    s = tokens.shape[1]
    x = jnp.take(params["tok_embed"]["embeddings"], tokens, axis=0)
    x = x + params["pos_embed"]["table"][:s]
    stacked = {name: jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"{name}_{i}"] for i in range(cfg["n_layer"])])
        for name in _BLOCK_LEAVES}
    block = jax.checkpoint(functools.partial(_block, eps=eps, mode=mode))
    x, _ = lax.scan(lambda h, p: (block(h, p), None), x, stacked)
    x = _layer_norm(params["ln_final"], x, eps)
    out = _mm("bse,ev->bsv", x, params["lm_head"]["W"], mode) \
        + params["lm_head"]["b"]
    return out.astype(jnp.float32)


def loss_fn(params, x, y, cfg, mode="f32"):
    """Mean over every position of -log softmax(logits)[target]."""
    logp = jax.nn.log_softmax(logits_fn(params, x, cfg, mode), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


# -------------------------------------------------------------- training
def train_steps(cfg, seed, x, y, steps=3, rows=4, mode="f32",
                batch_rows=None):
    """The family's loss and weights handed to ``common.train_steps``."""
    return common.train_steps(
        functools.partial(loss_fn, cfg=cfg, mode=mode),
        functools.partial(make_params, cfg, seed), cfg["train"], x, y,
        steps, rows, batch_rows)
