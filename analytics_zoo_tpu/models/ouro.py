"""OuroLM: the ``ouro`` family, a looped decoder: one stack of layers
whose weights are shared across ``total_ut_steps`` passes a token.

    x = Emb[tok]
    for t in 1..total_ut_steps:          the same layers each pass
      for each layer:
        x = x + RMSNorm(Attn(RMSNorm(x)))        sandwich norms
        x = x + RMSNorm(MLP(RMSNorm(x)))         SwiGLU, one fused input
      x = RMSNorm_f(x)                   closes every pass, feeds the next
    logits = x W_head                    untied head

Attention is grouped-query with rotary positions by rotate-half, no
biases.  Built from the framework's own layers (``Embedding``,
``RMSNorm``, ``GroupedQueryAttention``, ``GatedMLP``, ``Dense``,
``Merge``), each layer instance called once a pass (a layer instance
contributes one params entry), so ``compile`` / ``predict`` /
``InferenceModel.load_keras_net`` / ``generate_stream`` work as for the
other families; the decode engine gets this family's looped prefill and
decode step and its per-pass cache through ``models.generation.
family_of`` (``generation_ouro.py``).  The keras graph is the
cache-free forward: every pass over the whole sequence.

The exit gate (``Linear(d, 1)``) is held and not evaluated: at
``early_exit_threshold`` 1 every token runs every pass.  A threshold
below 1 (tokens leaving early, a pass count per request) is not
supported.  Training is not supported here (ROADMAP); the scan-based
``generate`` and beam search are ``TransformerLM``'s alone."""

from __future__ import annotations

import jax.numpy as jnp

from ..core import initializers
from ..core.module import Layer, register_layer
from ..pipeline.api.keras.engine import Model
from ..pipeline.api.keras.layers import (
    Activation, Dense, Embedding, GatedMLP, GroupedQueryAttention, Input,
    Merge, RMSNorm)
from . import generation_ouro as _family  # registers the family
from .common import ZooModel, register_zoo_model


@register_layer
class LoopExitGate(Layer):
    """The exit gate of a looped decoder, ``sigmoid(x W + b)`` over the
    hidden state after each pass: its weights are held, and at
    ``threshold`` 1 it is never evaluated (no token leaves before the
    last pass), so the layer passes its input on unchanged."""

    def __init__(self, threshold=1.0, init="glorot_uniform",
                 input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.threshold = float(threshold)
        if self.threshold < 1.0:
            raise ValueError(
                f"early exit below threshold 1 ({threshold}) is not "
                "supported: every token runs every pass")
        self.init_name = init

    def init_params(self, rng, input_shape):
        return {"W": initializers.get(self.init_name)(
                    rng, (input_shape[-1], 1)),
                "b": jnp.zeros((1,))}

    def call(self, params, state, inputs, training=False, rng=None):
        return inputs

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(threshold=self.threshold, init=self.init_name)
        return cfg


@register_zoo_model
class OuroLM(ZooModel):
    """Decoder-only looped language model of the ``ouro`` family.
    Output: (batch, seq_len, vocab_size) LOG-probabilities (compile with
    ``loss="class_nll"``); the logits under them are ``x W_head`` of the
    last pass's closed hidden state."""

    def __init__(self, vocab_size=None, seq_len=128, max_len=None,
                 n_layers=2, d_model=64, n_heads=4, n_kv_heads=None,
                 head_dim=None, d_ff=None, total_ut_steps=4,
                 rope_theta=1e6, rms_norm_eps=1e-6,
                 early_exit_threshold=1.0, name=None, **kw):
        if int(total_ut_steps) < 1:
            raise ValueError(f"total_ut_steps must be >= 1, got "
                             f"{total_ut_steps}")
        kw.pop("family", None)
        super().__init__(
            name=name, family=_family.NAME, vocab_size=vocab_size,
            seq_len=seq_len, max_len=max_len or seq_len, n_layers=n_layers,
            d_model=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads or n_heads,
            head_dim=head_dim or d_model // n_heads,
            d_ff=d_ff or 4 * d_model, total_ut_steps=int(total_ut_steps),
            rope_theta=float(rope_theta), rms_norm_eps=float(rms_norm_eps),
            early_exit_threshold=float(early_exit_threshold), **kw)

    def build_model(self) -> Model:
        h = self.hyper
        eps, n = h["rms_norm_eps"], h["n_layers"]
        tokens = Input(shape=(h["seq_len"],), name="tokens")
        # explicit names: the decode path (generation_ouro.py) reads these
        # params by layer name; each instance is called once a pass
        x = Embedding(h["vocab_size"], h["d_model"],
                      input_length=h["seq_len"], name="tok_embed")(tokens)
        blocks = [dict(
            ln_attn=RMSNorm(eps, name=f"ln_attn_{i}"),
            attn=GroupedQueryAttention(
                h["n_heads"], h["n_kv_heads"], h["head_dim"],
                rope_theta=h["rope_theta"], rope=_family.ROPE,
                name=f"attn_{i}"),
            ln_attn_out=RMSNorm(eps, name=f"ln_attn_out_{i}"),
            ln_mlp=RMSNorm(eps, name=f"ln_mlp_{i}"),
            mlp=GatedMLP(h["d_ff"], name=f"mlp_{i}"),
            ln_mlp_out=RMSNorm(eps, name=f"ln_mlp_out_{i}"))
            for i in range(n)]
        ln_final = RMSNorm(eps, name="ln_final")
        for _ in range(h["total_ut_steps"]):
            for blk in blocks:
                a = blk["ln_attn_out"](blk["attn"](blk["ln_attn"](x)))
                x = Merge(mode="sum")([x, a])
                m = blk["ln_mlp_out"](blk["mlp"](blk["ln_mlp"](x)))
                x = Merge(mode="sum")([x, m])
            x = ln_final(x)
        x = LoopExitGate(h["early_exit_threshold"], name="exit_gate")(x)
        logits = Dense(h["vocab_size"], bias=False, name="lm_head")(x)
        out = Activation("log_softmax")(logits)
        return Model(input=tokens, output=out, name="ouro_lm")

    def generate(self, *a, **kw):
        """Not this family's: the one-scan ``generate`` and beam search
        are written for ``TransformerLM``'s block and key/value cache."""
        raise ValueError(
            "OuroLM is served through the decode engine: "
            "InferenceModel(decode_capacity=...).load_keras_net(net), then "
            "generate / generate_stream; the scan-based generate() and "
            "beam search support TransformerLM only")
