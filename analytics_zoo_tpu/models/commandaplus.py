"""CommandAPlusLM: the text side of the ``cohere2_moe`` family (Command
A+), a second decoder family beside ``TransformerLM``.

Every layer is a PARALLEL block: one bias-free LayerNorm feeds both
grouped-query attention and a top-k, sigmoid-routed mixture of gated
experts with averaged shared experts beside them, and both results add
to the residual, ``x' = x + a + m``.  ``sliding_attention`` layers turn
queries and keys by rotary positions (interleaved pairs) and see the
last ``sliding_window`` keys; ``full_attention`` layers carry no
positions at all.  Embeddings are tied: the head is the embedding table
transposed, times ``logit_scale``.  There is no positional table.

Built from the framework's own layers (``TiedEmbedding``, ``LayerNorm(
bias=False)``, ``GroupedQueryAttention``, ``TopKMoE``, ``Merge``), so
``compile`` / ``predict`` / ``InferenceModel.load_keras_net`` /
``generate_stream`` work as for ``TransformerLM``; the decode engine
gets this family's prefill and decode step through
``models.generation.family_of`` (``generation_cohere2moe.py``).

``experts_held = (first, count)``: the contiguous range of each layer's
routed experts whose weights this model holds: one chip's share of an
expert-parallel deployment.  The router keeps all ``n_experts`` outputs
and its ``top_k``; the layer adds the held experts' part of the routed
sum.  Default: all of them.

Training: ``compile`` + ``fit`` run (the graph is differentiable off
the kernel path), but nothing here balances the router and the
attention kernel has no backward for a window or groups: training this
family is not supported yet (ROADMAP M1).  The scan-based ``generate``
and beam search are ``TransformerLM``'s alone; serve this family through
the decode engine.
"""

from __future__ import annotations

from ..pipeline.api.keras.engine import Model
from ..pipeline.api.keras.layers import (
    Activation, GroupedQueryAttention, Input, LayerNorm, Merge,
    TiedEmbedding, TopKMoE)
from . import generation_cohere2moe as _family  # registers the family
from .common import ZooModel, register_zoo_model

#: the published pattern's period: three windowed layers to one full
LAYER_PERIOD = ("sliding_attention", "sliding_attention",
                "sliding_attention", "full_attention")


@register_zoo_model
class CommandAPlusLM(ZooModel):
    """Decoder-only language model of the ``cohere2_moe`` family.

    Output: (batch, seq_len, vocab_size) LOG-probabilities (compile with
    ``loss="class_nll"``), as ``TransformerLM``; the logits under them
    are ``LN_f(x) Emb^T * logit_scale``."""

    def __init__(self, vocab_size=None, seq_len=128, max_len=None,
                 n_layers=4, d_model=128, n_heads=8, n_kv_heads=2,
                 head_dim=None, d_ff=None, n_experts=8, top_k=2,
                 n_shared=1, experts_held=None, sliding_window=64,
                 layer_types=None, rope_theta=50000.0,
                 layer_norm_eps=1e-5, logit_scale=1.0, name=None, **kw):
        kinds = list(layer_types) if layer_types else [
            LAYER_PERIOD[i % len(LAYER_PERIOD)] for i in range(n_layers)]
        if len(kinds) < n_layers:
            raise ValueError(f"layer_types names {len(kinds)} layers, "
                             f"n_layers is {n_layers}")
        held = tuple(experts_held) if experts_held else (0, n_experts)
        kw.pop("family", None)
        super().__init__(
            name=name, family=_family.NAME, vocab_size=vocab_size,
            seq_len=seq_len, max_len=max_len or seq_len, n_layers=n_layers,
            d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim or d_model // n_heads, d_ff=d_ff or d_model,
            n_experts=n_experts, top_k=top_k, n_shared=n_shared,
            experts_held=[int(held[0]), int(held[1])],
            sliding_window=sliding_window, layer_types=kinds[:n_layers],
            rope_theta=float(rope_theta),
            layer_norm_eps=float(layer_norm_eps),
            logit_scale=float(logit_scale), **kw)

    def build_model(self) -> Model:
        h = self.hyper
        tokens = Input(shape=(h["seq_len"],), name="tokens")
        # explicit names: the decode path (generation_cohere2moe.py)
        # reads these params by layer name
        table = TiedEmbedding(h["vocab_size"], h["d_model"],
                              logit_scale=h["logit_scale"],
                              input_length=h["seq_len"], name="tok_embed")
        x = table(tokens)
        for i, kind in enumerate(h["layer_types"]):
            sliding = kind == "sliding_attention"
            n = LayerNorm(h["layer_norm_eps"], bias=False,
                          name=f"ln_{i}")(x)
            a = GroupedQueryAttention(
                h["n_heads"], h["n_kv_heads"], h["head_dim"],
                rope_theta=h["rope_theta"] if sliding else None,
                window=h["sliding_window"] if sliding else None,
                name=f"attn_{i}")(n)
            m = TopKMoE(h["n_experts"], h["top_k"], h["d_ff"],
                        n_shared=h["n_shared"],
                        experts_held=tuple(h["experts_held"]),
                        name=f"moe_{i}")(n)
            x = Merge(mode="sum")([x, a, m])
        x = LayerNorm(h["layer_norm_eps"], bias=False, name="ln_final")(x)
        out = Activation("log_softmax")(table(x))
        return Model(input=tokens, output=out, name="command_a_plus_lm")

    def generate(self, *a, **kw):
        """Not this family's: the one-scan ``generate`` and beam search
        are written for ``TransformerLM``'s block."""
        raise ValueError(
            "CommandAPlusLM is served through the decode engine: "
            "InferenceModel(decode_capacity=...).load_keras_net(net), then "
            "generate / generate_stream; the scan-based generate() and "
            "beam search support TransformerLM only")
