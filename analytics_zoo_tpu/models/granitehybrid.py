"""GraniteHybridLM: the ``granitemoehybrid`` family without experts
(Granite 4.0-H), a decoder of Mamba-2 state-space layers with a few
grouped-query attention layers among them.

Every layer is two pre-norm residual blocks, each scaled by
``residual_multiplier``:

    x = x + m * Mixer(RMSNorm(x))        Mixer: Mamba-2, or attention
    x = x + m * MLP(RMSNorm(x))          SwiGLU, one fused input_linear

``layer_types`` says which layers are ``"mamba"`` and which
``"attention"``.  Attention layers carry no positions and scale their
softmax by ``attention_multiplier``; the Mamba layers are
``ops/ssm.py``'s.  The embedding is multiplied by
``embedding_multiplier`` on the way in, and the tied head divides its
logits by ``logits_scaling``.

Built from the framework's own layers (``TiedEmbedding``, ``RMSNorm``,
``Mamba2Mixer``, ``GroupedQueryAttention``, ``GatedMLP``,
``MulConstant``, ``Merge``), so ``compile`` / ``predict`` /
``InferenceModel.load_keras_net`` / ``generate_stream`` work as for the
other families; the decode engine gets this family's prefill, decode
step and per-slot state through ``models.generation.family_of``
(``generation_granitehybrid.py``).  Training is not supported (the scan
has no backward kernel: ROADMAP M5); the scan-based ``generate`` and
beam search are ``TransformerLM``'s alone."""

from __future__ import annotations

from ..pipeline.api.keras.engine import Model
from ..pipeline.api.keras.layers import (
    Activation, GatedMLP, GroupedQueryAttention, Input, Mamba2Mixer, Merge,
    MulConstant, RMSNorm, TiedEmbedding)
from . import generation_granitehybrid as _family  # registers the family
from .common import ZooModel, register_zoo_model

#: the published pattern's period: five Mamba layers, one attention
#: layer, four Mamba layers
LAYER_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@register_zoo_model
class GraniteHybridLM(ZooModel):
    """Decoder-only language model of the ``granitemoehybrid`` family
    (dense).  Output: (batch, seq_len, vocab_size) LOG-probabilities
    (compile with ``loss="class_nll"``); the logits under them are
    ``RMSNorm_f(x) Emb^T / logits_scaling``."""

    def __init__(self, vocab_size=None, seq_len=128, max_len=None,
                 n_layers=10, d_model=64, n_heads=4, n_kv_heads=2,
                 head_dim=None, d_ff=None, layer_types=None,
                 mamba_n_heads=4, mamba_head_dim=None, mamba_d_state=16,
                 mamba_d_conv=4, mamba_chunk=256, rms_norm_eps=1e-5,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 attention_multiplier=None, logits_scaling=1.0, name=None,
                 **kw):
        kinds = list(layer_types) if layer_types else [
            LAYER_PERIOD[i % len(LAYER_PERIOD)] for i in range(n_layers)]
        if len(kinds) < n_layers or set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {kinds} do not name {n_layers} "
                             "'mamba' / 'attention' layers")
        head_dim = head_dim or d_model // n_heads
        kw.pop("family", None)
        super().__init__(
            name=name, family=_family.NAME, vocab_size=vocab_size,
            seq_len=seq_len, max_len=max_len or seq_len, n_layers=n_layers,
            d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, d_ff=d_ff or 4 * d_model,
            layer_types=kinds[:n_layers], mamba_n_heads=mamba_n_heads,
            mamba_head_dim=mamba_head_dim or 2 * d_model // mamba_n_heads,
            mamba_d_state=mamba_d_state, mamba_d_conv=mamba_d_conv,
            mamba_chunk=mamba_chunk, rms_norm_eps=float(rms_norm_eps),
            embedding_multiplier=float(embedding_multiplier),
            residual_multiplier=float(residual_multiplier),
            attention_multiplier=float(attention_multiplier
                                       or head_dim ** -0.5),
            logits_scaling=float(logits_scaling), **kw)

    def build_model(self) -> Model:
        h = self.hyper
        eps, res = h["rms_norm_eps"], h["residual_multiplier"]
        tokens = Input(shape=(h["seq_len"],), name="tokens")
        # explicit names: the decode path (generation_granitehybrid.py)
        # reads these params by layer name
        table = TiedEmbedding(h["vocab_size"], h["d_model"],
                              logit_scale=1.0 / h["logits_scaling"],
                              input_length=h["seq_len"], name="tok_embed")
        x = MulConstant(h["embedding_multiplier"])(table(tokens))
        for i, kind in enumerate(h["layer_types"]):
            n = RMSNorm(eps, name=f"ln_{i}")(x)
            if kind == "mamba":
                a = Mamba2Mixer(h["mamba_n_heads"], h["mamba_head_dim"],
                                h["mamba_d_state"], h["mamba_d_conv"],
                                h["mamba_chunk"], eps, name=f"mamba_{i}")(n)
            else:
                a = GroupedQueryAttention(
                    h["n_heads"], h["n_kv_heads"], h["head_dim"],
                    scale=h["attention_multiplier"], name=f"attn_{i}")(n)
            x = Merge(mode="sum")([x, MulConstant(res)(a)])
            n = RMSNorm(eps, name=f"ln_mlp_{i}")(x)
            m = GatedMLP(h["d_ff"], name=f"mlp_{i}")(n)
            x = Merge(mode="sum")([x, MulConstant(res)(m)])
        x = RMSNorm(eps, name="ln_final")(x)
        out = Activation("log_softmax")(table(x))
        return Model(input=tokens, output=out, name="granite_hybrid_lm")

    def generate(self, *a, **kw):
        """Not this family's: the one-scan ``generate`` and beam search
        are written for ``TransformerLM``'s block and key/value cache."""
        raise ValueError(
            "GraniteHybridLM is served through the decode engine: "
            "InferenceModel(decode_capacity=...).load_keras_net(net), then "
            "generate / generate_stream; the scan-based generate() and "
            "beam search support TransformerLM only")
