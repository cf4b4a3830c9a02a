"""Attention layers — the TPU-era extension the reference lacks
(SURVEY §5: "attention does not exist in the layer set"); long-context
support is first-class here, so the ops-level stack
(``ops/attention.py`` flash kernel, ``parallel/ring_attention``) gets a
Keras-level consumer.

Design note (the transpose-tax fix of round 4): q/k/v are projected
DIRECTLY into the (batch, heads, seq, head_dim) layout via
``einsum("bse,ehd->bhsd", x, W)`` — XLA folds the layout into the
projection matmul's output, and the pallas kernel's batch/head fold
becomes a free reshape.  No materialized (b,s,h,d)→(b,h,s,d) transposes
anywhere in the block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .....core import initializers
from .....core.module import Layer, register_layer
from .....ops.attention import (ROPES, attention_bhsd, attention_gqa_bhsd,
                                gqa_qkv, scale_queries)


@register_layer
class MultiHeadSelfAttention(Layer):
    """Multi-head self-attention over (batch, seq, d_model) inputs.

    - ``n_heads`` × ``head_dim`` (default ``d_model // n_heads``)
    - ``causal=True`` masks future positions (decoder-style)
    - ``implementation``: "auto" (pallas flash kernel on TPU, blockwise
      XLA elsewhere), "flash", "blockwise", "naive", or "ring" —
      sequence-parallel ring attention over the mesh's ``seq`` axis
      (``parallel/ring_attention``): activations stay sharded along the
      sequence, KV blocks rotate around the ring, so contexts beyond
      one chip's memory train like any other layer.  Requires the
      active mesh to carry a ``seq`` axis.

    Padding masks (right-padded variable-length batches — the
    reference's text domain pads to a fixed sequenceLength,
    TextClassifier.scala:34): pass a TWO-input list ``[x, lengths]``
    where ``lengths`` is (batch,) valid token counts.  Keys past each
    sequence's length are masked in every implementation (including
    inside the pallas flash kernels and across the ring); padded QUERY
    positions still emit (garbage) outputs — mask them downstream, as
    sequence losses and masked pooling do.  Composes with ``causal``.
    """

    def __init__(self, n_heads, head_dim=None, causal=True,
                 implementation="auto", init="glorot_uniform",
                 input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.n_heads = int(n_heads)
        self.head_dim = None if head_dim is None else int(head_dim)
        self.causal = bool(causal)
        self.implementation = implementation
        self.init_name = init

    def _dims(self, d_model):
        hd = self.head_dim or d_model // self.n_heads
        if hd * self.n_heads != d_model and self.head_dim is None:
            raise ValueError(
                f"d_model ({d_model}) not divisible by n_heads "
                f"({self.n_heads}); pass head_dim explicitly")
        return hd

    def init_params(self, rng, input_shape):
        if (isinstance(input_shape, (list, tuple)) and input_shape
                and isinstance(input_shape[0], (list, tuple))):
            input_shape = input_shape[0]  # [x, lengths] two-input form
        d_model = input_shape[-1]
        hd = self._dims(d_model)
        init = initializers.get(self.init_name)
        ks = jax.random.split(rng, 4)
        return {
            # (d_model, heads, head_dim): the bhsd projection layout
            "Wq": init(ks[0], (d_model, self.n_heads, hd)),
            "Wk": init(ks[1], (d_model, self.n_heads, hd)),
            "Wv": init(ks[2], (d_model, self.n_heads, hd)),
            # (heads, head_dim, d_model): output projection
            "Wo": init(ks[3], (self.n_heads, hd, d_model)),
        }

    def call(self, params, state, inputs, training=False, rng=None):
        lengths = None
        if isinstance(inputs, (list, tuple)):
            if len(inputs) != 2:
                raise ValueError(
                    "MultiHeadSelfAttention takes either one input "
                    "(batch, seq, d_model) or two ([x, lengths]); got "
                    f"{len(inputs)} inputs")
            inputs, lengths = inputs
            if lengths.ndim == 2 and lengths.shape[-1] == 1:
                lengths = lengths[:, 0]  # accept (batch, 1) columns
        if self.implementation == "ring":
            # sequence parallelism: project into the ring kernel's
            # (b, s, h, d) contract — still a pure einsum, no transpose
            from .....parallel.mesh import get_active_mesh
            from .....parallel.ring_attention import ring_attention_sharded
            # the ACTIVE mesh: the one compile(mesh=...) handed the
            # Trainer (set around every step trace/call), falling back
            # to the process default
            mesh = get_active_mesh()
            if mesh is None or "seq" not in mesh.axis_names:
                raise ValueError(
                    "implementation='ring' needs the active mesh to "
                    "carry a 'seq' axis (create_mesh({'seq': n, ...}))")
            seq_size = mesh.shape["seq"]
            if inputs.shape[-2] % seq_size:
                raise ValueError(
                    f"sequence length {inputs.shape[-2]} is not "
                    f"divisible by the mesh's seq axis ({seq_size})")
            q = jnp.einsum("bse,ehd->bshd", inputs, params["Wq"])
            k = jnp.einsum("bse,ehd->bshd", inputs, params["Wk"])
            v = jnp.einsum("bse,ehd->bshd", inputs, params["Wv"])
            o = ring_attention_sharded(q, k, v, mesh, causal=self.causal,
                                       kv_lengths=lengths)
            return jnp.einsum("bshd,hde->bse", o, params["Wo"])
        # project straight into (b, h, s, d) — layout rides the matmul
        q = jnp.einsum("bse,ehd->bhsd", inputs, params["Wq"])
        k = jnp.einsum("bse,ehd->bhsd", inputs, params["Wk"])
        v = jnp.einsum("bse,ehd->bhsd", inputs, params["Wv"])
        o = attention_bhsd(q, k, v, causal=self.causal,
                           implementation=self.implementation,
                           kv_lengths=lengths)
        return jnp.einsum("bhsd,hde->bse", o, params["Wo"])

    def compute_output_shape(self, input_shape):
        if (isinstance(input_shape, (list, tuple)) and input_shape
                and isinstance(input_shape[0], (list, tuple))):
            return tuple(input_shape[0])  # [x, lengths] two-input form
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(n_heads=self.n_heads, head_dim=self.head_dim,
                   causal=self.causal, implementation=self.implementation,
                   init=self.init_name)
        return cfg


@register_layer
class PositionalEmbedding(Layer):
    """Learned positional table added to a (batch, seq, d_model) input:
    ``y = x + table[:seq]``.  ``max_len`` bounds the trainable table;
    shorter sequences slice it (static shapes under jit)."""

    def __init__(self, max_len, init="uniform", input_shape=None,
                 name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.max_len = int(max_len)
        self.init_name = init

    def init_params(self, rng, input_shape):
        d_model = input_shape[-1]
        table = initializers.get(self.init_name)(
            rng, (self.max_len, d_model))
        return {"table": table * 0.02 if self.init_name == "uniform"
                else table}

    def call(self, params, state, inputs, training=False, rng=None):
        s = inputs.shape[-2]
        if s > self.max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_len {self.max_len}")
        return inputs + params["table"][:s].astype(inputs.dtype)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(max_len=self.max_len, init=self.init_name)
        return cfg


@register_layer
class GroupedQueryAttention(Layer):
    """Causal self-attention with ``n_heads`` query heads over
    ``n_kv_heads`` key/value heads (query head h reads key/value head
    ``h // (n_heads // n_kv_heads)``), no biases.  ``rope_theta``:
    rotary positions over all ``head_dim`` dims, in interleaved pairs
    (``rope="interleaved"``) or by rotate-half (``rope="half"``);
    ``None``: no positions at all.  ``window``: query i sees keys j with
    ``j <= i`` and ``i - j < window``; ``None``: every ``j <= i``.
    Products run in the weights' dtype with float32 accumulation, the
    softmax in float32; the output is float32.  Forward only on the
    chip's kernel path (``ops.attention.attention_gqa_bhsd``).
    ``scale``: the softmax's scale where it is not ``1 / sqrt(head_dim)``
    (folded into the queries: ``ops.attention.scale_queries``)."""

    def __init__(self, n_heads, n_kv_heads, head_dim, rope_theta=None,
                 window=None, init="glorot_uniform", scale=None,
                 rope="interleaved", input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = int(head_dim)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads ({n_heads}) is not a multiple of "
                             f"n_kv_heads ({n_kv_heads})")
        if rope not in ROPES:
            raise ValueError(f"rope {rope!r} is not one of {sorted(ROPES)}")
        self.rope = rope
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.window = None if window is None else int(window)
        self.scale = None if scale is None else float(scale)
        self.init_name = init

    def init_params(self, rng, input_shape):
        d_model, hd = input_shape[-1], self.head_dim
        init = initializers.get(self.init_name)
        ks = jax.random.split(rng, 4)
        return {"Wq": init(ks[0], (d_model, self.n_heads, hd)),
                "Wk": init(ks[1], (d_model, self.n_kv_heads, hd)),
                "Wv": init(ks[2], (d_model, self.n_kv_heads, hd)),
                "Wo": init(ks[3], (self.n_heads, hd, d_model))}

    def call(self, params, state, inputs, training=False, rng=None):
        q, k, v = gqa_qkv(params, inputs, jnp.arange(inputs.shape[1]),
                          self.rope_theta, self.rope)
        if self.scale is not None:
            q = scale_queries(q, self.scale)
        o = attention_gqa_bhsd(q, k, v, window=self.window)
        return jnp.einsum("bhsd,hde->bse", o, params["Wo"],
                          preferred_element_type=jnp.float32)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                   head_dim=self.head_dim, rope_theta=self.rope_theta,
                   window=self.window, init=self.init_name)
        if self.scale is not None:
            cfg["scale"] = self.scale   # omitted when None (byte-stability)
        if self.rope != "interleaved":
            cfg["rope"] = self.rope     # likewise
        return cfg
