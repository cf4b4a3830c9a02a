"""Embedding layers: Embedding, SparseEmbedding, WordEmbedding.

Parity surface: reference zoo/.../pipeline/api/keras/layers/{Embedding,
SparseEmbedding, WordEmbedding}.scala.  WordEmbedding reproduces the frozen
pretrained-GloVe path (WordEmbedding.scala:48-141): parse a GloVe text file
into an index + matrix, serve lookups from a non-trainable state buffer.

Lookups are ``jnp.take`` — XLA lowers them to efficient dynamic-gather on
TPU; embedding tables large enough to shard ride the standard param-sharding
rules in parallel/sharding.py.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .....core import initializers
from .....core.module import Layer, register_layer
from .. import regularizers


@register_layer
class Embedding(regularizers.RegularizedLayerMixin, Layer):
    """Trainable lookup table (reference Embedding.scala, incl. its
    wRegularizer arg)."""

    _reg_w_key = "embeddings"

    def __init__(self, input_dim, output_dim, init="uniform",
                 input_length=None, W_regularizer=None, input_shape=None,
                 name=None):
        if input_length is not None and input_shape is None:
            input_shape = (input_length,)
        super().__init__(input_shape=input_shape, name=name)
        self.input_dim = int(input_dim)
        self.output_dim = int(output_dim)
        self.init_name = init
        self._setup_regularizers(W_regularizer, None)

    def init_params(self, rng, input_shape):
        return {"embeddings": initializers.get(self.init_name)(
            rng, (self.input_dim, self.output_dim))}

    def call(self, params, state, inputs, training=False, rng=None):
        idx = inputs.astype(jnp.int32)
        y = jnp.take(params["embeddings"], idx, axis=0)
        if self.stateful:
            return y, {"aux_loss": self._penalty(params)}
        return y

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.output_dim,)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(input_dim=self.input_dim, output_dim=self.output_dim,
                   init=self.init_name,
                   W_regularizer=regularizers.to_config(
                       self.W_regularizer))
        return cfg


@register_layer
class SparseEmbedding(Embedding):
    """Embedding fed by sparse-style id bags (reference SparseEmbedding.scala).

    On TPU, ids arrive densely padded; semantics match Embedding.
    """


@register_layer
class WordEmbedding(Layer):
    """Frozen pretrained word embeddings (reference WordEmbedding.scala:48-141).

    The table lives in state (non-trainable), so the optimizer never touches
    it and it is replicated/sharded like any other buffer.
    """

    stateful = True

    def __init__(self, embedding_file=None, word_index=None, trainable=False,
                 input_length=None, input_shape=None, name=None,
                 _table=None, _output_dim=None):
        if input_length is not None and input_shape is None:
            input_shape = (input_length,)
        super().__init__(input_shape=input_shape, name=name)
        self.embedding_file = embedding_file
        self.word_index = word_index
        if _table is not None:
            self._table = np.asarray(_table, dtype=np.float32)
        elif embedding_file is not None:
            wi = word_index or WordEmbedding.get_word_index(embedding_file)
            self.word_index = wi
            self._table = _build_table(embedding_file, wi)
        else:
            raise ValueError("WordEmbedding needs embedding_file or _table")
        self.output_dim = self._table.shape[1]

    @staticmethod
    def get_word_index(embedding_file) -> Dict[str, int]:
        """Parse word→1-based-index from a GloVe-format file
        (reference WordEmbedding.scala:104-141)."""
        index = {}
        with open(embedding_file, "r", encoding="utf-8") as f:
            for i, line in enumerate(f):
                word = line.split(" ", 1)[0]
                index[word] = i + 1  # 0 reserved for padding/unknown
        return index

    def init_state(self, input_shape):
        return {"table": jnp.asarray(self._table)}

    def apply(self, params, state, inputs, training=False, rng=None):
        idx = inputs.astype(jnp.int32)
        return jnp.take(state["table"], idx, axis=0), state

    def call(self, params, state, inputs, training=False, rng=None):
        return self.apply(params, state, inputs, training=training,
                          rng=rng)[0]

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.output_dim,)

    def get_config(self):
        cfg = super().get_config()
        cfg["_table"] = np.asarray(self._table).tolist()
        return cfg


def _build_table(embedding_file, word_index) -> np.ndarray:
    """Rows ordered by index; row 0 is the zero (padding/unknown) vector."""
    vectors = {}
    dim = None
    with open(embedding_file, "r", encoding="utf-8") as f:
        for line in f:
            parts = line.rstrip().split(" ")
            word, vec = parts[0], np.asarray(parts[1:], dtype=np.float32)
            dim = dim or len(vec)
            if word in word_index:
                vectors[word_index[word]] = vec
    n = max(word_index.values()) + 1
    table = np.zeros((n, dim), dtype=np.float32)
    for idx, vec in vectors.items():
        table[idx] = vec
    return table


@register_layer
class TiedEmbedding(Layer):
    """One table for both ends of a language model (``tie_word_
    embeddings``): called on token ids ``(batch, seq)`` it looks them up
    (float32 out), called on hidden states ``(batch, seq, d)`` it is the
    output head, ``x E^T * logit_scale`` in the table's dtype with
    float32 accumulation.  Use the SAME instance at both nodes of the
    graph: a layer instance contributes one params entry."""

    def __init__(self, input_dim, output_dim, logit_scale=1.0,
                 init="uniform", input_length=None, input_shape=None,
                 name=None):
        if input_length is not None and input_shape is None:
            input_shape = (input_length,)
        super().__init__(input_shape=input_shape, name=name)
        self.input_dim, self.output_dim = int(input_dim), int(output_dim)
        self.logit_scale = float(logit_scale)
        self.init_name = init

    def init_params(self, rng, input_shape):
        return {"embeddings": initializers.get(self.init_name)(
            rng, (self.input_dim, self.output_dim))}

    def call(self, params, state, inputs, training=False, rng=None):
        table = params["embeddings"]
        if inputs.ndim == 2:
            return jnp.take(table, inputs.astype(jnp.int32),
                            axis=0).astype(jnp.float32)
        return jnp.einsum("bse,ve->bsv", inputs.astype(table.dtype), table,
                          preferred_element_type=jnp.float32) \
            * self.logit_scale

    def compute_output_shape(self, input_shape):
        if len(input_shape) == 2:
            return tuple(input_shape) + (self.output_dim,)
        return tuple(input_shape[:-1]) + (self.input_dim,)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(input_dim=self.input_dim, output_dim=self.output_dim,
                   logit_scale=self.logit_scale, init=self.init_name)
        return cfg
