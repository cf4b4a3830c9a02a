"""Padding-mask support through the attention stack (VERDICT r4 #4).

Right-padded variable-length batches — the reference's text domain pads
to a fixed sequenceLength (TextClassifier.scala:34) — must not attend to
pad tokens.  ``kv_lengths`` threads through naive/blockwise/flash (score
masking inside the pallas kernels, forward AND backward) and ring.  The
oracle is explicitly masked naive attention.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (
    attention, blockwise_attention, flash_attention, naive_attention)
from analytics_zoo_tpu.parallel.mesh import create_mesh
from analytics_zoo_tpu.parallel.ring_attention import ring_attention_sharded


def qkv(b=3, s=64, h=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(0, 1, (b, s, h, d)).astype(np.float32)
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


LENS = np.array([64, 37, 5])  # full, ragged, tiny


def explicit_masked_oracle(q, k, v, lens, causal):
    """Straight-line softmax with an explicit boolean mask — independent
    of the implementation under test (no shared kv_lengths code path)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q),
                       np.asarray(k)) / np.sqrt(d)
    mask = np.ones((b, 1, sq, sk), bool)
    for i, L in enumerate(lens):
        mask[i, :, :, L:] = False
    if causal:
        mask &= np.tril(np.ones((sq, sk), bool))[None, None]
    scores = np.where(mask, scores, -1e30)
    scores -= scores.max(-1, keepdims=True)
    p = np.exp(scores)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v))


@pytest.mark.parametrize("causal", [False, True])
def test_naive_kv_lengths_matches_explicit_mask(causal):
    q, k, v = qkv()
    ref = explicit_masked_oracle(q, k, v, LENS, causal)
    out = naive_attention(q, k, v, causal=causal, kv_lengths=LENS)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_kv_lengths_matches_naive(causal):
    q, k, v = qkv()
    ref = naive_attention(q, k, v, causal=causal, kv_lengths=LENS)
    out = blockwise_attention(q, k, v, causal=causal, block_k=16,
                              kv_lengths=LENS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kv_lengths_matches_naive(causal):
    """Kernel-level masking: lengths that straddle key-block boundaries
    (block_k=128 — the smallest block the TPU tiling rule admits;
    293 = 2 blocks + 37, 5 = partial first block)."""
    q, k, v = qkv(s=512)
    lens = np.array([512, 293, 5])
    ref = naive_attention(q, k, v, causal=causal, kv_lengths=lens)
    out = flash_attention(q, k, v, causal=causal, block_q=128,
                          block_k=128, interpret=True, kv_lengths=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_kv_lengths_matches_naive(causal):
    """The custom-VJP backward kernels replay the mask: dq/dk/dv must
    match autodiff through the masked naive oracle, and grads of padded
    keys/values must be exactly zero."""
    s = 256
    q, k, v = qkv(b=2, s=s, h=2, d=8, seed=1)
    lens = np.array([s, 139])  # full; one block + 11

    def loss_naive(q, k, v):
        # padded-query rows are garbage by contract: weight them zero,
        # as a sequence loss would
        o = naive_attention(q, k, v, causal=causal, kv_lengths=lens)
        w = (np.arange(s)[None, :, None, None]
             < lens[:, None, None, None])
        return jnp.sum(jnp.where(w, o, 0.0) ** 2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=128,
                            block_k=128, interpret=True, kv_lengths=lens)
        w = (np.arange(s)[None, :, None, None]
             < lens[:, None, None, None])
        return jnp.sum(jnp.where(w, o, 0.0) ** 2)

    g_ref = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for r, o in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=5e-4, atol=5e-5)
    # dk/dv of padded keys: exactly zero
    np.testing.assert_array_equal(np.asarray(g_out[1])[1, 139:], 0.0)
    np.testing.assert_array_equal(np.asarray(g_out[2])[1, 139:], 0.0)


def test_attention_dispatch_passes_lengths():
    q, k, v = qkv()
    ref = naive_attention(q, k, v, kv_lengths=LENS)
    for impl in ("naive", "blockwise", "auto"):
        out = attention(q, k, v, implementation=impl, kv_lengths=LENS)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_kv_lengths_validation():
    q, k, v = qkv()
    with pytest.raises(ValueError, match="kv_lengths"):
        naive_attention(q, k, v, kv_lengths=np.ones((3, 2)))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_kv_lengths_matches_naive(causal):
    """Global-position key masking across rotated shards: lengths that
    fall inside different devices' shards (8 devices × 8 positions)."""
    mesh = create_mesh({"seq": 8})
    q, k, v = qkv(b=3, s=64, h=2, d=16, seed=2)
    lens = np.array([64, 29, 3])  # shard 7 / mid shard 3 / inside shard 0
    ref = naive_attention(q, k, v, causal=causal, kv_lengths=lens)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                 kv_lengths=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_mhsa_layer_two_input_padded_batch():
    """Layer surface: [x, lengths] — outputs at valid positions must be
    INDEPENDENT of pad-row content, and match the single-input layer on
    the unpadded prefix."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Input, MultiHeadSelfAttention)

    zoo.init_nncontext()
    s, e = 16, 32
    x_in = Input(shape=(s, e), name="pm_x")
    len_in = Input(shape=(1,), name="pm_len")
    att = MultiHeadSelfAttention(n_heads=4, causal=False,
                                 implementation="naive",
                                 name="pm_att")([x_in, len_in])
    m = Model(input=[x_in, len_in], output=att)

    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, s, e)).astype(np.float32)
    lens = np.array([[16.0], [7.0]], np.float32)
    y1 = m.predict([x, lens], batch_size=2)
    # scribble over the padded tail of row 1: valid outputs unchanged
    x2 = x.copy()
    x2[1, 7:] = rng.normal(size=(s - 7, e)) * 50
    y2 = m.predict([x2, lens], batch_size=2)
    np.testing.assert_allclose(y1[1, :7], y2[1, :7], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(y1[0], y2[0], rtol=1e-4, atol=1e-5)


def test_mhsa_layer_padded_batch_trains():
    """Padded-batch encoder end-to-end: fit falls, and the model keeps
    the two-input contract through compile/fit/predict."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.api.keras import Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense, GlobalAveragePooling1D, Input, MultiHeadSelfAttention)

    zoo.init_nncontext()
    s, e = 16, 16
    x_in = Input(shape=(s, e), name="pt_x")
    len_in = Input(shape=(1,), name="pt_len")
    att = MultiHeadSelfAttention(n_heads=2, causal=False,
                                 implementation="naive",
                                 name="pt_att")([x_in, len_in])
    pooled = GlobalAveragePooling1D()(att)
    out = Dense(2, activation="softmax")(pooled)
    m = Model(input=[x_in, len_in], output=out)
    m.compile("adam", "categorical_crossentropy")

    rng = np.random.default_rng(4)
    n = 64
    x = rng.normal(size=(n, s, e)).astype(np.float32)
    lens = rng.integers(4, s + 1, size=(n, 1)).astype(np.float32)
    y = np.zeros((n, 2), np.float32)
    labels = rng.integers(0, 2, n)
    y[np.arange(n), labels] = 1.0
    hist = m.fit([x, lens], y, batch_size=16, nb_epoch=3)
    assert hist["loss"][-1] < hist["loss"][0] * 1.2
    p = m.predict([x, lens], batch_size=16)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, rtol=1e-4)
