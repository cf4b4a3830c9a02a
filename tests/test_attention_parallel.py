"""Attention implementations + ring attention + sharding/collectives tests.

The blockwise/pallas/ring variants must all match the naive oracle — the
TPU analogue of the reference's golden-oracle layer testing (SURVEY §4),
with the 8-device CPU mesh standing in for a slice.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.ops.attention import (
    attention, blockwise_attention, flash_attention, naive_attention)
from analytics_zoo_tpu.parallel.mesh import create_mesh
from analytics_zoo_tpu.parallel.ring_attention import ring_attention_sharded


def qkv(b=2, s=64, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(0, 1, (b, s, h, d)).astype(np.float32)
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_naive(causal):
    q, k, v = qkv()
    ref = naive_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_naive(causal):
    # 128 is the smallest block the TPU tiling rule admits (the rule
    # holds in interpret mode too): 2 x 2 blocks at s=256
    q, k, v = qkv(b=1, s=256, h=2, d=32)
    ref = naive_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128,
                          block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bhsd_layout_matches_bshd(causal):
    """VERDICT r3 #8: layout='bhsd' skips the materialized transposes;
    results must be identical to the default layout."""
    q, k, v = qkv(b=2, s=256, h=2, d=32)
    ref = flash_attention(q, k, v, causal=causal, block_q=128,
                          block_k=128, interpret=True)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=causal, block_q=128,
                          block_k=128, interpret=True, layout="bhsd")
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="layout"):
        flash_attention(q, k, v, layout="sbhd", interpret=True)


@pytest.mark.parametrize("causal,sq,sk,bq,bk", [
    (True, 256, 256, 128, 128),
    (False, 256, 256, 128, 128),
    (True, 128, 256, 128, 128),  # rectangular: cached-kv decode shape
    (True, 512, 512, 256, 128),  # uneven fwd blocks exercise bwd clamps
])
def test_flash_backward_matches_naive(causal, sq, sk, bq, bk):
    """The custom-VJP backward (pallas dq + dk/dv kernels) must match the
    naive oracle's autodiff — plain jax.grad of a pallas_call is
    unsupported, so this path is what on-chip LM TRAINING runs through;
    it was unreachable (AssertionError in pallas AD) until r5."""
    q = qkv(b=2, s=sq, h=2, d=32, seed=1)[0]
    _, k, v = qkv(b=2, s=sk, h=2, d=32, seed=2)
    rng = np.random.default_rng(9)
    ct = jnp.asarray(rng.normal(size=(2, sq, 2, 32)).astype(np.float32))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    ref = lambda q, k, v: naive_attention(q, k, v, causal=causal)
    out_f, vjp_f = jax.vjp(flash, q, k, v)
    out_n, vjp_n = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_n),
                               rtol=2e-4, atol=2e-5)
    for g_f, g_n in zip(vjp_f(ct), vjp_n(ct)):
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_n),
                                   rtol=2e-4, atol=2e-5)


def test_flash_causal_rejects_fully_masked_rows():
    """causal sq > sk: rows before the first key are fully masked; the
    backward replay would cancel the NEG_INF sentinel into phantom 1/n
    probabilities (code-review r5 finding) — flash raises, auto routes
    to blockwise, and the oracle parity holds there."""
    q = qkv(b=1, s=96, h=2, d=32, seed=5)[0]
    _, k, v = qkv(b=1, s=48, h=2, d=32, seed=6)
    with pytest.raises(ValueError, match="sq <= sk"):
        flash_attention(q, k, v, causal=True, interpret=True)
    out = attention(q, k, v, causal=True)  # auto: blockwise fallback
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(naive_attention(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal,sq,sk", [
    (True, 127, 127),    # prime, equal (training shape)
    (False, 127, 251),   # prime, cross (encoder cross-attention)
    (False, 131, 64),    # awkward q only
])
def test_flash_pads_awkward_lengths_matches_naive(causal, sq, sk):
    """Lengths with no tileable block divisor pad-and-mask inside
    flash_attention (r5; formerly a ValueError) — forward AND backward
    must match the naive oracle exactly, including with a kv_lengths
    ragged batch on top."""
    q = qkv(b=2, s=sq, h=2, d=16, seed=11)[0]
    _, k, v = qkv(b=2, s=sk, h=2, d=16, seed=12)
    for lens in (None, np.array([sk, max(1, sk // 3)])):
        ref = naive_attention(q, k, v, causal=causal, kv_lengths=lens)
        out = flash_attention(q, k, v, causal=causal, interpret=True,
                              kv_lengths=lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        # backward through the pad path too — a padded key block must
        # contribute exactly zero dk/dv even when lens < sk
        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, interpret=True,
            kv_lengths=lens) ** 2), argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(lambda q, k, v: jnp.sum(naive_attention(
            q, k, v, causal=causal, kv_lengths=lens) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


def test_flash_backward_prime_key_length_keeps_fwd_block():
    """sk=1009 (prime): the backward must not degenerate to a
    per-element grid — it falls back to the forward's block size."""
    q = qkv(b=1, s=64, h=1, d=16, seed=7)[0]
    _, k, v = qkv(b=1, s=1009, h=1, d=16, seed=8)
    loss = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=False, interpret=True) ** 2)
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(lambda q, k, v: jnp.sum(
        naive_attention(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_backward_bhsd_layout():
    """Gradients flow through the transpose-free layout fold too."""
    q, k, v = qkv(b=1, s=256, h=2, d=32, seed=4)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    loss_bhsd = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True,
        layout="bhsd") ** 2)
    loss_naive = lambda q, k, v: jnp.sum(
        naive_attention(q, k, v, causal=True) ** 2)
    g_f = jax.grad(loss_bhsd, argnums=(0, 1, 2))(qt, kt, vt)
    g_n = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(np.asarray(a.transpose(0, 2, 1, 3)),
                                   np.asarray(b), rtol=2e-4, atol=2e-5)


def test_mhsa_layer_trains_with_flash():
    """The layer-level path on-chip training uses: MultiHeadSelfAttention
    with implementation='flash' under jax.grad (interpret on CPU)."""
    import optax
    from analytics_zoo_tpu.pipeline.api.keras.engine import Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense, Input as KInput, MultiHeadSelfAttention)
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    from analytics_zoo_tpu.train.trainer import build_train_step

    x_in = KInput((32, 16), name="flash_train_in")
    h = MultiHeadSelfAttention(2, implementation="flash",
                               name="flash_train_attn")(x_in)
    graph = Model(input=x_in, output=Dense(4)(h)).to_graph()
    params, state = graph.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = build_train_step(graph, objectives.get("mse"), opt)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32, 16)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(4, 32, 4)).astype(np.float32))
    losses = []
    for _ in range(8):
        params, state, opt_state, loss = step(
            params, state, opt_state, jax.random.PRNGKey(1), x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # gradients are real and useful


def test_attention_dispatch_and_validation():
    q, k, v = qkv(s=32)
    out = attention(q, k, v, implementation="blockwise")
    assert out.shape == q.shape
    with pytest.raises(ValueError, match="must divide"):
        blockwise_attention(q, k, v, block_k=7)
    with pytest.raises(ValueError, match="Unknown implementation"):
        attention(q, k, v, implementation="warp")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_naive(causal):
    """8-way sequence parallelism must be numerically equivalent."""
    mesh = create_mesh({"seq": 8})
    q, k, v = qkv(b=2, s=64, h=2, d=8)
    ref = naive_attention(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, axis_name="seq",
                                 causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_long_sequence_memory_shape():
    """Long-context smoke: 8k tokens over 8 shards, local seq 1k."""
    mesh = create_mesh({"seq": 8})
    rng = np.random.default_rng(0)
    shape = (1, 8192, 2, 16)
    q = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    out = ring_attention_sharded(q, q, q, mesh, causal=True)
    assert out.shape == shape
    assert np.isfinite(np.asarray(out[0, :4])).all()


def test_fsdp_sharding_rules():
    from analytics_zoo_tpu.parallel import sharding as sh
    mesh = create_mesh({"data": 2, "fsdp": 4})
    params = {"big": np.zeros((512, 64)), "small": np.zeros((4, 4))}
    tree = sh.fsdp_tree(params, mesh, min_size=1024)
    assert tree["big"].spec == P("fsdp", None)   # 512 % 4 == 0 on axis 0
    assert tree["small"].spec == P()             # too small, replicated


def test_tensor_parallel_rules():
    from analytics_zoo_tpu.parallel import sharding as sh
    mesh = create_mesh({"data": 4, "tensor": 2})
    params = {"layer1": {"W": np.zeros((64, 32)), "b": np.zeros((32,))},
              "other": {"W": np.zeros((64, 32))}}
    tree = sh.tensor_parallel_tree(params, mesh, {r"layer1/W": 1})
    assert tree["layer1"]["W"].spec == P(None, "tensor")
    assert tree["layer1"]["b"].spec == P()
    assert tree["other"]["W"].spec == P()


def test_data_parallel_training_equivalence():
    """DP over 8 devices must match single-device training numerically —
    the invariant the reference's AllReduce design guarantees
    (wp-bigdl.md:113-160)."""
    import optax
    from analytics_zoo_tpu.core.graph import Input
    from analytics_zoo_tpu.pipeline.api.keras.engine import Model
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    from analytics_zoo_tpu.train.trainer import build_train_step
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    def run(devices):
        mesh = create_mesh({"data": devices},
                           devices=jax.devices()[:devices])
        x_in = Input((8,), name=f"dp_in_{devices}")
        graph = Model(input=x_in,
                      output=Dense(4, name=f"dp_d_{devices}")(x_in)
                      ).to_graph()
        params, state = graph.init(jax.random.PRNGKey(7))
        opt = optax.sgd(0.1)
        opt_state = opt.init(params)
        step = build_train_step(graph, objectives.get("mse"), opt)
        rng = np.random.default_rng(3)
        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = rng.normal(size=(32, 4)).astype(np.float32)
        bs = mesh_lib.data_sharding(mesh)
        params = jax.device_put(params, mesh_lib.replicated(mesh))
        xs = jax.device_put(x, bs)
        ys = jax.device_put(y, bs)
        for _ in range(5):
            params, state, opt_state, loss = step(
                params, state, opt_state, jax.random.PRNGKey(0), xs, ys)
        return jax.device_get(params), float(loss)

    p1, l1 = run(1)
    p8, l8 = run(8)
    assert l1 == pytest.approx(l8, rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p8)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_attention_auto_odd_lengths():
    """Regression: auto dispatch on non-128-divisible and prime lengths."""
    q600, k600, v600 = qkv(b=1, s=600, h=2, d=8, seed=2)
    ref = naive_attention(q600, k600, v600, causal=True)
    out = attention(q600, k600, v600, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    q7, k7, v7 = qkv(b=1, s=7, h=2, d=8, seed=3)
    out = attention(q7, k7, v7)  # prime length falls back to naive
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(naive_attention(q7, k7, v7)),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("batch,lens", [
    (4, None),          # batch splits over data x fsdp
    (4, [128, 70, 5, 33]),
    (3, None),          # 4 does not divide 3: every device runs it whole
])
def test_flash_under_trainer_mesh_matches_unsharded(batch, lens):
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device
    Trainer mesh the kernel runs inside shard_map on each device's
    slice of batch/heads — forward and backward must equal the same
    kernel run whole (no term crosses batch or heads)."""
    from analytics_zoo_tpu.ops.attention import attention_bhsd
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    mesh = create_mesh({"data": 2, "fsdp": 2}, devices=jax.devices()[:4])
    q, k, v = (a.transpose(0, 2, 1, 3)
               for a in qkv(b=batch, s=128, h=2, d=16, seed=21))
    lens = None if lens is None else jnp.asarray(lens, jnp.int32)

    def loss(q, k, v):
        return jnp.sum(attention_bhsd(q, k, v, causal=True,
                                      implementation="flash",
                                      kv_lengths=lens) ** 2)

    want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert mesh_lib.get_step_mesh() is None
    with mesh_lib.active_mesh(mesh):
        got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert mesh_lib.get_step_mesh() is None
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        # two separately compiled programs: float tolerance, not bits
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
