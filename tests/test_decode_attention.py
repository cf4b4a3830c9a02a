"""The decode step's attention op and the key/value slab it reads
(``ops/attention.py``): the pallas kernel ``zoo_decode_attn`` in
interpret mode against the ``jax.numpy`` op it replaces on the chip, the
slab's layout functions, the eligibility rule, and the decode engine run
through the kernel on the CPU.

What the chip's compiler makes of the kernel is in
``test_tpu_compile.py``."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

# ops/__init__ re-exports a function named ``attention``: import the module
A = importlib.import_module("analytics_zoo_tpu.ops.attention")


def case(b, t, heads, d_head, seed=0):
    rng = np.random.default_rng(seed)
    row = heads * d_head
    ck, cv = (jnp.asarray(rng.standard_normal((b, t, row)), jnp.float32)
              for _ in range(2))
    q, kn, vn = (jnp.asarray(rng.standard_normal((b, row)), jnp.float32)
                 for _ in range(3))
    return q, kn, vn, ck, cv


def kernel(q, kn, vn, ck, cv, pos, heads, block):
    return A._decode_attn_call(q, kn, vn, ck, cv,
                               jnp.asarray(pos, jnp.int32), heads, block,
                               True)


@pytest.fixture
def through_the_kernel(monkeypatch):
    """``decode_attention`` as on the chip, the kernel interpreted: the
    platform test says TPU, the prefill stays off the flash kernel."""
    real = A._decode_attn_call
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        A, "_decode_attn_call",
        lambda *a, interpret, **kw: real(*a, interpret=True, **kw))
    monkeypatch.setattr(A, "_auto_implementation",
                        lambda *a, **kw: "naive")


# ------------------------------------------------ kernel against the op
@pytest.mark.parametrize("heads,d_head", [(2, 64), (4, 32), (16, 16),
                                          (1, 256)])
@pytest.mark.parametrize("block", [128, 256])
def test_kernel_matches_the_op_over_ragged_lengths(block, heads, d_head):
    """Lengths 1, block - 1, block, block + 1 and max_len in one call:
    a slot with nothing cached, one that ends inside a block, on its
    edge, just past it, and a full one."""
    t = 3 * block
    lengths = np.array([1, block - 1, block, block + 1, t])
    q, kn, vn, ck, cv = case(len(lengths), t, heads, d_head)
    pos = lengths - 1
    want = A._decode_attention_reference(q, kn, vn, ck, cv,
                                         jnp.asarray(pos), heads)
    got = kernel(q, kn, vn, ck, cv, pos, heads, block)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-6)
    for g, w in zip(got[1:], want[1:]):     # the slabs: bit for bit
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("length", [1, 5, 127, 128, 129, 200])
def test_rows_past_the_length_are_neither_read_nor_written(length):
    """Poison every row from the length on (the new row's place
    included) with NaN: the output stays finite and equals the op's on
    the clean slab, and the poison is still there, except in the new
    row."""
    block, heads, t = 128, 2, 384
    q, kn, vn, ck, cv = case(2, t, heads, 64, seed=length)
    pos = np.array([length - 1, length - 1])
    want = A._decode_attention_reference(q, kn, vn, ck, cv,
                                         jnp.asarray(pos), heads)[0]
    dead = jnp.arange(t)[None, :, None] >= length - 1
    o, k2, v2 = kernel(q, kn, vn, jnp.where(dead, jnp.nan, ck),
                       jnp.where(dead, jnp.nan, cv), pos, heads, block)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want, rtol=2e-5, atol=2e-6)
    for slab, new, old in ((k2, kn, ck), (v2, vn, cv)):
        slab = np.asarray(slab)
        np.testing.assert_array_equal(slab[:, length - 1], new)
        assert np.isnan(slab[:, length:]).all()
        np.testing.assert_array_equal(slab[:, :length - 1],
                                      np.asarray(old)[:, :length - 1])


@pytest.mark.parametrize("pos", [0, 7, 8, 130, 255])
def test_the_new_row_is_written_in_place(pos):
    """The kernel's slabs ARE its operands (``input_output_aliases``):
    one row changes, and no copy of a slab is made on the way."""
    heads, t = 2, 256
    q, kn, vn, ck, cv = case(3, t, heads, 64, seed=pos)
    p = np.array([pos, 0, t - 1])
    _, k2, v2 = kernel(q, kn, vn, ck, cv, p, heads, 128)
    np.testing.assert_array_equal(k2, A.kv_write_row(ck, kn, jnp.asarray(p)))
    np.testing.assert_array_equal(v2, A.kv_write_row(cv, vn, jnp.asarray(p)))
    text = str(jax.make_jaxpr(
        lambda *a: kernel(*a, p, heads, 128))(q, kn, vn, ck, cv))
    assert "input_output_aliases=((7, 2), (8, 3))" in text


def test_a_shared_position_is_the_per_row_one():
    """``generate``'s scan steps every row at one scalar position: the
    same numbers as a vector of that position."""
    heads, t = 4, 64
    q, kn, vn, ck, cv = case(3, t, heads, 8)
    one = A.decode_attention(q, kn, vn, ck, cv, 17, heads)
    each = A.decode_attention(q, kn, vn, ck, cv,
                              jnp.full((3,), 17, jnp.int32), heads)
    for a, b in zip(one, each):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------- the eligibility
@pytest.mark.parametrize("slots,max_len,row,heads,dtype,block", [
    (16, 1024, 1024, 16, jnp.float32, 128),     # the benchmark's engine
    (32, 1024, 1024, 16, jnp.float32, 128),
    (2, 256, 128, 2, jnp.float32, 128),
    (4, 256, 256, 1, jnp.float32, 128),         # a head of two lane tiles
    (16, 1000, 1024, 16, jnp.float32, None),    # not whole blocks
    (16, 48, 1024, 16, jnp.float32, None),      # generate()'s own cache
    (16, 1024, 64, 1, jnp.float32, None),       # a row of half a lane tile
    (16, 1024, 192, 3, jnp.float32, None),      # 192 is not 128 lanes
    (16, 1024, 384, 4, jnp.float32, None),      # heads of 96 straddle tiles
    (16, 1024, 1024, 16, jnp.bfloat16, None),   # the 8-row tile is float32's
    (512, 1024, 1024, 16, jnp.float32, None),   # operands past the VMEM
])
def test_decode_plan(slots, max_len, row, heads, dtype, block):
    plan, why = A._decode_plan(slots, max_len, row, heads, dtype)
    assert plan == block
    assert (why is None) == (block is not None)


def test_off_the_chip_the_op_is_the_masked_softmax(monkeypatch):
    """On the CPU, and on the chip for a shape the plan refuses, no
    kernel: the engine then counts a whole slab a step as read."""
    q, kn, vn, ck, cv = case(2, 256, 2, 64)
    boom = lambda *a, **kw: pytest.fail("the kernel ran")  # noqa: E731
    monkeypatch.setattr(A, "_decode_attn_call", boom)
    A.decode_attention(q, kn, vn, ck, cv, jnp.array([3, 200]), 2)
    assert A.decode_read_block(2, 256, 128, 2) == 256
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    assert A.decode_read_block(2, 256, 128, 2) == 128
    assert A.decode_read_block(2, 200, 128, 2) == 200
    A.decode_attention(*case(2, 200, 2, 64), jnp.array([3, 199]), 2)


# ------------------------------------------------- the slab's functions
def test_slab_layout_functions():
    shape = A.kv_slab_shape(3, 32, 4, 8)
    assert shape == (3, 32, 32)
    k, v = A.kv_slab_zeros(3, 32, 4, 8)
    assert k.shape == v.shape == shape and k.dtype == jnp.float32
    assert not k.any()
    sk, sv = A.kv_slab_spec(3, 32, 4, 8)
    assert (sk.shape, sk.dtype) == (shape, jnp.float32) and sv == sk
    x = jnp.arange(2 * 4 * 5 * 8, dtype=jnp.float32).reshape(2, 4, 5, 8)
    rows = A.kv_rows(x)                         # (b, h, s, d) -> rows
    assert rows.shape == (2, 5, 32)
    np.testing.assert_array_equal(A.kv_heads(rows, 4),
                                  x.transpose(0, 2, 1, 3))
    assert A.kv_pad(rows, 32).shape == (2, 32, 32)
    assert not A.kv_pad(rows, 32)[:, 5:].any()
    k = A.kv_insert(k, rows[1:], 2, 7)          # one sequence into slot 2
    np.testing.assert_array_equal(k[2, 7:12], rows[1])
    assert not k[:2].any() and not k[2, :7].any() and not k[2, 12:].any()
    row = jnp.ones((3, 32))
    np.testing.assert_array_equal(
        A.kv_write_row(v, row, 4)[:, 4], row)
    each = A.kv_write_row(v, row, jnp.array([0, 9, 31]))
    assert [int(each[i].sum(-1).argmax()) for i in range(3)] == [0, 9, 31]
    assert float(each.sum()) == 3 * 32


# --------------------------------------- the engine through the kernel
VOCAB, SEQ = 64, 256


@pytest.fixture(scope="module")
def lm():
    from analytics_zoo_tpu.models import TransformerLM
    m = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                      d_model=128, n_heads=4)
    m.ensure_inference_ready()
    return m


def served(lm, **kw):
    from analytics_zoo_tpu.pipeline.inference.decode import DecodeEngine
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, max_len=SEQ,
                       prompt_buckets=(8, 16), **kw)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n) for n in (5, 12, 16, 3, 9)]
        outs = eng.generate(prompts, [130, 20, 9, 140, 40])
        return outs, eng.stats()
    finally:
        eng.close()


@pytest.fixture(scope="module")
def plain(lm):
    return served(lm, capacity=4)


def test_engine_through_the_kernel_serves_the_same_tokens(
        lm, plain, through_the_kernel):
    """Every plan of the engine (admit, step, fused window) with the
    kernel in the step: greedy streams, token for token, and the same
    live positions, of which the kernel reads blocks and not slabs."""
    outs, stats = served(lm, capacity=4)
    for a, b in zip(outs, plain[0]):
        np.testing.assert_array_equal(a, b)
    assert stats["kv_positions_live"] == plain[1]["kv_positions_live"]
    assert stats["kv_positions_read"] < plain[1]["kv_positions_read"]
    assert stats["kv_positions_read"] % 128 == 0


def test_mesh_engine_runs_the_kernel_inside_a_shard_map(
        lm, plain, through_the_kernel):
    """A mesh-sharded engine splits the slots over its devices; the
    kernel cannot be partitioned, so each device runs it on its own
    slots."""
    outs, _ = served(lm, capacity=4, mesh={"axes": {"tensor": 2}})
    for a, b in zip(outs, plain[0]):
        np.testing.assert_array_equal(a, b)


def test_generate_scan_takes_the_kernel_where_its_cache_fits(
        lm, through_the_kernel, monkeypatch):
    """``TransformerLM.generate`` sizes its cache prompt + new tokens:
    whole blocks go through the kernel, anything else through the
    masked softmax, with the same tokens."""
    from analytics_zoo_tpu.models import generation
    prompt = np.random.default_rng(1).integers(0, VOCAB, (2, 8))
    ran = []
    real = A._decode_attn_call
    monkeypatch.setattr(A, "_decode_attn_call",
                        lambda *a, **kw: ran.append(1) or real(*a, **kw))
    with_kernel = generation.generate(lm, prompt, 120)      # cache 128
    assert ran
    del ran[:]
    without = generation.generate(lm, prompt, 119)          # cache 127
    assert not ran
    np.testing.assert_array_equal(with_kernel[:, :127], without)
