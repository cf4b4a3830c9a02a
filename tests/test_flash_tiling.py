"""The flash kernels' tiling of the (query, key) plane, at the blocks
``_flash_plan`` chooses.

The three kernels skip the tiles above the causal diagonal and the key
blocks past a ``kv_lengths`` boundary, and hold every tile transposed.
A wrong bound there is silent: a skipped tile still gives numbers.
These cases hold forward, dq, dk and dv against ``naive_attention`` in
interpret mode where the skip can break, and hold the static account of
the tiling (``_flash_tile_counts``, which the kernels' loop bounds are
written in terms of) against the element-level mask.
"""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

A = importlib.import_module("analytics_zoo_tpu.ops.attention")


def _qkv(b, h, sq, sk, d, seed):
    rng = np.random.default_rng(seed)

    def mk(s):
        return jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
    return mk(sq), mk(sk), mk(sk), mk(sq)


# (id, b, h, sq, sk, d, causal, kv_lengths, the plan it pins)
CASES = [
    # the train cell's shape: 2 x 2 tiles, one skipped
    ("cell_1024", 1, 1, 1024, 1024, 64, True, None, (512, 512, 0, 0)),
    # the diagonal sits at sk - sq = 640: no multiple of either block
    ("cross_unaligned", 1, 2, 512, 1152, 16, True, None,
     (512, 384, 0, 0)),
    # lengths: whole, inside the second tile, on the first tile's edge
    # (0 keys past the first block), one key
    ("lengths_causal", 4, 1, 1024, 1024, 16, True,
     (1024, 700, 512, 1), (512, 512, 0, 0)),
    ("lengths_noncausal", 3, 1, 256, 1024, 16, False,
     (1024, 513, 512), (256, 512, 0, 0)),
    # a length with no 128-multiple divisor: padded to 384 and masked
    ("padded_328", 2, 2, 328, 328, 16, True, None, (384, 384, 56, 56)),
    ("padded_328_lengths", 2, 1, 328, 328, 16, True, (328, 130),
     (384, 384, 56, 56)),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_forward_and_gradients_match_naive_at_the_plans_blocks(case):
    _, b, h, sq, sk, d, causal, lens, plan = case
    assert A._flash_plan(causal, sq, sk, d, jnp.float32)[0] == plan
    lens = None if lens is None else np.asarray(lens)
    q, k, v, w = _qkv(b, h, sq, sk, d, seed=sq + sk)
    if lens is not None:
        # padded query rows are garbage by contract: weight them zero
        w = w * (np.arange(sq)[None, :, None, None]
                 < lens[:, None, None, None])

    def grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v, causal=causal, kv_lengths=lens)
            return jnp.sum(out * w), out
        (_, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
        return (out * (w != 0),) + g

    want = grads(A.naive_attention)
    got = grads(lambda *a, **kw: A.flash_attention(*a, interpret=True,
                                                   **kw))
    for name, r, o in zip(("out", "dq", "dk", "dv"), want, got):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=5e-4, atol=5e-5, err_msg=name)
    if lens is not None:
        for i, n in enumerate(lens):    # padded keys: exactly zero
            np.testing.assert_array_equal(np.asarray(got[2])[i, n:], 0.0)
            np.testing.assert_array_equal(np.asarray(got[3])[i, n:], 0.0)


def test_tile_counts_at_the_train_cells_shape():
    """s 1024 at 512 x 512: three of the four tiles run, in every
    kernel (75 %); the parent's plan (256 x 1024 forward, 256 x 512
    backward) ran 4 of 4, 6 of 8 and 6 of 8."""
    assert A._flash_plan(True, 1024, 1024, 64, jnp.bfloat16)[0] \
        == (512, 512, 0, 0)
    assert A._flash_tile_counts(True, 1024, 1024, 512, 512) \
        == {"fwd": (3, 4), "dq": (3, 4), "dkv": (3, 4)}
    assert A._flash_tile_counts(True, 1024, 1024, 256, 1024)["fwd"] \
        == (4, 4)
    assert A._flash_tile_counts(True, 1024, 1024, 256, 512)["dkv"] == (6, 8)
    assert A._flash_tile_counts(True, 1024, 1024, 256, 256)["dq"] \
        == (10, 16)
    assert A._flash_tile_counts(False, 1024, 1024, 512, 512)["fwd"] \
        == (4, 4)


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (1024, 1024, 512, 512), (1024, 1024, 256, 128), (1024, 1024, 128, 512),
    (512, 1152, 512, 384), (256, 896, 256, 128), (384, 1024, 128, 256),
    (128, 128, 128, 128), (768, 768, 384, 384),
])
def test_tile_spans_agree_with_the_element_mask(sq, sk, bq, bk):
    """``_row_tiles`` and ``_col_tiles`` against the mask itself: a tile
    is skipped iff no element of it is visible, and the row-wise and
    column-wise walks count the same tiles."""
    visible = np.tril(np.ones((sq, sk), bool), k=sk - sq)
    n_q, n_k = sq // bq, sk // bk
    some = visible.reshape(n_q, bq, n_k, bk).any((1, 3))
    for i in range(n_q):
        n = A._row_tiles(i, bq, bk, n_k, sk - sq, True)
        assert list(some[i]) == [j < n for j in range(n_k)]
    for j in range(n_k):
        start = A._col_tiles(j, bq, bk, sk - sq, True)
        assert list(some[:, j]) == [i >= start for i in range(n_q)]
    counts = A._flash_tile_counts(True, sq, sk, bq, bk)
    assert counts["fwd"] == counts["dq"] == counts["dkv"] \
        == (some.sum(), n_q * n_k)
