"""The ``cohere2_moe`` family (CommandAPlusLM, ISSUE 36) on the CPU at
tiny sizes with seeded weights: the keras graph, the decode engine
(prefill, ring slabs, fused windows, slots of unequal length) and the
plain reference ``benchmark/reference/cohere2moe.py`` give the same
LOGITS; the experts' shares add up to the uncut layer; the ring slab is
a full-length slab under the window mask; the new kernels in interpret
mode are ``naive_attention`` with repeated heads and an explicit mask;
ties and empty experts; the new counters."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models import CommandAPlusLM
from analytics_zoo_tpu.models import generation_cohere2moe as fam
from analytics_zoo_tpu.models.generation import family_of
from analytics_zoo_tpu.observability import profile
from analytics_zoo_tpu.ops import moe
from analytics_zoo_tpu.pipeline.inference import DecodeEngine
from benchmark.reference import cohere2moe as ref

# ops/__init__ re-exports a function named ``attention``: import the module
A = importlib.import_module("analytics_zoo_tpu.ops.attention")

#: a tiny configuration under the benchmark file's keys: 4 layers (one
#: period), window 8, 16 experts of which 4..11 are held, top 4, 2 shared
CFG = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 32,
       "num_hidden_layers": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16,
       "num_experts_published": 16, "experts_held": [4, 8],
       "num_experts_per_tok": 4, "num_shared_experts": 2,
       "sliding_window": 8, "rope_theta": 50000, "layer_norm_eps": 1e-5,
       "logit_scale": 0.5, "n_positions": 48, "initializer_range": 0.3,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}


def build(cfg=CFG, seq_len=None):
    from benchmark.adapters import cohere2moe as adapter
    return adapter.build(cfg, {"seq_len": seq_len or cfg["n_positions"]})


@pytest.fixture(scope="module")
def served():
    """The model with the reference's seeded float32 weights, its
    params, and an engine of 3 slots over them."""
    net = build()
    net.compile("sgd", "class_nll")
    net.trainer.adopt_weights(ref.make_params(CFG, 7, jnp.float32))
    params = net.trainer.state.params
    eng = DecodeEngine(params, net.hyper, capacity=3, max_len=48,
                       prompt_buckets=(8, 16, 24), step_fuse=4)
    eng.warmup()
    yield net, params, eng
    eng.close()


def ref_logits(params, seq):
    x = np.zeros((1, CFG["n_positions"]), np.int32)
    x[0, :len(seq)] = seq
    return np.asarray(ref.logits_fn(params, jnp.asarray(x), CFG))[0]


def test_keras_graph_forward_is_the_reference(served):
    """The graph outputs log-probabilities; under them are the
    reference's logits."""
    net, params, _ = served
    rng = np.random.default_rng(0)
    x = rng.integers(0, CFG["vocab_size"], (2, 48)).astype(np.int32)
    got, _ = net.to_graph().apply(params, net.trainer.state.model_state,
                                  jnp.asarray(x), training=False)
    want = jax.nn.log_softmax(ref.logits_fn(params, jnp.asarray(x), CFG))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_engine_logits_are_the_references_full_forward(served):
    """Prefill, then decode through the engine: at every served position
    the reference's logit of the served token is its best (the gap the
    benchmark's check takes), for slots of unequal length that share
    fused windows and contexts that wrap the 8-row ring five times."""
    _, params, eng = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (5, 13, 20, 9, 24)]
    news = [40, 30, 25, 36, 20]
    outs = eng.generate(prompts, news)
    for p, o in zip(prompts, outs):
        seq = np.concatenate([p, o])
        lg = ref_logits(params, seq)[len(p) - 1:len(seq) - 1]
        gap = lg.max(-1) - lg[np.arange(len(o)), o]
        assert gap.max() < 1e-3, gap.max()


def test_decode_step_logits_match_the_reference(served):
    """Logits, not tokens: the family's prefill + insert + decode steps,
    driven by hand, against the reference's row at each position."""
    net, params, _ = served
    hyper = net.hyper
    rng = np.random.default_rng(2)
    seq = rng.integers(0, 64, 40).astype(np.int32)
    n0 = 11
    want = ref_logits(params, seq)
    caches = [A.kv_slab_zeros(*d) for d in fam.slab_dims(hyper, 2, 48)]
    assert [c[0].shape[1] for c in caches] == [8, 8, 8, 48]
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :n0] = seq[:n0]
    x, pc = fam.prefill(params, hyper, jnp.asarray(prompt), 16)
    np.testing.assert_allclose(
        np.asarray(fam.head(params, hyper, x[0, :n0])), want[:n0],
        atol=2e-4, rtol=2e-4)
    caches = fam.insert(hyper, caches, pc, jnp.int32(1), jnp.int32(n0))
    for pos in range(n0, 40):
        tok = jnp.asarray([0, seq[pos]], jnp.int32)
        at = jnp.asarray([0, pos], jnp.int32)
        logits, caches, chosen = fam.decode_step(
            params, hyper, caches, fam.embed(params, tok, at), at)
        np.testing.assert_allclose(np.asarray(logits[1]), want[pos],
                                   atol=3e-4, rtol=3e-4)
    assert chosen.shape == (2, 4, 4)


def test_the_shares_add_up():
    """The routed parts of all 16 ``experts_held`` ranges of 8, plus the
    shared mean ONCE, are the uncut layer's m: what ties one chip's
    share to the model."""
    d, f, n, k = 32, 24, 128, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    p = {"router": jax.random.normal(next(keys), (d, n)),
         "w_gate": jax.random.normal(next(keys), (n, d, f)) * 0.3,
         "w_up": jax.random.normal(next(keys), (n, d, f)) * 0.3,
         "w_down": jax.random.normal(next(keys), (n, f, d)) * 0.3,
         "s_gate": jax.random.normal(next(keys), (4, d, f)) * 0.3,
         "s_up": jax.random.normal(next(keys), (4, d, f)) * 0.3,
         "s_down": jax.random.normal(next(keys), (4, f, d)) * 0.3}
    h = jax.random.normal(next(keys), (40, d))
    whole, chosen = moe.moe_sublayer(p, h, k, (0, n))
    top_i, g = moe.route(h, p["router"], k)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(top_i))
    parts = sum(moe.moe_experts(
        h, top_i, g, (first, 8), *(p[w][first:first + 8]
                                   for w in ("w_gate", "w_up", "w_down")))
        for first in range(0, n, 8))
    shared = moe.shared_mean(h, p["s_gate"], p["s_up"], p["s_down"])
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)
    # and the reference's layer, told it holds everything, agrees
    cfg = {"experts_held": [0, n], "num_experts_per_tok": k,
           "num_shared_experts": 4}
    np.testing.assert_allclose(np.asarray(ref._moe(p, h, cfg, "f32")),
                               np.asarray(whole), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("tokens", [24, 300])
def test_sorted_and_dense_plans_agree(tokens, monkeypatch):
    """The decode step's plan (every held expert over all tokens) and
    the prefill's (routed pairs sorted by expert, grouped products in
    chunks) are one sum; 300 tokens x 4 picks take three 512-row chunks
    when every pick is held."""
    d, f, n, k = 16, 24, 8, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(tokens), 6))
    wr = jax.random.normal(next(keys), (d, n))
    wg, wu = (jax.random.normal(next(keys), (n, d, f)) * 0.3
              for _ in range(2))
    wd = jax.random.normal(next(keys), (n, f, d)) * 0.3
    h = jax.random.normal(next(keys), (tokens, d))
    top_i, g = moe.route(h, wr, k)
    monkeypatch.setattr(moe, "_CHUNK_ROWS", 512)
    for held in ((0, 8), (2, 3)):
        w = [x[held[0]:held[0] + held[1]] for x in (wg, wu, wd)]
        np.testing.assert_allclose(
            np.asarray(moe._experts_sorted(h, top_i, g, held, *w)),
            np.asarray(moe._experts_dense(h, top_i, g, held, *w)),
            atol=2e-5, rtol=2e-5)


def test_ties_go_to_the_lower_index_and_an_expert_nobody_chose():
    """Equal scores: the lower index is chosen, in the program and in
    the reference alike; a held expert with no token adds nothing."""
    d, n, k = 8, 6, 2
    wr = np.zeros((d, n), np.float32)
    wr[0, 1] = wr[0, 4] = 1.0           # experts 1 and 4 tie for first
    wr[0, 5] = -5.0                     # nobody chooses 5
    h = jnp.ones((5, d))
    top_i, g = moe.route(h, jnp.asarray(wr), k)
    np.testing.assert_array_equal(np.asarray(top_i), [[1, 4]] * 5)
    np.testing.assert_allclose(np.asarray(g), 0.5, atol=1e-6)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    w = [jax.random.normal(kk, s) for kk, s in zip(
        keys, ((n, d, 4), (n, d, 4), (n, 4, d)))]
    only5 = moe.moe_experts(h, top_i, g, (5, 1), *(x[5:6] for x in w))
    assert not np.asarray(only5).any()
    cfg = {"experts_held": [0, n], "num_experts_per_tok": k,
           "num_shared_experts": 0}
    p = {"router": jnp.asarray(wr), "w_gate": w[0], "w_up": w[1],
         "w_down": w[2]}
    np.testing.assert_allclose(
        np.asarray(ref._moe(p, h, cfg, "f32")),
        np.asarray(moe.moe_experts(h, top_i, g, (0, n), *w)),
        atol=1e-5, rtol=1e-5)


def test_ring_slab_is_a_full_slab_under_the_window_mask():
    """Row for row: a sliding layer's 8-row ring after the write at pos
    holds positions pos-7..pos, position p in row p mod 8, and attending
    over it equals attending over a full-length slab under the window
    mask."""
    rng = np.random.default_rng(4)
    b, t, w, n_kv, g, d = 2, 40, 8, 2, 2, 16
    keys = rng.normal(size=(b, t, n_kv * d)).astype(np.float32)
    vals = rng.normal(size=(b, t, n_kv * d)).astype(np.float32)
    ring_k, ring_v = A.kv_slab_zeros(b, w, n_kv, d)
    # a prompt of 21 (slot 0) and of 5 (slot 1) admitted from a bucket of 24
    lens = np.array([21, 5])
    for slot in range(b):
        rk, rv = fam.insert(
            {}, [(ring_k, ring_v)],
            [(jnp.asarray(keys[slot:slot + 1, :24]),
              jnp.asarray(vals[slot:slot + 1, :24]))],
            jnp.int32(slot), jnp.int32(lens[slot]))[0]
        ring_k, ring_v = rk, rv
    for step in range(12):
        pos = lens + step
        q = rng.normal(size=(b, n_kv * g * d)).astype(np.float32)
        kn = keys[np.arange(b), pos]
        vn = vals[np.arange(b), pos]
        o, ring_k, ring_v = A.decode_attention_gqa(
            jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), ring_k,
            ring_v, jnp.asarray(pos, jnp.int32), n_kv * g, n_kv)
        for s in range(b):
            lo = max(pos[s] - w + 1, 0)
            for p in range(lo, pos[s] + 1):     # the ring, row for row
                np.testing.assert_array_equal(
                    np.asarray(ring_k[s, p % w]), keys[s, p])
            kk = keys[s, lo:pos[s] + 1].reshape(-1, n_kv, d)
            vv = vals[s, lo:pos[s] + 1].reshape(-1, n_kv, d)
            qq = q[s].reshape(n_kv, g, d)
            sc = np.einsum("ngd,tnd->ngt", qq, kk) / np.sqrt(d)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            want = np.einsum("ngt,tnd->ngd", pr, vv).reshape(-1)
            np.testing.assert_allclose(np.asarray(o[s]), want, atol=2e-5,
                                       rtol=2e-5)


def _repeat_heads(x, g):
    return jnp.repeat(x, g, axis=1)


@pytest.mark.parametrize("window", [None, 200, 128, 1])
def test_flash_forward_grouped_and_windowed_in_interpret_mode(window):
    """``zoo_flash_fwd`` with the key/value block mapped h -> h // group
    and a window, against ``naive_attention`` over REPEATED heads and an
    explicit mask."""
    rng = np.random.default_rng(5)
    b, h, n_kv, s, d = 1, 4, 2, 384, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, n, s, d)), jnp.float32)
               for n in (h, n_kv, n_kv))
    out, _ = A._flash_fwd_call(
        q.reshape(b * h, s, d), k.reshape(b * n_kv, s, d),
        v.reshape(b * n_kv, s, d), jnp.zeros((b * h, 1, 1)), sq=s, sk=s,
        causal=True, masked=False, block_q=128, block_k=128,
        scale=1 / np.sqrt(d), interpret=True, window=window,
        group=h // n_kv)
    kr, vr = _repeat_heads(k, h // n_kv), _repeat_heads(v, h // n_kv)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / np.sqrt(d)
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, sc, -1e30), -1), vr)
    np.testing.assert_allclose(np.asarray(out.reshape(b, h, s, d)),
                               np.asarray(want), atol=2e-3, rtol=2e-3)
    if window is None:      # and the plain causal oracle agrees with both
        naive = A.naive_attention(
            q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
            vr.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(want), np.asarray(naive),
                                   atol=1e-4, rtol=1e-4)


def test_window_tiles_skip_what_the_window_left_behind():
    """``_row_start`` with ``_row_tiles``: a windowed row of tiles
    starts where the window of the block's first row starts."""
    assert A._row_start(0, 512, 512, 0, 4096) == 0
    assert A._row_start(8, 512, 512, 0, 4096) == 0      # rows 4096..4607
    assert A._row_start(9, 512, 512, 0, 4096) == 1      # first row 4608
    assert A._row_start(10, 512, 512, 0, 4096) == 2
    assert A._row_start(5, 512, 512, 0, None) == 0
    assert A._row_tiles(10, 512, 512, 11, 0, True) == 11


#: (query heads, cached heads, d_head, rows, block): heads of 128, one
#: cached head a lane tile; granite's heads of 64, two a tile, over its
#: 1280 rows in blocks of 256; heads of 32, four a tile; heads of 128 in
#: groups of ONE (as many cached heads as query heads: ouro's 16 over 16)
_GQA_128, _GQA_64, _GQA_32, _GQA_ONE = (
    (16, 2, 128, 256, 128), (32, 8, 64, 1280, 256), (16, 8, 32, 256, 128),
    (4, 4, 128, 384, 128))


@pytest.mark.parametrize("layout, positions, passes", [
    pytest.param(_GQA_128, [5, 255, 100], None, id="positions0"),
    pytest.param(_GQA_128, [256, 300, 700], None, id="positions1"),
    pytest.param(_GQA_128, [0, 511, 512], None, id="positions2"),
    pytest.param(_GQA_64, [5, 255, 1279], None, id="d64-block-edge-last-row"),
    pytest.param(_GQA_64, [256, 700, 1024], None, id="d64-block-starts"),
    pytest.param(_GQA_32, [3, 300, 511], None, id="d32-ring"),
    pytest.param(_GQA_ONE, [5, 130, 383], None, id="group-one"),
    pytest.param(_GQA_ONE, [0, 127, 383], (4, 2), id="group-one-pass2-of-4"),
    pytest.param(_GQA_128, [5, 255, 100], (3, 0), id="pass0-of-3"),
    pytest.param(_GQA_64, [256, 700, 1279], (2, 1), id="d64-pass1-of-2")])
def test_decode_gqa_kernel_in_interpret_mode(layout, positions, passes):
    """``zoo_decode_attn_gqa`` over a bfloat16 slab, as a full slab (pos
    < rows) and as a ring (pos >= rows), against the masked softmax; the
    slabs come out with the new row written and nothing else touched.
    Heads narrower than a lane tile go in lane-packed: each row's score
    and result are its own head's.  ``passes``: (passes, pass) slabs
    with a pass axis, the kernel told which pass's rows to read and
    write; every other pass's rows come out as they went in."""
    rng = np.random.default_rng(6)
    heads, n_kv, d, rows, block = layout
    b = 3
    bf = jnp.bfloat16
    lead = (b,) if passes is None else (b, passes[0])
    ck, cv = (jnp.asarray(rng.normal(size=lead + (rows, n_kv * d)), bf)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, heads * d)), bf)
    kn, vn = (jnp.asarray(rng.normal(size=(b, n_kv * d)), bf)
              for _ in range(2))
    pos = jnp.asarray(positions, jnp.int32)
    at = {} if passes is None else {"pass_index": jnp.int32(passes[1])}
    o1, k1, v1 = A._decode_attention_gqa_reference(q, kn, vn, ck, cv, pos,
                                                   heads, n_kv, **at)
    o2, k2, v2 = A._decode_gqa_call(q, kn, vn, ck, cv, pos, n_heads=heads,
                                    n_kv_heads=n_kv, block=block,
                                    interpret=True, **at)
    np.testing.assert_array_equal(np.asarray(k1, np.float32),
                                  np.asarray(k2, np.float32))
    np.testing.assert_array_equal(np.asarray(v1, np.float32),
                                  np.asarray(v2, np.float32))
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=0.02)
    if passes is not None:      # the other passes untouched, then that one
        others = np.arange(passes[0]) != passes[1]
        np.testing.assert_array_equal(np.asarray(k2, np.float32)[:, others],
                                      np.asarray(ck, np.float32)[:, others])
        k1, v1 = (x[:, passes[1]] for x in (k1, v1))
    # against repeated heads and an explicit mask, float32
    g = heads // n_kv
    live = np.arange(rows)[None] < np.minimum(np.asarray(pos) + 1,
                                              rows)[:, None]
    kk = np.repeat(np.asarray(k1, np.float32).reshape(b, rows, n_kv, d), g,
                   axis=2)
    vv = np.repeat(np.asarray(v1, np.float32).reshape(b, rows, n_kv, d), g,
                   axis=2)
    sc = np.einsum("bhd,bthd->bht",
                   np.asarray(q, np.float32).reshape(b, heads, d),
                   kk) / np.sqrt(d)
    sc = np.where(live[:, None, :], sc, -1e30)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    want = np.einsum("bht,bthd->bhd", pr, vv).reshape(b, heads * d)
    np.testing.assert_allclose(np.asarray(o2, np.float32), want, atol=0.03)


def test_decode_gqa_plan_says_what_it_admits():
    assert A._decode_gqa_plan(4096, 128, 8, 128, jnp.bfloat16)[0] == 512
    assert A._decode_gqa_plan(6144, 128, 8, 128, jnp.bfloat16)[0] == 512
    assert A._decode_gqa_plan(384, 16, 2, 128, jnp.bfloat16)[0] == 128
    # heads of 64, two cached heads a lane tile: granite's 1280 rows
    assert A._decode_gqa_plan(1280, 32, 8, 64, jnp.bfloat16)[0] == 256
    assert A._decode_gqa_plan(4096, 128, 8, 64, jnp.bfloat16)[0] == 512
    assert A._decode_gqa_plan(256, 16, 8, 32, jnp.bfloat16)[0] == 256
    for bad in ((4096, 128, 8, 128, jnp.float32),
                (1280, 32, 8, 64, jnp.float32),
                (4096, 128, 8, 96, jnp.bfloat16),      # 96 does not divide 128
                (4096, 128, 1, 64, jnp.bfloat16),      # half a lane tile
                (4096, 128, 8, 256, jnp.bfloat16),
                (4096, 12, 8, 64, jnp.bfloat16),       # not whole groups
                (100, 128, 8, 128, jnp.bfloat16)):
        block, why = A._decode_gqa_plan(*bad)
        assert block is None and why
    assert profile.KERNEL_DECODE_ATTN_GQA == "zoo_decode_attn_gqa"
    assert profile.KERNEL_DECODE_ATTN_GQA in profile.KERNELS
    for scope in ("zoo_moe", "zoo_moe_router", "zoo_moe_experts",
                  "zoo_moe_shared"):
        assert scope in profile.SCOPES


def test_rope_turns_interleaved_pairs():
    x = jnp.asarray(np.random.default_rng(7).normal(size=(3, 5, 8)),
                    jnp.float32)
    pos = jnp.arange(5)
    y = np.asarray(A.rope_interleaved(x, pos[None, :], 50000.0))
    for i in range(4):
        ang = np.arange(5) * 50000.0 ** (-2 * i / 8)
        a, b = np.asarray(x[..., 2 * i]), np.asarray(x[..., 2 * i + 1])
        np.testing.assert_allclose(y[..., 2 * i],
                                   a * np.cos(ang) - b * np.sin(ang),
                                   atol=1e-5)
        np.testing.assert_allclose(y[..., 2 * i + 1],
                                   a * np.sin(ang) + b * np.cos(ang),
                                   atol=1e-5)
    # position 0 turns nothing; norms are kept
    np.testing.assert_allclose(y[:, 0], np.asarray(x[:, 0]), atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_the_engine_refuses_what_it_cannot_do_for_this_family(served):
    net, params, _ = served
    assert family_of(net.hyper).name == "cohere2_moe"
    assert family_of({"n_layers": 2}).name == "transformer_lm"
    with pytest.raises(ValueError, match="prefix_pool"):
        DecodeEngine(params, net.hyper, capacity=2, prefix_pool=2)
    with pytest.raises(ValueError, match="draft"):
        DecodeEngine(params, net.hyper, capacity=2, draft_params=params,
                     draft_hyper=net.hyper)
    with pytest.raises(ValueError, match="mesh"):
        DecodeEngine(params, net.hyper, capacity=2,
                     mesh={"axes": {"data": 2}})
    with pytest.raises(ValueError, match="decode engine"):
        net.generate(np.zeros((1, 4), np.int32), 4)
    with pytest.raises(ValueError, match="no generation functions"):
        family_of({"family": "nope"})


def test_window_skipped_counter_is_exact_on_a_scripted_run(served):
    """One request of 10 + 20 tokens, alone, window 8, max_len 48: at
    the step whose new token sits at position n - 1 (n live positions),
    each of the 3 ring layers holds min(n, 8) and skips n - min(n, 8);
    the full layer holds n.  Steps cover n = 11 .. 29 (the first token
    comes from the prefill), plus the surplus steps of the last fused
    window, which the dispatcher counts as it schedules them."""
    net, params, _ = served
    eng = DecodeEngine(params, net.hyper, capacity=2, max_len=48,
                       prompt_buckets=(16,), step_fuse=4)
    phase, on_spans = eng._phase, []

    def recording(name, **stats):       # what the dispatch spans carry
        if name == "dispatch":
            on_spans.append(stats["kv_positions_window_skipped"])
        return phase(name, **stats)

    eng._phase = recording
    try:
        eng.generate([np.arange(10, dtype=np.int32)], 20)
        s = eng.stats()
    finally:
        eng.close()
    assert sum(on_spans) == s["kv_positions_window_skipped"]
    ns = range(11, 11 + s["steps"])
    assert s["steps"] >= 19
    assert eng._kv_block is None and len(eng._kv_kinds) == 2    # two kinds
    assert s["kv_positions_window_skipped"] == sum(
        3 * (n - min(n, 8)) for n in ns)
    assert s["kv_positions_live"] == sum(3 * min(n, 8) + n for n in ns)
    # off the chip a step reads whole slabs: 3 rings of 8 and 48 rows
    assert s["kv_positions_read"] == s["steps"] * (3 * 8 + 48)


def test_moe_counters_count_live_slots_and_held_experts(served):
    """Uniform ids: of the routed pairs of live slots, the held range's
    share is held / published = 8 / 16 here (1 / 16 in the benchmark's
    cut), within sampling; experts hit never pass held x layers a
    step."""
    _, _, eng = served
    before = eng.stats()
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (6, 12, 18, 7, 15, 9)]
    eng.generate(prompts, [30] * 6)
    s = {k: v - before[k] for k, v in eng.stats().items()
         if k.startswith("moe_") or k in ("tokens", "admitted", "steps")}
    decoded = s["tokens"] - s["admitted"]   # first tokens are prefill's
    assert s["moe_assignments"] == decoded * 4 * 4   # layers x top_k
    share = s["moe_assignments_held"] / s["moe_assignments"]
    assert abs(share - 8 / 16) < 0.06, share
    assert 0 < s["moe_experts_hit"] <= s["steps"] * 4 * 8
    assert s["moe_experts_hit"] <= s["moe_assignments_held"]


def test_count_routed_on_a_scripted_window(served):
    """``_count_routed`` by hand: 2 steps, 3 slots (one free), 4 layers,
    top 4; held experts 4..11."""
    _, _, eng = served

    class Req:
        def __init__(self, left):
            self.max_new, self.produced = left, 0
            self.stream = type("S", (), {"done": False})()

    chosen = np.zeros((2, 3, 4, 4), np.int32)       # all expert 0: absent
    chosen[0, 0, :, 0] = 5                          # slot 0, step 0: 4 held
    chosen[1, 0, 0, :] = [4, 5, 6, 12]              # slot 0, step 1: 3 held
    chosen[:, 1] = 7                                # free slot: not counted
    chosen[1, 2] = 11                               # past slot 2's last token
    got = eng._count_routed([Req(5), None, Req(1)], chosen)
    # live (step, slot): (0,0) (1,0) (0,2) -> 3 x 4 layers x 4 picks
    assert got == (48, 7, 4 + 3)


def test_reference_products_with_bfloat16_weights_are_highests():
    """The reference's product with a bfloat16 weight (three exact
    bfloat16 terms of the activation) is ``highest``'s: the same
    bfloat16 weights upcast to float32 take the ``highest`` path and
    give the same logits."""
    stored = ref.make_params(CFG, 11)           # bfloat16, as served
    assert stored["attn_0"]["Wq"].dtype == jnp.bfloat16
    upcast = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), stored)
    x = np.random.default_rng(9).integers(0, 64, (2, 48)).astype(np.int32)
    a = np.asarray(ref.logits_fn(stored, jnp.asarray(x), CFG))
    b = np.asarray(ref.logits_fn(upcast, jnp.asarray(x), CFG))
    np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    # and the control is another forward: bfloat16 all the way
    c = np.asarray(ref.logits_fn(stored, jnp.asarray(x), CFG, "bf16"))
    assert np.abs(c - a).max() > 50 * np.abs(b - a).max()
