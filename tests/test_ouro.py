"""The ``ouro`` family (OuroLM: one stack of layers run several times a
token, each pass with its own key/value cache) on the CPU at tiny sizes
with seeded float32 weights: the keras graph, the decode engine and the
plain reference ``benchmark/reference/ouro.py`` give the same LOGITS;
the slot's pass-t rows are the keys and values the reference makes at
pass t; a cache shared across passes is seen; the spans and counters
count the passes; what the family refuses."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models import OuroLM
from analytics_zoo_tpu.models import generation_ouro as fam
from analytics_zoo_tpu.models.generation import family_of
from analytics_zoo_tpu.pipeline.inference import DecodeEngine
from benchmark.reference import ouro as ref

# ops/__init__ re-exports a function named ``attention``: import the module
A = importlib.import_module("analytics_zoo_tpu.ops.attention")

#: a tiny configuration under the benchmark file's keys: 2 layers run 3
#: times, d 128, 2 query heads over 2 key/value heads of 64, vocabulary
#: 256.  ``initializer_range`` 0.2 makes the layers' share of the logits
#: large against the table's, so that a fault in a pass moves them
CFG = {"vocab_size": 256, "hidden_size": 128, "intermediate_size": 96,
       "num_hidden_layers": 2, "num_attention_heads": 2,
       "num_key_value_heads": 2, "head_dim": 64, "total_ut_steps": 3,
       "rope_theta": 1e4, "rms_norm_eps": 1e-6, "early_exit_threshold": 1,
       "n_positions": 48, "initializer_range": 0.2}
BUCKETS = (8, 16, 24)
#: float32 throughout, program and reference: what tells them apart is
#: the order of float32 sums (fused products, the softmax), about 1e-6
#: relative a layer run, compounded over 6 layer runs, 6 norms between
#: them and the head, on logits up to 10: the largest gap seen is 1.8e-4.
#: 5e-4 holds that with room, and a fault moves logits by 1e-1 and more
#: (``test_a_cache_shared_across_passes_is_seen``)
ATOL, RTOL = 5e-4, 1e-4


def build(cfg=CFG):
    from benchmark.adapters import ouro as adapter
    return adapter.build(cfg, {"seq_len": cfg["n_positions"]})


@pytest.fixture(scope="module")
def served():
    """The model with the reference's seeded float32 weights, its
    params, and an engine of 3 slots over them."""
    net = build()
    net.compile("sgd", "class_nll")
    net.trainer.adopt_weights(ref.make_params(CFG, 7, jnp.float32))
    params = net.trainer.state.params
    eng = DecodeEngine(params, net.hyper, capacity=3, max_len=48,
                       prompt_buckets=BUCKETS, step_fuse=4)
    eng.warmup()
    yield net, params, eng
    eng.close()


def ref_logits(params, seq):
    x = np.zeros((1, CFG["n_positions"]), np.int32)
    x[0, :len(seq)] = seq
    return np.asarray(ref.logits_fn(params, jnp.asarray(x), CFG))[0]


def served_gaps(params, prompts, outs):
    """At every served position, how far the reference's logit of the
    served token lies below its best: 0 where the engine served the
    reference's greedy token."""
    gaps = []
    for p, o in zip(prompts, outs):
        seq = np.concatenate([p, o])
        lg = ref_logits(params, seq)[len(p) - 1:len(seq) - 1]
        gaps.append(lg.max(-1) - lg[np.arange(len(o)), o])
    return np.concatenate(gaps)


def empty_states(hyper, capacity=2, max_len=48):
    return [tuple(jnp.zeros(shape, dtype) for shape, dtype in leaves)
            for leaves in fam.state_shapes(hyper, capacity, max_len,
                                           jnp.float32)]


def prefill_then_decode(params, hyper, seq, n0, steps, slot=1):
    """The family's prefill + insert into ``slot`` of 2, then ``steps``
    decode steps through the cache (the other slot keeps stepping on
    garbage).  Returns the prefill's logits of the prompt, each step's
    logits of the slot, and the states."""
    states = empty_states(hyper)
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :n0] = seq[:n0]
    x, rows = fam.prefill(params, hyper, jnp.asarray(prompt), 16,
                          length=jnp.int32(n0))
    first = np.asarray(fam.head(params, hyper, x[0, :n0]))
    states = fam.insert(hyper, states, rows, jnp.int32(slot), jnp.int32(n0))
    logits = []
    for pos in range(n0, n0 + steps):
        tok = jnp.asarray([3, seq[pos]] if slot else [seq[pos], 3],
                          jnp.int32)
        at = jnp.asarray([0, pos] if slot else [pos, 0], jnp.int32)
        lg, states = fam.decode_step(params, hyper, states,
                                     fam.embed(params, tok, at), at)
        logits.append(np.asarray(lg[slot]))
    return first, np.stack(logits), states


# ------------------------------------------------------------ the model
def test_keras_graph_forward_is_the_reference(served):
    """The graph outputs log-probabilities; under them are the
    reference's logits (every pass over the whole sequence)."""
    net, params, _ = served
    x = np.random.default_rng(0).integers(0, 256, (2, 48)).astype(np.int32)
    got, _ = net.to_graph().apply(params, net.trainer.state.model_state,
                                  jnp.asarray(x), training=False)
    want = jax.nn.log_softmax(ref.logits_fn(params, jnp.asarray(x), CFG))
    # the graph's head is a plain float32 product (no ``highest``): on the
    # CPU that is float32, a few 1e-5 of a log-probability of size 5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-4)


def test_parameter_tree_is_the_references():
    net = build()
    graph, _ = jax.eval_shape(lambda key: net.to_graph().init(key),
                              jax.random.PRNGKey(0))
    want = {layer: {leaf: shape for leaf, (shape, _) in leaves.items()}
            for layer, leaves in ref.param_spec(CFG).items()}
    assert jax.tree_util.tree_map(lambda a: a.shape, graph) == want
    # the layers once, whatever the passes: 2 layers, 4 norms each
    assert sorted(k for k in want if k.startswith("attn_")) == [
        "attn_0", "attn_1"]
    assert len([k for k in want if k.startswith("ln_")]) == 2 * 4 + 1


def test_prefill_then_decode_logits_are_the_references(served):
    """Logits, not tokens: the family's prefill of 11 positions, then 8
    decode steps through the cache, in slot 1 of 2 and in slot 0 of 2,
    against the reference's full forward at each position."""
    net, params, _ = served
    seq = np.random.default_rng(2).integers(0, 256, 40).astype(np.int32)
    want = ref_logits(params, seq)
    for slot in (1, 0):
        first, steps, _ = prefill_then_decode(params, net.hyper, seq, 11, 8,
                                              slot)
        np.testing.assert_allclose(first, want[:11], atol=ATOL, rtol=RTOL)
        np.testing.assert_allclose(steps, want[11:19], atol=ATOL, rtol=RTOL)


def test_each_pass_keeps_its_own_rows(served):
    """After the prefill and the steps, the slot's pass-t part of layer
    l's slabs holds the keys and values the reference makes at pass t,
    every live row of every pass; the passes' rows differ (a pass does
    not see another's)."""
    net, params, _ = served
    seq = np.random.default_rng(3).integers(0, 256, 30).astype(np.int32)
    _, _, states = prefill_then_decode(params, net.hyper, seq, 9, 6)
    want = np.asarray(ref.pass_kv(params, jnp.asarray(seq[:15]), CFG))
    assert want.shape == (3, 2, 2, 15, 128)
    for layer, slabs in enumerate(states):
        for kind, slab in enumerate(slabs):
            got = np.asarray(slab[1, :, :15])         # slot 1, every pass
            np.testing.assert_allclose(got, want[:, layer, kind],
                                       atol=ATOL, rtol=RTOL)
    assert np.abs(want[0] - want[1]).max() > 0.1


def test_a_cache_shared_across_passes_is_seen(served, monkeypatch):
    """A planted fault: every pass of a step reads and writes pass 1's
    part of the slabs (the shared-cache variant, which is other
    mathematics).  The decode logits then leave the reference by far
    more than the tolerance."""
    net, params, _ = served
    seq = np.random.default_rng(2).integers(0, 256, 40).astype(np.int32)
    want = ref_logits(params, seq)
    real = fam.decode_attention_gqa

    def first_pass_only(*a, pass_index=None):
        return real(*a, pass_index=jnp.int32(0))

    monkeypatch.setattr(fam, "decode_attention_gqa", first_pass_only)
    _, steps, _ = prefill_then_decode(params, net.hyper, seq, 11, 8)
    assert np.abs(steps - want[11:19]).max() > 100 * ATOL


def test_engine_serves_the_references_greedy_tokens(served):
    """Prefill, then decode through the engine: at every served position
    the reference's logit of the served token is its best, in slots that
    share fused windows, for prompts at a bucket's exact length too."""
    _, params, eng = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 13, 20, 8, 24, 2)]
    outs = eng.generate(prompts, [20, 25, 16, 30, 20, 12])
    assert served_gaps(params, prompts, outs).max() < 1e-3


def test_served_through_load_keras_net_and_generate_stream(served):
    """The normal path: ``InferenceModel(decode_capacity=...)``,
    ``load_keras_net``, ``generate_stream``."""
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel
    net, params, _ = served
    im = InferenceModel(decode_capacity=2, decode_max_len=48,
                        decode_prompt_buckets=BUCKETS)
    im.load_keras_net(net)
    try:
        prompt = np.arange(7, 17, dtype=np.int32)
        got = list(im.generate_stream(prompt, 9))
    finally:
        im.close()
    assert len(got) == 9
    assert served_gaps(params, [prompt], [np.asarray(got)]).max() < 1e-3


# ------------------------------------------------- the engine's counts
def test_the_slabs_lie_where_the_family_says(served):
    """Each layer's slabs hold a pass axis inside the slot; the
    ``kv_positions_*`` counters count every pass of every layer; a step
    counts its passes in ``pass_steps``."""
    net, _, eng = served
    for a, b in eng._caches:
        assert a.shape == b.shape == (3, 3, 48, 2 * 64)
    assert eng._kv_kinds == [(48, 48, 3 * 2)]
    assert eng._passes == 3
    before = eng.stats()
    eng.generate([np.arange(1, 6, dtype=np.int32)] * 2, 4)
    s = eng.stats()
    assert s["admitted"] - before["admitted"] == 2
    assert s["pass_steps"] - before["pass_steps"] \
        == 3 * (s["steps"] - before["steps"]) > 0


def test_spans_carry_the_passes(served, monkeypatch):
    """The dispatch and admit spans carry ``passes``: the passes the plan
    ran."""
    from analytics_zoo_tpu.observability import profile
    net, params, eng = served
    seen = []
    real = profile.annotate

    def annotate(name, **stats):
        seen.append((name, stats))
        return real(name, **stats)

    monkeypatch.setattr(profile, "annotate", annotate)
    eng.generate([np.arange(1, 9, dtype=np.int32)], 6)
    for phase in ("decode/dispatch", "decode/admit"):
        stats = [s for name, s in seen if name == phase]
        assert stats and all(s["passes"] == 3 for s in stats), phase


def test_the_engine_refuses_what_it_cannot_do_for_this_family(served):
    net, params, _ = served
    assert family_of(net.hyper).name == "ouro"
    for kwargs, what in (({"prefix_pool": 2}, "prefix_pool"),
                         ({"draft_params": params,
                           "draft_hyper": net.hyper}, "draft"),
                         ({"mesh": {"axes": {"data": 2}}}, "mesh")):
        with pytest.raises(ValueError, match=what):
            DecodeEngine(params, net.hyper, capacity=2, **kwargs)
    with pytest.raises(ValueError, match="decode engine"):
        net.generate(np.zeros((1, 4), np.int32), 4)
    with pytest.raises(ValueError, match="threshold"):
        OuroLM(vocab_size=16, seq_len=8, early_exit_threshold=0.9)


# --------------------------------------------------------- the pieces
def test_rope_turns_halves():
    """Rotate-half: dimension i pairs with i + d/2 and turns by ``p *
    theta ** (-2i / d)``; position 0 turns nothing and norms are kept."""
    x = jnp.asarray(np.random.default_rng(7).normal(size=(3, 5, 8)),
                    jnp.float32)
    y = np.asarray(A.rope_half(x, jnp.arange(5)[None, :], 1e4))
    for i in range(4):
        ang = np.arange(5) * 1e4 ** (-2 * i / 8)
        a, b = np.asarray(x[..., i]), np.asarray(x[..., i + 4])
        np.testing.assert_allclose(y[..., i], a * np.cos(ang)
                                   - b * np.sin(ang), atol=1e-5)
        np.testing.assert_allclose(y[..., i + 4], a * np.sin(ang)
                                   + b * np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(y[:, 0], np.asarray(x[:, 0]), atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    assert not np.allclose(y, np.asarray(A.rope_interleaved(
        x, jnp.arange(5)[None, :], 1e4)))


def test_the_layer_says_its_rotary_pairing():
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        GroupedQueryAttention
    half = GroupedQueryAttention(2, 2, 64, rope_theta=1e4, rope="half")
    assert half.get_config()["rope"] == "half"
    assert "rope" not in GroupedQueryAttention(
        2, 2, 64, rope_theta=1e4).get_config()
    with pytest.raises(ValueError, match="rope"):
        GroupedQueryAttention(2, 2, 64, rope="spiral")


def test_the_prompts_rows_go_into_every_pass_of_a_slot():
    """``kv_insert`` into a slab with a pass axis lays every pass's rows
    of a prompt into one slot at once, and touches nothing else."""
    slab = jnp.zeros((3, 4, 16, 8))
    rows = jnp.arange(4 * 5 * 8, dtype=jnp.float32).reshape(1, 4, 5, 8) + 1
    out = np.asarray(A.kv_insert(slab, rows, 2))
    np.testing.assert_array_equal(out[2, :, :5], np.asarray(rows[0]))
    assert not out[:2].any() and not out[2, :, 5:].any()


def test_reference_control_is_another_forward():
    """The bfloat16 control differs from the float32 reference far more
    than bfloat16 weights upcast to float32 do."""
    stored = ref.make_params(CFG, 11)
    assert stored["attn_0"]["Wq"].dtype == jnp.bfloat16
    upcast = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), stored)
    x = np.random.default_rng(9).integers(0, 256, (1, 48)).astype(np.int32)
    a = np.asarray(ref.logits_fn(stored, jnp.asarray(x), CFG))
    b = np.asarray(ref.logits_fn(upcast, jnp.asarray(x), CFG))
    c = np.asarray(ref.logits_fn(stored, jnp.asarray(x), CFG, "bf16"))
    np.testing.assert_allclose(a, b, atol=ATOL, rtol=RTOL)
    assert np.abs(a - c).max() > 100 * ATOL
