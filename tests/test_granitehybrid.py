"""The ``granitemoehybrid`` family (GraniteHybridLM: Mamba-2 state-space
layers beside grouped-query attention without positions) on the CPU at
tiny sizes with seeded weights: the keras graph, the decode engine and
the plain reference ``benchmark/reference/granitehybrid.py`` give the
same LOGITS; the chunked scan is the sequential recurrence, its final
state the state at each prompt's own length; the state-update kernel in
interpret mode is its twin; a reused slot inherits nothing; what the
family refuses; faults in the admission are seen."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from analytics_zoo_tpu.models import GraniteHybridLM
from analytics_zoo_tpu.models import generation_granitehybrid as fam
from analytics_zoo_tpu.models.generation import family_of
from analytics_zoo_tpu.observability import profile
from analytics_zoo_tpu.ops import ssm
from analytics_zoo_tpu.pipeline.inference import DecodeEngine
from benchmark.reference import granitehybrid as ref

# ops/__init__ re-exports a function named ``attention``: import the module
A = importlib.import_module("analytics_zoo_tpu.ops.attention")

#: a tiny configuration under the benchmark file's keys: one period of
#: ten (attention at index 5), d 64, 4 heads of 16, a Mamba layer of 4
#: heads of 32 with a state of 16, chunks of 8, vocabulary 256.  The
#: embedding multiplier is not the published 12: at these widths the
#: tied head would then put each token's own id first whatever the
#: layers do, and greedy decoding would repeat the prompt's last token
#: (the layers' sum outweighs the embedding at the published widths)
CFG = {"vocab_size": 256, "hidden_size": 64, "shared_intermediate_size": 96,
       "num_hidden_layers": 10, "num_attention_heads": 4,
       "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 32,
       "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
       "rms_norm_eps": 1e-5, "embedding_multiplier": 1.5,
       "residual_multiplier": 0.22, "attention_multiplier": 1 / 16,
       "logits_scaling": 8.0, "n_positions": 48, "initializer_range": 0.2,
       "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4}
BUCKETS = (8, 16, 24)


def build(cfg=CFG, seq_len=None):
    from benchmark.adapters import granitehybrid as adapter
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


# ------------------------------------------------------------ the model
def test_keras_graph_forward_is_the_reference(served):
    """The graph outputs log-probabilities; under them are the
    reference's logits (its recurrence position by position, the graph's
    chunked scan)."""
    net, params, _ = served
    x = np.random.default_rng(0).integers(0, 256, (2, 48)).astype(np.int32)
    got, _ = net.to_graph().apply(params, net.trainer.state.model_state,
                                  jnp.asarray(x), training=False)
    want = jax.nn.log_softmax(ref.logits_fn(params, jnp.asarray(x), CFG))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_parameter_tree_is_the_references():
    net = build()
    graph, _ = jax.eval_shape(lambda key: net.to_graph().init(key),
                              jax.random.PRNGKey(0))
    want = {layer: {leaf: shape for leaf, (shape, _) in leaves.items()}
            for layer, leaves in ref.param_spec(CFG).items()}
    assert jax.tree_util.tree_map(lambda a: a.shape, graph) == want
    assert sorted(k for k in want if k.startswith("attn_")) == ["attn_5"]


# ------------------------------------------------------ the chunked scan
def _recurrence(x, dt, A_, B, C, D, length):
    """The published definition, a position at a time, for one row."""
    def step(S, t):
        d = jnp.where(t < length, dt[t], 0.0)
        S = S * jnp.exp(d * A_)[:, None, None] \
            + (d[:, None] * x[t])[..., None] * B[t][None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, C[t],
                             precision=lax.Precision.HIGHEST) \
            + D[:, None] * x[t]
    h, p, n = x.shape[1], x.shape[2], B.shape[-1]
    S, y = lax.scan(step, jnp.zeros((h, p, n)), jnp.arange(x.shape[0]))
    return y, S


@pytest.mark.parametrize("chunk,lengths", [
    (8, (1, 7)), (8, (8, 9)), (8, (16, 23)), (8, (24, 3)),
    (16, (11, 24)), (64, (24, 17))])
def test_ssd_scan_is_the_sequential_recurrence(chunk, lengths):
    """Lengths on both sides of chunk boundaries and of the padded width
    (24, no multiple of 16 or 64): every live row of ``y`` and the final
    state, which is the recurrence's state AT the row's length."""
    rng = np.random.default_rng(chunk + sum(lengths))
    b, s, h, p, n = 2, 24, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, s, h)) - 2,
                                     jnp.float32))
    A_ = -jnp.asarray(rng.uniform(1, 4, h), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
            for _ in range(2))
    D = jnp.asarray(rng.normal(size=h), jnp.float32)
    y, final = ssm.ssd_scan(x, dt, A_, B, C, D, jnp.asarray(lengths),
                            chunk=chunk, dtype=jnp.float32)
    for i, n_live in enumerate(lengths):
        want_y, want_s = _recurrence(x[i], dt[i], A_, B[i], C[i], D, n_live)
        np.testing.assert_allclose(np.asarray(y[i, :n_live]),
                                   np.asarray(want_y[:n_live]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(final[i]), np.asarray(want_s),
                                   atol=2e-5, rtol=2e-5)


def test_conv_step_goes_on_where_the_prompt_stopped():
    """The window a prompt leaves at its length, stepped one input at a
    time, gives what the whole-sequence convolution gives; a prompt
    shorter than the window leaves zeros before it."""
    rng = np.random.default_rng(3)
    s, c, k = 20, 6, 4
    x = jnp.asarray(rng.normal(size=(1, s, c)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, c)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(c,)), jnp.float32)
    whole = ssm.causal_conv(x, w, b)
    for length in (1, 2, 3, 9):
        window = ssm.conv_window(x, length, k - 1)
        if length < k - 1:
            assert not np.asarray(window[0, :k - 1 - length]).any()
        for t in range(length, s):
            y, window = ssm.conv_step(window, x[:, t], w, b)
            np.testing.assert_allclose(np.asarray(y[0]),
                                       np.asarray(whole[0, t]), atol=1e-5)


@pytest.mark.parametrize("slots,heads", [(3, 64), (2, 8)])
def test_decode_kernel_in_interpret_mode_is_its_twin(slots, heads):
    """``zoo_ssm_decode`` in the pallas interpreter against the twin in
    ``jax.numpy``, with the state aliased through the call: it comes out
    updated in the buffer it came in."""
    rng = np.random.default_rng(slots + heads)
    p, n = 16, 128
    state = jnp.asarray(rng.normal(size=(slots, heads, p, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(slots, heads, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.3, 1.0, (slots, heads)), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(slots, n)), jnp.float32)
            for _ in range(2))
    want_y, want_s = ssm._ssm_decode_reference(state, u, a, B, C)
    got_y, got_s = ssm._ssm_decode_call(state, u, a, B, C, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-4, rtol=1e-5)
    [call] = [e for e in jax.make_jaxpr(
        lambda *a: ssm._ssm_decode_call(*a, interpret=True))(
            state, u, a, B, C).eqns[0].params["jaxpr"].eqns
        if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((0, 0),)


def test_ssm_decode_takes_the_kernel_on_the_chip(monkeypatch):
    """On a TPU the step's update is the kernel (a call named
    ``zoo_ssm_decode``, in ``profile.KERNELS``); elsewhere the twin."""
    assert profile.KERNEL_SSM_DECODE == "zoo_ssm_decode"
    assert profile.KERNEL_SSM_DECODE in profile.KERNELS
    args = (jnp.zeros((2, 8, 4, 128)), jnp.ones((2, 8, 4)),
            jnp.ones((2, 8)), -jnp.ones(8), jnp.ones((2, 128)),
            jnp.ones((2, 128)), jnp.ones(8))
    def calls(jaxpr):       # the inner jits and what each runs
        return {e.params["name"]: [x.primitive.name
                                   for x in e.params["jaxpr"].eqns]
                for e in jaxpr.eqns if e.primitive.name == "jit"}

    # a new function each time: a traced function's jaxpr is cached
    assert calls(jax.make_jaxpr(lambda *a: ssm.ssm_decode(*a))(*args)) == {}
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    on = calls(jax.make_jaxpr(lambda *a: ssm.ssm_decode(*a))(*args))
    assert "pallas_call" in on["_ssm_decode_call"]


# ------------------------------------------------------------ the engine
def test_engine_logits_are_the_references_full_forward(served):
    """Prefill, then decode through the engine: at every served position
    the reference's logit of the served token is its best, for prompts
    shorter than the convolution's window, across chunk boundaries and
    at a bucket's exact length, in slots that share fused windows."""
    _, params, eng = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 13, 20, 9, 24, 2, 8)]
    outs = eng.generate(prompts, [40, 30, 25, 36, 20, 12, 16])
    assert served_gaps(params, prompts, outs).max() < 1e-3


def test_decode_step_logits_match_the_reference(served):
    """Logits, not tokens: the family's prefill + insert + decode steps,
    driven by hand in slot 1 of 2 (slot 0 keeps stepping on garbage),
    against the reference's row at each position."""
    net, params, _ = served
    hyper = net.hyper
    seq = np.random.default_rng(2).integers(0, 256, 40).astype(np.int32)
    n0 = 11
    want = ref_logits(params, seq)
    states = [tuple(jnp.zeros(shape, dtype) for shape, dtype in leaves)
              for leaves in fam.state_shapes(hyper, 2, 48, jnp.float32)]
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :n0] = seq[:n0]
    x, pst = fam.prefill(params, hyper, jnp.asarray(prompt), 16,
                         length=jnp.int32(n0))
    np.testing.assert_allclose(
        np.asarray(fam.head(params, hyper, x[0, :n0])), want[:n0],
        atol=2e-4, rtol=2e-4)
    states = fam.insert(hyper, states, pst, jnp.int32(1), jnp.int32(n0))
    for pos in range(n0, 40):
        tok = jnp.asarray([3, seq[pos]], jnp.int32)
        at = jnp.asarray([0, pos], jnp.int32)
        logits, states = fam.decode_step(params, hyper, states,
                                         fam.embed(params, tok, at), at)
        np.testing.assert_allclose(np.asarray(logits[1]), want[pos],
                                   atol=3e-4, rtol=3e-4)


def test_a_reused_slot_gives_a_fresh_engines_logits(served):
    """A slot freed by a long request and admitted into again serves
    what a fresh engine serves: the admission overwrites the state whole
    (a free slot's garbage and the old request's state reach nothing),
    and the padding of the bucket never enters it."""
    net, params, eng = served
    rng = np.random.default_rng(4)
    first = [rng.integers(0, 256, n).astype(np.int32) for n in (17, 23, 6)]
    eng.generate(first, [30, 25, 29])
    again = [rng.integers(0, 256, n).astype(np.int32) for n in (3, 14, 21)]
    reused = eng.generate(again, [20, 20, 20])
    fresh_eng = DecodeEngine(params, net.hyper, capacity=3, max_len=48,
                             prompt_buckets=BUCKETS, step_fuse=4)
    try:
        fresh = fresh_eng.generate(again, [20, 20, 20])
    finally:
        fresh_eng.close()
    for a, b in zip(reused, fresh):
        np.testing.assert_array_equal(a, b)
    assert served_gaps(params, again, reused).max() < 1e-3


def test_the_state_lies_where_the_family_says(served):
    """Typed per-layer state: a Mamba layer's window (capacity, 3, conv
    dim) in the weights' dtype and its float32 state; the attention
    layer's two slabs; only the attention layer's slab is counted in
    ``kv_positions_*``."""
    net, _, eng = served
    kinds = fam.layer_kinds(net.hyper)
    for kind, (a, b) in zip(kinds, eng._caches):
        if kind == "mamba":
            assert a.shape == (3, 3, 128 + 32) and b.shape == (3, 4, 32, 16)
            assert b.dtype == jnp.float32
        else:
            assert a.shape == b.shape == (3, 48, 2 * 16)
    assert eng._kv_kinds == [(48, 48, 1)]
    before = eng.stats()
    eng.generate([np.arange(1, 6, dtype=np.int32)] * 2, 4)
    s = eng.stats()
    assert s["admitted"] - before["admitted"] == 2


def test_the_engine_refuses_what_it_cannot_do_for_this_family(served):
    net, params, _ = served
    assert family_of(net.hyper).name == "granitemoehybrid"
    for kwargs, what in (({"prefix_pool": 2}, "prefix_pool"),
                         ({"draft_params": params,
                           "draft_hyper": net.hyper}, "draft"),
                         ({"mesh": {"axes": {"data": 2}}}, "mesh")):
        with pytest.raises(ValueError, match=what):
            DecodeEngine(params, net.hyper, capacity=2, **kwargs)
    with pytest.raises(ValueError, match="decode engine"):
        net.generate(np.zeros((1, 4), np.int32), 4)


def test_served_through_load_keras_net_and_generate_stream(served):
    """The normal path: ``InferenceModel(decode_capacity=...)``,
    ``load_keras_net``, ``generate_stream``."""
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel
    net, params, eng = served
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


@pytest.mark.parametrize("fault", ["bucket_end", "window_zero"])
def test_a_fault_in_the_admission_is_seen(served, monkeypatch, fault):
    """The two ways to lay a recurrent state down wrong: at the end of
    the bucket (the padding eaten), or without the convolution's window.
    The engine then serves tokens the reference does not put first."""
    net, params, _ = served
    real_prefill, real_insert = fam.prefill, fam.insert
    if fault == "bucket_end":
        monkeypatch.setattr(
            fam.FAMILY, "prefill",
            lambda p, h, prompt, cache_len, length=None: real_prefill(
                p, h, prompt, cache_len))
    else:
        def insert(hyper, caches, states, slot, length):
            states = [(jnp.zeros_like(a), b) if kind == "mamba" else (a, b)
                      for kind, (a, b) in zip(fam.layer_kinds(hyper),
                                              states)]
            return real_insert(hyper, caches, states, slot, length)
        monkeypatch.setattr(fam.FAMILY, "insert", insert)
    eng = DecodeEngine(params, net.hyper, capacity=3, max_len=48,
                       prompt_buckets=BUCKETS, step_fuse=4)
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 256, n).astype(np.int32)
                   for n in (5, 13, 19)]
        outs = eng.generate(prompts, [20, 20, 20])
    finally:
        eng.close()
    assert served_gaps(params, prompts, outs).max() > 1e-2


# --------------------------------------------------------- the layers
def test_scale_folded_into_the_queries_is_the_scaled_softmax():
    """``attention_multiplier`` 1/16 over heads of 16 is a factor of
    1/4 on the queries: exact in bfloat16, and the softmax of ``q k^T /
    16`` is what the layer computes."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 4, 9, 16)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(1, 2, 9, 16)), jnp.bfloat16)
            for _ in range(2))
    qs = A.scale_queries(q, 1 / 16)
    np.testing.assert_array_equal(np.asarray(qs, np.float32),
                                  np.asarray(q, np.float32) / 4)
    got = A.attention_gqa_bhsd(qs.astype(jnp.float32),
                               k.astype(jnp.float32), v.astype(jnp.float32))
    kr, vr = (jnp.repeat(t.astype(jnp.float32), 2, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr) / 16
    sc = jnp.where(jnp.tril(jnp.ones((9, 9), bool)), sc, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_rmsnorm_and_gated_norm():
    from analytics_zoo_tpu.pipeline.api.keras.layers import RMSNorm
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, 8)),
                    jnp.float32)
    g = jnp.linspace(0.5, 2.0, 8)
    layer = RMSNorm(1e-5)
    got = layer.call({"gamma": g}, {}, x)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    z = jnp.ones((3, 8)) * 2.0
    gated = ssm.gated_rmsnorm(x, z, g, 1e-5)
    xs = x * jax.nn.silu(2.0)
    np.testing.assert_allclose(
        np.asarray(gated),
        np.asarray(xs / np.sqrt(np.mean(np.square(xs), -1, keepdims=True)
                                + 1e-5) * g), atol=1e-5)
    assert layer.get_config()["epsilon"] == 1e-5


def test_the_families_without_recurrent_state_keep_their_slabs():
    """The seam's ``state_shapes`` gives the other two families exactly
    the key/value slab pairs they had (what keeps their plans' programs
    as they were)."""
    from analytics_zoo_tpu.models import generation, generation_cohere2moe
    hyper = {"n_layers": 3, "n_heads": 4, "d_model": 64}
    shapes = generation.TRANSFORMER_LM.state_shapes(hyper, 5, 32,
                                                    jnp.float32)
    assert shapes == [(((5, 32, 64), jnp.float32),) * 2] * 3
    chyper = {"n_layers": 2, "n_kv_heads": 2, "head_dim": 8,
              "sliding_window": 16,
              "layer_types": ["sliding_attention", "full_attention"]}
    got = generation_cohere2moe.FAMILY.state_shapes(chyper, 4, 32,
                                                    jnp.bfloat16)
    assert got == [(((4, 16, 16), jnp.bfloat16),) * 2,
                   (((4, 32, 16), jnp.bfloat16),) * 2]


def test_plans_hold_the_mixers_scopes(served):
    """The step and admit plans open ``zoo_ssm`` around each Mamba mixer,
    ``zoo_ssm_proj``, ``zoo_ssm_conv`` and ``zoo_ssm_scan`` inside it,
    ``zoo_mlp`` around each MLP, and the step the attention layer's
    ``zoo_decode_attention``."""
    net, params, eng = served
    weights = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (params, None))
    lowered = jax.jit(lambda *a: eng._step_body(*a)).lower(
        *eng._step_specs(), weights)
    text = lowered.as_text(debug_info=True)
    for scope in (profile.SCOPE_SSM, profile.SCOPE_SSM_PROJ,
                  profile.SCOPE_SSM_CONV, profile.SCOPE_SSM_SCAN,
                  profile.SCOPE_DECODE_ATTENTION, profile.SCOPE_MLP):
        assert scope in text, scope
    admit = eng._build_admit_fn(8)
    caches, tok, pos, samp = eng._state_specs()
    i0 = jax.ShapeDtypeStruct((), jnp.int32)
    f0 = jax.ShapeDtypeStruct((), jnp.float32)
    text = admit.lower(caches, [], tok, pos, samp,
                       jax.ShapeDtypeStruct((1, 8), jnp.int32), i0, i0,
                       i0, f0, i0, f0, weights).as_text(
        debug_info=True)
    for scope in (profile.SCOPE_SSM, profile.SCOPE_SSM_PROJ,
                  profile.SCOPE_SSM_CONV, profile.SCOPE_SSM_SCAN,
                  profile.SCOPE_MLP):
        assert scope in text, scope
    assert "zoo_prefill" not in text


def test_reference_control_is_another_forward():
    """The bfloat16 control differs from the float32 reference far more
    than bfloat16 weights upcast to float32 do."""
    stored = ref.make_params(CFG, 11)
    assert stored["mamba_0"]["in_proj"].dtype == jnp.bfloat16
    upcast = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), stored)
    x = np.random.default_rng(9).integers(0, 256, (1, 48)).astype(np.int32)
    a = np.asarray(ref.logits_fn(stored, jnp.asarray(x), CFG))
    b = np.asarray(ref.logits_fn(upcast, jnp.asarray(x), CFG))
    np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    c = np.asarray(ref.logits_fn(stored, jnp.asarray(x), CFG, "bf16"))
    assert np.abs(c - a).max() > 50 * np.abs(b - a).max()


def test_mamba2_initialisation_keeps_memory():
    """``A_log`` in log [1, 16], ``D`` 1, softplus(``dt_bias``) in
    [0.001, 0.1]: the state remembers across tens of positions."""
    p = ref.make_params(CFG, 3, jnp.float32)["mamba_0"]
    a = np.exp(np.asarray(p["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (a >= 1).all() and (a <= 16).all()
    assert (dt >= 1e-3 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert (np.asarray(p["D"]) == 1).all()
    assert isinstance(build(), GraniteHybridLM)
