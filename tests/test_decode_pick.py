"""The pick of the next token (ISSUE 32): a dispatch whose live slots
are all greedy takes its tokens by argmax alone; the sort of the
vocabulary, the cumulative sums and the draw run, in the other branch of
one ``conditional``, only when a live slot samples.

The pinned contracts:
* no stream changes: every engine flavour (single step, fused window,
  speculative window, slot-sharded over two devices) yields, in an
  all-greedy and in a mixed batch, the tokens of an engine whose every
  dispatch is forced through the sorted branch, which is the program of
  before this branch existed (``vmap(_pick)`` over all slots);
* greedy streams are those of ``build_generate_fn``'s static-greedy
  plan, which compiles to a bare argmax;
* in the sorted branch every row's token is ``vmap(_pick)``'s, a greedy
  row's the argmax in both branches;
* a sampled stream replays from its seed whoever decodes beside it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models import TransformerLM
from analytics_zoo_tpu.models.generation import build_generate_fn
from analytics_zoo_tpu.pipeline.inference import DecodeEngine
from analytics_zoo_tpu.pipeline.inference import decode as D

VOCAB, SEQ, BUCKET = 64, 48, 16
GREEDY = dict(temperature=0.0)
WARM = dict(temperature=0.8, top_k=16, top_p=0.9)
HOT = dict(temperature=1.1, top_p=0.95)
MIXES = {"all_greedy": [GREEDY] * 5,
         "mixed": [GREEDY, WARM, GREEDY, HOT, GREEDY]}
LENGTHS, MAX_NEWS = (4, 9, 6, 12, 3), (9, 4, 12, 6, 7)


@pytest.fixture(scope="module")
def lm():
    """A target whose blocks are scaled down, so that its 0-layer draft
    (embeddings, final norm, head) is often right and a speculative
    window really accepts: (params, hyper, draft params, draft hyper)."""
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                          d_model=32, n_heads=2)
    model.ensure_inference_ready()
    params = dict(model.trainer.state.params)
    for name in params:
        if name.startswith(("attn_", "mlp_", "ln_attn", "ln_mlp")):
            params[name] = jax.tree_util.tree_map(lambda a: a * 0.05,
                                                  params[name])
    draft = {k: params[k] for k in ("tok_embed", "pos_embed", "ln_final",
                                    "lm_head")}
    return params, model.hyper, draft, dict(model.hyper, n_layers=0,
                                            moe_every=0)


def prompts():
    rng = np.random.default_rng(67)
    return [rng.integers(0, VOCAB, n) for n in LENGTHS]


def build(lm, flavour):
    params, hyper, draft, draft_hyper = lm
    more = {"step": dict(step_fuse=1), "fused": dict(step_fuse=4),
            "spec": dict(draft_params=draft, draft_hyper=draft_hyper,
                         spec_tokens=4),
            "mesh": dict(mesh={"axes": {"tensor": 2}})}[flavour]
    return DecodeEngine(params, hyper, capacity=4, max_len=SEQ,
                        prompt_buckets=(BUCKET,), **more)


def serve(eng, mix):
    """Five requests into four slots, the mix's knobs one a request."""
    streams = [eng.submit(p, m, seed=10 + i, **kw) for i, (p, m, kw)
               in enumerate(zip(prompts(), MAX_NEWS, MIXES[mix]))]
    return [s.result(timeout=120).tolist() for s in streams]


@pytest.fixture(scope="module")
def as_before(lm):
    """{mix: streams} of an engine that takes the sorted branch in every
    dispatch: each token through ``vmap(_pick)``, as before."""
    eng = build(lm, "fused")
    eng._any_sampled = lambda: True
    try:
        out = {mix: serve(eng, mix) for mix in MIXES}
        stats = eng.stats()
    finally:
        eng.close()
    assert stats["steps_sorted"] == stats["steps"] > 0
    return out


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("flavour", ["step", "fused", "spec", "mesh"])
def test_no_stream_changes(lm, as_before, flavour, mix):
    eng = build(lm, flavour)
    try:
        assert serve(eng, mix) == as_before[mix]
        stats = eng.stats()
    finally:
        eng.close()
    if mix == "all_greedy":
        assert stats["steps_sorted"] == 0 < stats["steps"]
    else:
        assert 0 < stats["steps_sorted"] <= stats["steps"]
    if flavour == "spec":
        assert stats["spec_accepted"] > 0


def test_greedy_streams_are_the_static_greedy_plans(lm, as_before):
    params, hyper = lm[:2]
    for prompt, max_new, got in zip(prompts(), MAX_NEWS,
                                    as_before["all_greedy"]):
        padded = np.zeros((1, BUCKET), np.int32)
        padded[0, :len(prompt)] = prompt
        plan = build_generate_fn(hyper, BUCKET, max_new, 0.0, None,
                                 ragged=True)
        # greedy there is a Python value: no sort in the plan at all
        args = (params, padded, np.array([len(prompt)]),
                jax.random.PRNGKey(0))
        assert "stablehlo.sort" not in plan.lower(*args).as_text()
        assert np.asarray(plan(*args))[0].tolist() == got


def test_each_branch_picks_what_it_should():
    rows, vocab = 6, 257
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(rows, vocab)) * 3, jnp.float32)
    seed = jnp.arange(rows, dtype=jnp.int32) + 3
    index = jnp.asarray([0, 5, 1, 9, 2, 7], jnp.int32)
    temp = jnp.asarray([0.0, 0.8, 0.0, 1.3, 0.6, 0.0], jnp.float32)
    topk = jnp.asarray([0, 16, 5, 0, 40, 0], jnp.int32)
    topp = jnp.asarray([1.0, 0.9, 0.5, 0.95, 1.0, 1.0], jnp.float32)
    knobs = (seed, index, temp, topk, topp)
    argmax = np.argmax(np.asarray(logits), axis=-1)
    want = np.asarray(jax.vmap(D._pick)(logits, *knobs))
    sorted_ = np.asarray(D._pick_tokens(logits, *knobs, True))
    assert sorted_.dtype == np.int32 and (sorted_ == want).all()
    greedy = np.asarray(temp) == 0.0
    assert (sorted_[greedy] == argmax[greedy]).all()
    assert (sorted_[~greedy] != argmax[~greedy]).any()     # it did draw
    bare = np.asarray(D._pick_tokens(logits, *knobs, False))
    assert bare.dtype == np.int32 and (bare == argmax).all()
    # one row with scalars, as an admission picks its first token
    for r in range(rows):
        one = [k[r] for k in knobs]
        assert int(D._pick_tokens(logits[r], *one, True)) == want[r]
        assert int(D._pick_tokens(logits[r], *one, False)) == argmax[r]


def test_sampled_stream_replays_beside_other_requests(lm):
    """The same (prompt, knobs, seed) alone, and beside three greedy
    requests whose dispatches it turns to the sorted branch: the same
    stream; the greedy neighbours' streams do not notice either."""
    eng = build(lm, "fused")
    p = prompts()
    try:
        alone = eng.generate([p[1]], [10], seed=8, timeout=120, **WARM)[0]
        quiet = eng.generate(p[2:], [10, 6, 7], timeout=120)
        sorted_before = eng.stats()["steps_sorted"]
        greedy = [eng.submit(q, m) for q, m in zip(p[2:], (10, 6, 7))]
        sampled = eng.submit(p[1], 10, seed=8, **WARM)
        assert sampled.result(timeout=120).tolist() == alone.tolist()
        for s, want in zip(greedy, quiet):
            assert s.result(timeout=120).tolist() == want.tolist()
        assert eng.stats()["steps_sorted"] > sorted_before
    finally:
        eng.close()
