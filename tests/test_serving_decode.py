"""Continuous-batching decode engine (ISSUE 7): slot-array stepping,
iteration-level scheduling, and the serving wiring.

The pinned contracts:
* a slot stepped one token at a time is BIT-identical to
  ``TransformerLM.generate``'s compiled scan for the same prompt
  (both sides padded to the same prompt bucket — XLA CPU kernels
  differ per batch shape, so the comparison must hold the shape
  fixed);
* exactly one decode-executable compile per (bucket, capacity): a
  warmed engine serves a staggered arrival/completion schedule that
  sweeps occupancy 1..capacity under ``zoolint.sanitize(max_compiles=
  0)`` — admission and eviction are state writes, never recompiles;
* fused-window dispatch (``step_fuse > 1``) changes per-dispatch
  overhead, never the token stream;
* EOS/max_new eviction frees slots for queued requests (admitted
  count > capacity through one engine);
* the crash net: a dispatcher death fails every live + queued stream
  with the original error and closes the engine to later submits.
"""

import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.models import TransformerLM
from analytics_zoo_tpu.pipeline.inference import (DecodeEngine,
                                                  DecodeEngineClosedError,
                                                  InferenceModel)
from analytics_zoo_tpu.pipeline.inference.decode import TokenStream
from analytics_zoo_tpu.serving import ModelRegistry
from analytics_zoo_tpu.serving.metrics import registry_families

VOCAB, SEQ, BUCKET = 64, 48, 16


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                          d_model=32, n_heads=2)
    model.ensure_inference_ready()
    return model


@pytest.fixture(scope="module")
def engine(lm):
    """One shared warmed engine (capacity 3, one prompt bucket) for the
    read-only tests; tests that mutate engine internals build their
    own."""
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=3,
                       max_len=SEQ, prompt_buckets=(BUCKET,))
    eng.warmup()
    yield eng
    eng.close()


def scan_ref(lm, prompt, max_new):
    """The scan-path comparator: same prompt padded to the SAME bucket
    the engine uses (same compiled shape -> bit-comparable)."""
    L = len(prompt)
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :L] = prompt
    full = lm.generate(padded, max_new_tokens=max_new, temperature=0.0,
                       prompt_lengths=np.array([L]))
    return np.asarray(full[0, L:L + max_new], np.int32)


# ---------------------------------------------------------------- equivalence
def test_step_decode_matches_scan_decode(lm, engine):
    """Satellite 1: a slot stepped one token at a time is bit-identical
    to the compiled-scan generate for the same (ragged) prompts —
    including prompts decoded CONCURRENTLY in neighboring slots, which
    is the whole point of the per-slot masking."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, int(n))
               for n in (3, 7, BUCKET, 5, 11, 2)]
    max_news = [9, 4, 12, 7, 3, 12]
    outs = engine.generate(prompts, max_news, timeout=120)
    for p, mn, out in zip(prompts, max_news, outs):
        ref = scan_ref(lm, p, mn)
        assert np.array_equal(out, ref), (p.tolist(), out, ref)


def test_fused_windows_change_overhead_not_tokens(lm):
    """step_fuse=1 (pure per-step) and step_fuse=4 (fused ladder)
    produce identical streams — fusion may never cross a scheduling
    event, so the schedule (and the tokens) are invariant."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (4, 9, 6, 13)]
    max_news = [11, 5, 8, 2]
    outs = {}
    for fuse in (1, 4):
        eng = DecodeEngine(lm.trainer.state.params, lm.hyper,
                           capacity=2, max_len=SEQ,
                           prompt_buckets=(BUCKET,), step_fuse=fuse)
        try:
            eng.warmup()
            outs[fuse] = eng.generate(prompts, max_news, timeout=120)
            if fuse == 4:
                assert eng.stats()["fused_dispatches"] > 0
        finally:
            eng.close()
    for a, b in zip(outs[1], outs[4]):
        assert np.array_equal(a, b)


def test_eos_evicts_early_and_is_included(lm, engine):
    """EOS stops the slot's stream AT the EOS token (included), exactly
    where the scan path's continuation first emits it."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, VOCAB, 6)
    ref = scan_ref(lm, prompt, 12)
    eos = int(ref[4])
    stop = int(np.argmax(ref == eos))  # first occurrence
    out = engine.generate([prompt], [12], eos_id=eos, timeout=120)[0]
    assert np.array_equal(out, ref[:stop + 1])
    assert int(out[-1]) == eos


# ------------------------------------------------------------- compile pin
def test_one_compile_per_plan_at_every_occupancy(lm, zoolint_sanitize):
    """The acceptance-criteria pin: a warmed engine serves a staggered
    schedule that holds occupancy at EVERY level 1..capacity (ramping
    up and draining down) with ZERO further XLA compiles — the
    sanitizer's exact compile counter is the witness.  Transfer guards
    ride along: every host<->device hop in the loop must be explicit.
    """
    capacity = 3
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper,
                       capacity=capacity, max_len=SEQ,
                       prompt_buckets=(BUCKET,))
    eng.warmup()
    rng = np.random.default_rng(0)
    try:
        with zoolint_sanitize(max_compiles=0):
            # deterministic occupancy sweep: for k = 1..capacity run k
            # concurrent requests to completion (occupancy exactly k
            # while they decode), then ramp DOWN through staggered
            # completions: capacity concurrent requests with strictly
            # increasing max_new, so the batch thins capacity -> 1
            # as short members evict and nothing refills
            for k in range(1, capacity + 1):
                streams = [eng.submit(rng.integers(0, VOCAB, 4 + i), 6)
                           for i in range(k)]
                for s in streams:
                    assert s.result(timeout=120).shape == (6,)
            streams = [eng.submit(rng.integers(0, VOCAB, 5),
                                  4 * (i + 1))
                       for i in range(capacity)]
            for i, s in enumerate(streams):
                assert s.result(timeout=120).shape == (4 * (i + 1),)
        stats = eng.stats()
        assert stats["prefill_misses"] == {BUCKET: 1}
        assert stats["admitted"] == sum(range(1, capacity + 1)) + capacity
        assert stats["slots_active"] == 0
    finally:
        eng.close()


def test_slots_recycle_beyond_capacity(engine):
    """More live requests than slots: eviction frees slots for queued
    requests mid-run, every stream completes, bookkeeping balances."""
    before = engine.stats()
    rng = np.random.default_rng(5)
    n = 10  # > 3x capacity
    prompts = [rng.integers(0, VOCAB, int(rng.integers(2, BUCKET + 1)))
               for _ in range(n)]
    max_news = [int(rng.integers(1, 10)) for _ in range(n)]
    outs = engine.generate(prompts, max_news, timeout=120)
    assert [len(o) for o in outs] == max_news
    after = engine.stats()
    assert after["admitted"] - before["admitted"] == n
    assert after["evicted"] - before["evicted"] == n
    assert after["slots_active"] == 0
    assert after["queued"] == 0
    # a second pass over the same bucket must be pure cache hits
    assert after["prefill_misses"] == before["prefill_misses"]


# ------------------------------------------------------------- streaming API
def test_token_stream_iterates_incrementally(lm, engine):
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, VOCAB, 8)
    ref = scan_ref(lm, prompt, 10)
    stream = engine.submit(prompt, 10)
    got = list(stream)
    assert np.array_equal(np.asarray(got, np.int32), ref)
    assert stream.done
    # result() after exhaustion returns the same tokens
    assert np.array_equal(stream.result(timeout=1), ref)


def test_token_stream_result_timeout(engine):
    s = TokenStream(request_id=1)  # never finished by anyone
    with pytest.raises(TimeoutError):
        s.result(timeout=0.05)


def test_submit_validation(engine):
    with pytest.raises(ValueError, match="non-empty 1-D"):
        engine.submit(np.zeros((2, 3), np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit([1, 2, 3], 0)
    with pytest.raises(ValueError, match="exceeds the largest"):
        engine.submit(np.zeros(BUCKET + 1, np.int32), 4)
    with pytest.raises(ValueError, match="exceeds"):
        engine.submit(np.zeros(BUCKET, np.int32), SEQ)  # > max_len


def test_generate_batch_validation_is_all_or_nothing(engine):
    """A bad late row must fail the WHOLE batch before any row is
    queued — otherwise earlier rows decode into abandoned streams,
    burning slots the caller gave up on."""
    before = engine.stats()
    with pytest.raises(ValueError, match="exceeds the largest"):
        engine.generate([np.ones(4, np.int32),
                         np.zeros(BUCKET + 1, np.int32)], 4)
    assert engine.stats()["admitted"] == before["admitted"]


def test_engine_config_validation(lm):
    params, hyper = lm.trainer.state.params, lm.hyper
    with pytest.raises(ValueError, match="capacity"):
        DecodeEngine(params, hyper, capacity=0)
    with pytest.raises(ValueError, match="prefix_pool"):
        DecodeEngine(params, hyper, capacity=1, prefix_pool=-1)
    with pytest.raises(ValueError, match="positional table"):
        DecodeEngine(params, hyper, capacity=1, max_len=SEQ + 1)
    with pytest.raises(ValueError, match="room to decode"):
        DecodeEngine(params, hyper, capacity=1, max_len=8,
                     prompt_buckets=(8,))


# ---------------------------------------------------------------- lifecycle
def test_close_drains_then_rejects(lm):
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,))
    eng.warmup()
    rng = np.random.default_rng(1)
    streams = [eng.submit(rng.integers(0, VOCAB, 4), 8)
               for _ in range(4)]  # 2 queued behind 2 active
    eng.close()
    # graceful drain: everything submitted BEFORE close completes
    for s in streams:
        assert s.result(timeout=120).shape == (8,)
    with pytest.raises(DecodeEngineClosedError):
        eng.submit(rng.integers(0, VOCAB, 4), 2)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crash_net_fails_all_streams(lm):
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,))
    eng.warmup()
    boom = RuntimeError("injected decode crash")
    # the crash waits for the last submit: a submit AFTER the crash is
    # refused (DecodeEngineClosedError), which is the case further down
    all_in = threading.Event()

    def exploding(*a, **kw):
        all_in.wait(timeout=30)
        raise boom

    eng._step_fn = exploding
    eng._stepk_fns = {k: exploding for k in eng._stepk_fns}
    rng = np.random.default_rng(2)
    streams = [eng.submit(rng.integers(0, VOCAB, 4), 8)
               for _ in range(4)]
    all_in.set()
    for s in streams:
        with pytest.raises(RuntimeError, match="injected decode crash"):
            s.result(timeout=60)
    # the engine is dead: later submits must not strand
    deadline = time.time() + 10
    while not eng.closed and time.time() < deadline:
        time.sleep(0.02)
    with pytest.raises(DecodeEngineClosedError):
        eng.submit(rng.integers(0, VOCAB, 4), 2)


def test_concurrent_submitters(lm, engine):
    """Many threads streaming through one engine: per-thread outputs
    stay bit-exact vs the scan path (no cross-request bleed)."""
    rng = np.random.default_rng(17)
    cases = [(rng.integers(0, VOCAB, int(rng.integers(2, 12))),
              int(rng.integers(1, 9))) for _ in range(8)]
    refs = [scan_ref(lm, p, mn) for p, mn in cases]
    outs = [None] * len(cases)
    errs = []

    def worker(i):
        try:
            outs[i] = engine.submit(cases[i][0], cases[i][1]) \
                .result(timeout=120)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(cases))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    for out, ref in zip(outs, refs):
        assert np.array_equal(out, ref)


def test_unwarmed_engine_serves_and_late_warmup_raises(lm):
    """The dispatcher starts lazily at the first submit, so an
    unwarmed engine serves (paying its compiles inline), and a warmup
    AFTER serving began — which would rebind the donated decode state
    under a live dispatcher — is refused instead of racing."""
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,))
    try:
        rng = np.random.default_rng(31)
        prompt = rng.integers(0, VOCAB, 5)
        out = eng.submit(prompt, 4).result(timeout=120)
        assert np.array_equal(out, scan_ref(lm, prompt, 4))
        assert eng.stats()["prefill_misses"] == {BUCKET: 1}
        with pytest.raises(RuntimeError, match="before the first"):
            eng.warmup()
    finally:
        eng.close()


# ------------------------------------------------------- serving integration
def test_inference_model_generate_wiring(lm):
    im = InferenceModel(supported_concurrent_num=2, decode_capacity=2,
                        decode_prompt_buckets=(BUCKET,))
    im.load_keras_net(lm)
    try:
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, VOCAB, 5), rng.integers(0, VOCAB, 9)]
        outs = im.generate(prompts, [6, 3], timeout=120)
        assert np.array_equal(outs[0], scan_ref(lm, prompts[0], 6))
        assert np.array_equal(outs[1], scan_ref(lm, prompts[1], 3))
        stream = im.generate_stream(prompts[0], 6)
        assert np.array_equal(stream.result(timeout=120), outs[0])
        stats = im.serving_stats()
        assert stats["decode"]["capacity"] == 2
        assert stats["decode"]["tokens"] >= 15
    finally:
        im.close()


def test_inference_model_without_engine_raises(lm):
    im = InferenceModel(supported_concurrent_num=1)
    im.load_keras_net(lm)
    try:
        with pytest.raises(RuntimeError, match="no decode engine"):
            im.generate([[1, 2, 3]], 4)
    finally:
        im.close()


def test_decode_capacity_requires_lm():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    net = Sequential()
    net.add(Dense(4, input_shape=(3,)))
    im = InferenceModel(supported_concurrent_num=1, decode_capacity=2)
    with pytest.raises(ValueError, match="generation-capable"):
        im.load_keras_net(net)


def test_failed_reload_leaves_handle_on_old_version(lm):
    """A reload whose decode-engine build fails must leave BOTH planes
    on the old version — a half-swapped handle (new predict plane,
    stale generate engine) is the one state no caller can reason
    about."""
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    im = InferenceModel(supported_concurrent_num=1, decode_capacity=2,
                        decode_prompt_buckets=(BUCKET,))
    im.load_keras_net(lm)
    try:
        rng = np.random.default_rng(37)
        prompt = rng.integers(0, VOCAB, 5)
        before = im.generate([prompt], [4], timeout=120)[0]
        old_engine = im.decode_engine
        bad = Sequential()
        bad.add(Dense(4, input_shape=(3,)))
        with pytest.raises(ValueError, match="generation-capable"):
            im.load_keras_net(bad)  # validation fires BEFORE any swap
        assert im.decode_engine is old_engine
        assert not old_engine.closed
        after = im.generate([prompt], [4], timeout=120)[0]
        assert np.array_equal(before, after)
        # the predict plane still serves the LM graph too, not Dense
        out = im.predict(np.zeros((1, BUCKET), np.int32))
        assert np.asarray(out).shape[-1] == VOCAB
    finally:
        im.close()


# ------------------------------------------------- decode engine v2
def test_sampled_streams_replay_and_occupancy_invariance(lm, engine):
    """The sampling contract: a (prompt, sampling params, seed) tuple
    replays bit-identically, and the stream is invariant to WHO ELSE
    is decoding — the per-slot fold_in key depends only on (seed,
    absolute token index), and a slot's logits only on its own
    cache."""
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (4, 9, 6)]
    kw = dict(temperature=0.8, top_k=16, top_p=0.95)
    a = engine.generate(prompts, [8, 5, 7], seed=[7, 8, 9],
                        timeout=120, **kw)
    b = engine.generate(prompts, [8, 5, 7], seed=[7, 8, 9],
                        timeout=120, **kw)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    # the same request ALONE (occupancy 1, different slot schedule)
    alone = engine.generate([prompts[1]], [5], seed=8, timeout=120,
                            **kw)[0]
    assert np.array_equal(alone, a[1])
    # and a different seed diverges (astronomically unlikely to
    # collide on every token at temperature 0.8)
    c = engine.generate([prompts[1]], [5], seed=1234, timeout=120,
                        **kw)[0]
    assert not np.array_equal(c, a[1])
    assert engine.stats()["sampled_tokens"] >= 27


def test_greedy_requests_share_the_sampling_plan_bit_exact(lm, engine):
    """temperature=0 THROUGH the sampling-capable step plan still
    argmaxes — greedy and sampled requests decode side by side in one
    dispatch and the greedy stream stays pinned to the scan path."""
    rng = np.random.default_rng(43)
    gp, sp = rng.integers(0, VOCAB, 6), rng.integers(0, VOCAB, 9)
    ref = scan_ref(lm, gp, 8)
    s_greedy = engine.submit(gp, 8)
    s_sampled = engine.submit(sp, 8, temperature=1.1, seed=5)
    out_g = s_greedy.result(timeout=120)
    s_sampled.result(timeout=120)
    assert np.array_equal(out_g, ref)


def test_sampling_validation(engine):
    with pytest.raises(ValueError, match="temperature"):
        engine.submit([1, 2], 4, temperature=-0.5)
    with pytest.raises(ValueError, match="temperature"):
        engine.submit([1, 2], 4, temperature=float("nan"))
    with pytest.raises(ValueError, match="top_k"):
        engine.submit([1, 2], 4, temperature=0.5, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        engine.submit([1, 2], 4, temperature=0.5, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        engine.submit([1, 2], 4, temperature=0.5, top_p=1.5)
    with pytest.raises(ValueError, match="seed"):
        engine.submit([1, 2], 4, seed=-1)
    with pytest.raises(ValueError, match="seed"):
        engine.generate([[1, 2]], [4], seed=[2 ** 40])


@pytest.fixture(scope="module")
def shared_prefix_requests(lm):
    """A shared-system-prompt mix: every prompt opens with the same
    8-token prefix (= the small bucket, so the pool splits there) and
    carries its own tail."""
    rng = np.random.default_rng(47)
    sys_prompt = rng.integers(0, VOCAB, 8)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, VOCAB, int(u))])
               for u in (3, 5, 2, 0, 7)]
    return sys_prompt, prompts


def _pool_engine(lm, size):
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(8, BUCKET),
                       prefix_pool=size)
    eng.warmup()
    return eng


def test_prefix_pool_hits_and_streams_match_pool_off(
        lm, shared_prefix_requests):
    """Pool hits serve the SAME streams as a pool-less engine (one
    prefix prefill for the whole mix instead of five), and a repeat
    pass is all hits."""
    _, prompts = shared_prefix_requests
    max_news = [6] * len(prompts)
    pooled = _pool_engine(lm, size=4)
    plain = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                         max_len=SEQ, prompt_buckets=(8, BUCKET))
    plain.warmup()
    try:
        o_pool = pooled.generate(prompts, max_news, timeout=120)
        o_plain = plain.generate(prompts, max_news, timeout=120)
        for a, b in zip(o_pool, o_plain):
            assert np.array_equal(a, b), (a, b)
        st = pooled.stats()
        assert st["prefix_misses"] == 1  # one compute of the prefix
        assert st["prefix_hits"] == len(prompts) - 1
        o2 = pooled.generate(prompts, max_news, timeout=120)
        for a, b in zip(o2, o_pool):
            assert np.array_equal(a, b)
        assert pooled.stats()["prefix_misses"] == 1  # still one
    finally:
        pooled.close()
        plain.close()


def test_prefix_pool_eviction_recomputes_never_wrong(lm):
    """Memory pressure: a 1-entry pool alternating two prefixes
    evicts every admission — each recomputes its OWN prefix (streams
    stay bit-identical to the first pass), never serves the other's
    block."""
    rng = np.random.default_rng(53)
    pfx_a, pfx_b = (rng.integers(0, VOCAB, 8) for _ in range(2))
    pa = np.concatenate([pfx_a, rng.integers(0, VOCAB, 4)])
    pb = np.concatenate([pfx_b, rng.integers(0, VOCAB, 4)])
    eng = _pool_engine(lm, size=1)
    try:
        ref_a = eng.generate([pa], [6], timeout=120)[0]
        ref_b = eng.generate([pb], [6], timeout=120)[0]
        for _ in range(2):  # thrash: a evicts b evicts a ...
            assert np.array_equal(
                eng.generate([pa], [6], timeout=120)[0], ref_a)
            assert np.array_equal(
                eng.generate([pb], [6], timeout=120)[0], ref_b)
        st = eng.stats()
        assert st["prefix_evictions"] >= 4, st
        assert st["prefix_hits"] == 0  # every admission recomputed
        assert st["prefix_pool_entries"] == 1
    finally:
        eng.close()


def test_prefix_pool_zero_further_compiles(lm, zoolint_sanitize,
                                           shared_prefix_requests):
    """A warmed pooled engine serves eligible (split) AND ineligible
    (short, monolithic) prompts — hits, misses, evictions — with ZERO
    further compiles: every (prefix, bucket) pair plan was warmed."""
    _, prompts = shared_prefix_requests
    eng = _pool_engine(lm, size=1)
    rng = np.random.default_rng(59)
    try:
        with zoolint_sanitize(max_compiles=0):
            eng.generate(prompts, [4] * len(prompts), timeout=120)
            eng.generate([rng.integers(0, VOCAB, 3)], [4],
                         timeout=120)  # < smallest bucket: monolithic
            eng.generate([rng.integers(0, VOCAB, 16)], [4],
                         timeout=120)  # exact-bucket prefix, no tail
    finally:
        eng.close()


def _skeleton_draft(lm):
    """The 0-layer draft: the target's embedding/unembedding skeleton
    (token+position embed -> final LN -> lm_head) — the cheapest
    possible proposer, supported by the generic decode math."""
    params = lm.trainer.state.params
    dparams = {k: params[k] for k in ("tok_embed", "pos_embed",
                                      "ln_final", "lm_head")}
    return dparams, dict(lm.hyper, n_layers=0, moe_every=0)


def _spec_engine(lm, dparams, dhyper, k=4, params=None):
    eng = DecodeEngine(params if params is not None
                       else lm.trainer.state.params,
                       lm.hyper, capacity=3, max_len=SEQ,
                       prompt_buckets=(BUCKET,), draft_params=dparams,
                       draft_hyper=dhyper, spec_tokens=k)
    eng.warmup()
    return eng


def test_spec_forced_full_rejection_is_bit_exact(lm):
    """The fallback pin: a draft that ALWAYS proposes token 0 against
    a target that NEVER emits it (lm_head bias -1e9 on token 0)
    forces full rejection on every window — acceptance 0, one exact
    token per window, streams bit-identical to the same target
    decoding non-speculatively.  By construction, not by luck: the
    exact token is the same traced step body the plain plan runs."""
    import jax.numpy as jnp

    params = lm.trainer.state.params
    tweaked = dict(params)
    head = dict(params["lm_head"])
    head["b"] = jnp.asarray(
        np.asarray(head["b"]).copy()
        + np.eye(1, np.asarray(head["b"]).shape[0], 0)[0] * -1e9)
    tweaked["lm_head"] = head
    dparams, dhyper = _skeleton_draft(lm)
    dhead = dict(head)
    dhead["b"] = jnp.asarray(np.asarray(params["lm_head"]["b"]).copy()
                             + np.eye(1, np.asarray(head["b"]).shape[0],
                                      0)[0] * 1e9)
    dparams = dict(dparams)
    dparams["lm_head"] = dhead

    rng = np.random.default_rng(61)
    prompts = [rng.integers(1, VOCAB, int(n)) for n in (4, 9, 6)]
    max_news = [9, 4, 7]
    spec = _spec_engine(lm, dparams, dhyper, params=tweaked)
    plain = DecodeEngine(tweaked, lm.hyper, capacity=3, max_len=SEQ,
                         prompt_buckets=(BUCKET,))
    plain.warmup()
    try:
        o_spec = spec.generate(prompts, max_news, timeout=120)
        o_plain = plain.generate(prompts, max_news, timeout=120)
        for a, b in zip(o_spec, o_plain):
            assert np.array_equal(a, b), (a, b)
        st = spec.stats()
        assert st["spec_proposed"] > 0
        assert st["spec_accepted"] == 0  # full rejection, every window
        assert st["spec_acceptance"] == 0.0
        assert not any(0 in np.asarray(o) for o in o_spec)
    finally:
        spec.close()
        plain.close()


def test_spec_streams_match_non_spec_and_accept(lm):
    """The general case: a residual-dominated target (block outputs
    down-scaled, the agreement regime a distilled draft provides)
    against its 0-layer skeleton draft — real acceptance, streams
    still identical to the non-speculative engine, greedy AND
    sampled."""
    import jax

    params = lm.trainer.state.params
    scaled = jax.tree_util.tree_map(lambda a: a, dict(params))
    for name in list(scaled):
        if name.startswith(("attn_", "mlp_", "ln_attn", "ln_mlp",
                            "moe_")):
            scaled[name] = jax.tree_util.tree_map(
                lambda a: a * 0.05, scaled[name])
    dparams, dhyper = _skeleton_draft(lm)
    rng = np.random.default_rng(67)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (4, 9, 6, 12)]
    max_news = [9, 4, 12, 6]
    spec = _spec_engine(lm, dparams, dhyper, params=scaled)
    plain = DecodeEngine(scaled, lm.hyper, capacity=3, max_len=SEQ,
                         prompt_buckets=(BUCKET,))
    plain.warmup()
    try:
        o_spec = spec.generate(prompts, max_news, timeout=120)
        o_plain = plain.generate(prompts, max_news, timeout=120)
        for a, b in zip(o_spec, o_plain):
            assert np.array_equal(a, b), (a, b)
        st = spec.stats()
        assert st["spec_accepted"] > 0, st
        # sampled verification: the window positions draw from the
        # same fold_in keys the plain engine uses -> identical streams
        s_spec = spec.generate(prompts, max_news, temperature=0.7,
                               top_k=24, seed=[1, 2, 3, 4],
                               timeout=120)
        s_plain = plain.generate(prompts, max_news, temperature=0.7,
                                 top_k=24, seed=[1, 2, 3, 4],
                                 timeout=120)
        for a, b in zip(s_spec, s_plain):
            assert np.array_equal(a, b), (a, b)
    finally:
        spec.close()
        plain.close()


def test_spec_config_validation(lm):
    params, hyper = lm.trainer.state.params, lm.hyper
    dparams, dhyper = _skeleton_draft(lm)
    with pytest.raises(ValueError, match="BOTH draft_params"):
        DecodeEngine(params, hyper, draft_params=dparams)
    with pytest.raises(ValueError, match="spec_tokens"):
        DecodeEngine(params, hyper, draft_params=dparams,
                     draft_hyper=dhyper, spec_tokens=1)
    with pytest.raises(ValueError, match="vocabulary"):
        DecodeEngine(params, hyper, draft_params=dparams,
                     draft_hyper=dict(dhyper, vocab_size=7))
    with pytest.raises(ValueError, match="mutually"):
        DecodeEngine(params, hyper, draft_params=dparams,
                     draft_hyper=dhyper, prefix_pool=2)


def test_registry_generate_and_decode_families(lm):
    from analytics_zoo_tpu.observability import Tracer

    tracer = Tracer()
    reg = ModelRegistry(tracer=tracer)
    try:
        reg.deploy("lm", lm, decode_capacity=2,
                   decode_prompt_buckets=(BUCKET,))
        rng = np.random.default_rng(29)
        prompt = rng.integers(0, VOCAB, 6)
        out, info = reg.generate_ex("lm", [prompt], 5)
        assert np.array_equal(out[0], scan_ref(lm, prompt, 5))
        assert info["model"] == "lm" and info["version"] == 1
        # the span carries the decode phase taxonomy
        trace = tracer.find(info["request_id"])
        phases = {p["name"] for p in trace["phases"]}
        assert {"prefill", "decode_step"} <= phases, phases
        # control-plane counters tick on the generate path too
        snap = reg.metrics("lm")["lm"]
        assert snap["versions"][1]["requests"] == 1
        # satellite 2: the Prometheus bridge exports the decode
        # families off the same snapshot
        fams = {f.name: f for f in registry_families(reg.metrics())}
        for name in ("zoo_decode_tokens_total", "zoo_decode_steps_total",
                     "zoo_decode_slot_occupancy",
                     "zoo_decode_slot_capacity"):
            assert name in fams, name
        (tok_labels, tok_v), = fams["zoo_decode_tokens_total"].samples
        assert tok_labels["model"] == "lm" and tok_v == 5
        (cap_labels, cap_v), = fams["zoo_decode_slot_capacity"].samples
        assert cap_labels["model"] == "lm" and cap_v == 2
        assert fams["zoo_decode_tokens_total"].mtype == "counter"
        assert fams["zoo_decode_slot_occupancy"].mtype == "gauge"
    finally:
        reg.shutdown()


# ------------------------------------- first tokens behind the next window
class Tap:
    """An engine's plans and ``jax.device_get`` wrapped, so that a test
    sees the ORDER in which the dispatcher enqueues work and waits for
    results: ``log`` holds ``("admit", slot, first token on the device)``,
    ``("window", slots live at the dispatch, its tokens on the device)``
    and ``("get", what was fetched)``.  ``hold()`` stops the dispatcher
    at its next plan call until ``release()``, which is how a test gets
    several requests into ONE round of admissions; ``fail_firsts`` makes
    the fetch of a first token raise."""

    def __init__(self, eng, monkeypatch):
        import jax

        self.eng, self.log = eng, []
        self.fail_firsts = None
        self._gate = threading.Event()
        self._gate.set()
        self._get = jax.device_get
        for b, fn in list(eng._admit_fns.items()):
            eng._admit_fns[b] = self._admit(fn, 7)
        for key, fn in list(eng._pfxadmit_fns.items()):
            eng._pfxadmit_fns[key] = self._admit(fn, 8)
        eng._step_fn = self._window(eng._step_fn, 1)
        for k, fn in list(eng._stepk_fns.items()):
            eng._stepk_fns[k] = self._window(fn, 4)
        monkeypatch.setattr(jax, "device_get", self._logged_get)

    def hold(self):
        self._gate.clear()

    def release(self):
        self._gate.set()

    def _admit(self, fn, slot_arg):
        def admit(*args):
            assert self._gate.wait(timeout=60)
            out = fn(*args)
            self.log.append(("admit", int(args[slot_arg]), out[-1]))
            return out
        return admit

    def _window(self, fn, toks_out):
        def window(*args):
            assert self._gate.wait(timeout=60)
            out = fn(*args)
            live = tuple(s for s, r in enumerate(self.eng._slots)
                         if r is not None)
            self.log.append(("window", live, out[toks_out]))
            return out
        return window

    def _logged_get(self, x):
        self.log.append(("get", x))
        if self.fail_firsts is not None and any(
                e[0] == "admit" and e[2] is x for e in self.log):
            raise self.fail_firsts
        return self._get(x)

    def at(self, kind, obj=None):
        """Indices in the log of the events of one kind (about one
        device value)."""
        return [i for i, e in enumerate(self.log)
                if e[0] == kind and (obj is None or e[-1] is obj)]


def tapped_engine(lm, monkeypatch, capacity=3, **kw):
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper,
                       capacity=capacity, max_len=SEQ,
                       prompt_buckets=kw.pop("prompt_buckets", (BUCKET,)),
                       **kw)
    eng.warmup()
    return eng, Tap(eng, monkeypatch)


def test_first_token_is_fetched_behind_the_next_window(lm, monkeypatch):
    """The order that keeps the device's queue full: admit plan, then
    the dispatch of a window that holds the new slot, then the fetch of
    the window that was in flight, and only then the fetch of the first
    token."""
    eng, tap = tapped_engine(lm, monkeypatch)
    rng = np.random.default_rng(71)
    try:
        prompts = [rng.integers(0, VOCAB, int(n))
                   for n in (3, 9, 5, 12, 2, 7, 4)]
        eng.generate(prompts, [9, 4, 12, 7, 3, 6, 10], timeout=120)
    finally:
        eng.close()
    admits, windows = tap.at("admit"), tap.at("window")
    assert len(admits) == 7
    behind_a_window_in_flight = 0
    for i in admits:
        _, slot, tok0 = tap.log[i]
        [got] = tap.at("get", tok0)
        # a window that names the slot was dispatched in between
        after = [w for w in windows if i < w < got]
        assert after and slot in tap.log[after[0]][1], (i, got)
        # the window that was in flight when the plan was dispatched is
        # fetched behind that dispatch and before the first token
        before = [w for w in windows if w < i]
        if before:
            [prev] = tap.at("get", tap.log[before[-1]][2])
            assert prev < got
            if prev > i:
                behind_a_window_in_flight += 1
                assert after[0] < prev
    # seven requests into three slots: some were admitted mid-flight
    assert behind_a_window_in_flight >= 2


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_streams_equal_a_one_at_a_time_reference(lm, sampled):
    """Seven requests of mixed lengths through three slots give, each,
    the tokens it gets alone in a one-slot engine that serves one
    request at a time (nothing in flight beside it, nothing behind it)."""
    rng = np.random.default_rng(73)
    prompts = [rng.integers(0, VOCAB, int(n))
               for n in (3, 9, BUCKET, 12, 2, 7, 4)]
    max_news = [9, 1, 12, 7, 3, 6, 10]
    kw = (dict(temperature=0.9, top_k=20, top_p=0.9) if sampled else {})
    seeds = list(range(11, 18))
    outs = []
    for capacity in (1, 3):
        eng = DecodeEngine(lm.trainer.state.params, lm.hyper,
                           capacity=capacity, max_len=SEQ,
                           prompt_buckets=(BUCKET,))
        eng.warmup()
        try:
            if capacity == 1:
                outs.append([eng.generate([p], [m], seed=s, timeout=120,
                                          **kw)[0]
                             for p, m, s in zip(prompts, max_news, seeds)])
            else:
                outs.append(eng.generate(prompts, max_news, seed=seeds,
                                         timeout=120, **kw))
                st = eng.stats()
                assert st["admitted"] == 7
                assert st["first_tokens_deferred"] == 6     # one of 1 token
        finally:
            eng.close()
    for alone, together, m in zip(*outs, max_news):
        assert len(alone) == m and np.array_equal(alone, together)


@pytest.mark.parametrize("pool", [0, 2], ids=["monolithic", "pooled"])
def test_request_that_ends_at_its_first_token(lm, pool):
    """``max_new == 1`` never takes a slot; a first token equal to
    ``eos_id`` is seen when it is fetched, a window late: either way the
    stream holds exactly one token, the counters count it once and the
    slot serves the next request."""
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=1,
                       max_len=SEQ, prompt_buckets=(8, BUCKET),
                       prefix_pool=pool)
    eng.warmup()
    rng = np.random.default_rng(79)
    prompt, other = rng.integers(0, VOCAB, 11), rng.integers(0, VOCAB, 9)

    def delta(run):
        before = eng.stats()
        out = run()
        after = eng.stats()
        assert after["slots_active"] == 0 and after["queued"] == 0
        return out, {k: after[k] - before[k] for k in (
            "admitted", "evicted", "tokens", "first_tokens_deferred")}

    try:
        ref = eng.generate([prompt], [6], timeout=120)[0]
        ref_other = eng.generate([other], [5], timeout=120)[0]
        one, d = delta(lambda: eng.generate([prompt], [1], timeout=120)[0])
        assert np.array_equal(one, ref[:1])
        assert d == {"admitted": 1, "evicted": 1, "tokens": 1,
                     "first_tokens_deferred": 0}
        eos = int(ref[0])
        one, d = delta(lambda: eng.generate([prompt], [6], eos_id=eos,
                                            timeout=120)[0])
        assert np.array_equal(one, ref[:1])
        assert d == {"admitted": 1, "evicted": 1, "tokens": 1,
                     "first_tokens_deferred": 1}

        # back to back through the ONE slot: each ends at its first
        # token and the next request gets the slot and its own stream
        def mixed():
            streams = [eng.submit(prompt, 1),
                       eng.submit(other, 5),
                       eng.submit(prompt, 6, eos_id=eos),
                       eng.submit(other, 5)]
            return [s.result(timeout=120) for s in streams]
        outs, d = delta(mixed)
        assert d == {"admitted": 4, "evicted": 4, "tokens": 12,
                     "first_tokens_deferred": 3}
        for out, want in zip(outs, (ref[:1], ref_other, ref[:1],
                                    ref_other)):
            assert np.array_equal(out, want), (out, want)
    finally:
        eng.close()


def test_admissions_of_one_round_share_a_window(lm, monkeypatch):
    """Two requests found queued in one round: both admit plans, ONE
    window that holds both slots, then both first tokens in the order of
    admission."""
    eng, tap = tapped_engine(lm, monkeypatch)
    rng = np.random.default_rng(83)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (6, 4, 10)]
    try:
        tap.hold()      # the dispatcher stops inside the lead's admit plan
        streams = [eng.submit(p, m) for p, m in zip(prompts, (8, 7, 5))]
        tap.release()
        outs = [s.result(timeout=120) for s in streams]
    finally:
        tap.release()
        eng.close()
    for p, m, out in zip(prompts, (8, 7, 5), outs):
        assert np.array_equal(out, scan_ref(lm, p, m))
    kinds = [e[0] for e in tap.log]
    assert kinds[:4] == ["admit", "admit", "admit", "window"]
    slots = [e[1] for e in tap.log[:3]]
    assert sorted(tap.log[3][1]) == sorted(slots) == [0, 1, 2]
    # no window was in flight, so the three first tokens come next
    assert kinds[4:7] == ["get"] * 3
    for e, first in zip(tap.log[4:7], tap.log[:3]):
        assert e[1] is first[2]
    assert eng.stats()["first_tokens_deferred"] == 3


def test_close_with_first_tokens_pending_serves_them(lm, monkeypatch):
    """``close()`` while admissions are dispatched and no first token is
    fetched yet: the drain is graceful, requests of one token included."""
    eng, tap = tapped_engine(lm, monkeypatch, capacity=2)
    rng = np.random.default_rng(89)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (6, 4, 10, 3)]
    max_news = [1, 7, 5, 1]
    closer = threading.Thread(target=eng.close, kwargs={"timeout": 60})
    try:
        tap.hold()
        streams = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
        closer.start()          # the sentinel queues behind the four
        time.sleep(0.05)
        tap.release()
        for p, m, s in zip(prompts, max_news, streams):
            assert np.array_equal(s.result(timeout=120),
                                  scan_ref(lm, p, m))
    finally:
        tap.release()
        closer.join(timeout=60)
    assert not closer.is_alive() and eng.closed
    assert not eng._firsts and eng.stats()["slots_active"] == 0


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_failed_first_token_fetch_strands_no_caller(lm, monkeypatch):
    """The crash net with first tokens pending: one of a request that
    never held a slot (one token), two of live slots (three pending
    first tokens are a round's bound at three slots), one request still
    queued.  Every stream ends with the error."""
    eng, tap = tapped_engine(lm, monkeypatch)
    tap.fail_firsts = RuntimeError("injected first-token fetch failure")
    rng = np.random.default_rng(97)
    try:
        tap.hold()
        streams = [eng.submit(rng.integers(0, VOCAB, 5), m)
                   for m in (1, 8, 6, 4)]
        tap.release()
        for s in streams:
            with pytest.raises(RuntimeError, match="first-token fetch"):
                s.result(timeout=60)
    finally:
        tap.release()
    deadline = time.time() + 10
    while not eng.closed and time.time() < deadline:
        time.sleep(0.02)
    with pytest.raises(DecodeEngineClosedError):
        eng.submit(rng.integers(0, VOCAB, 4), 2)
    # the one-token request and two that took slots, their window, the
    # fetch that failed
    assert [e[0] for e in tap.log] == ["admit"] * 3 + ["window", "get"]
    assert len(tap.log[3][1]) == 2
    assert not eng._firsts and eng.stats()["slots_active"] == 0


def test_deferred_count_and_loop_phases_sum_to_the_wall():
    """Every admission but those of one token has its first token
    fetched behind a window, and the loop's phases (``admit_fetch`` now
    beside ``admit``, none inside another) still sum to its wall.  The
    sum is taken over the dispatcher's whole life, once ``close`` has
    joined it: a phase still in flight at a snapshot is counted only
    when it ends, so a window between two snapshots may miss one."""
    from analytics_zoo_tpu.pipeline.inference.decode import LOOP_PHASES

    # wide enough that a step outweighs the loop's own bookkeeping
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                          d_model=256, n_heads=2)
    model.ensure_inference_ready()
    eng = DecodeEngine(model.trainer.state.params, model.hyper,
                       capacity=3, max_len=SEQ, prompt_buckets=(BUCKET,))
    eng.warmup()
    rng = np.random.default_rng(101)
    max_news = [1 if i % 8 == 7 else 10 for i in range(120)]
    lengths = rng.integers(2, BUCKET, 120)
    assert not any(eng.stats()[f"loop_{p}_s"] for p in LOOP_PHASES)
    try:
        # the dispatcher starts at the first submit
        t0 = time.perf_counter()
        streams = [eng.submit(rng.integers(0, VOCAB, int(n)), m)
                   for n, m in zip(lengths, max_news)]
        [s.result(timeout=120) for s in streams]
    finally:
        eng.close(timeout=60)
    wall = time.perf_counter() - t0
    assert not eng._thread.is_alive()
    done = eng.stats()
    assert done["admitted"] == 120 and done["evicted"] == 120
    assert done["first_tokens_deferred"] == 120 - max_news.count(1)
    assert done["tokens"] == sum(max_news)
    loop_s = sum(done[f"loop_{p}_s"] for p in LOOP_PHASES)
    assert 0.95 * wall <= loop_s <= wall, (loop_s, wall)
    assert done["loop_admit_fetch_s"] > 0


# ----------------------------- the round after an eviction and the freed caller
def test_round_after_an_eviction_waits_for_the_freed_caller(lm, monkeypatch):
    """A caller in a closed loop asks again once its stream has ended,
    which takes it a moment.  The round after the eviction waits for
    that request at its look at the queue, so the request is admitted
    before the next window goes out and no window is dispatched with the
    freed slot empty.  (Without the wait the dispatcher, which holds the
    interpreter when it wakes the caller, wins that race or loses it
    from run to run, and losing costs the request a whole window.)"""
    from analytics_zoo_tpu.pipeline.inference import decode

    # far longer than the caller below takes: the order is then certain
    monkeypatch.setattr(decode, "_RESUBMIT_WAIT_S", 30.0)
    eng, tap = tapped_engine(lm, monkeypatch, capacity=2)
    rng = np.random.default_rng(103)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (5, 4, 7)]
    max_news = [30, 6, 5]
    try:
        tap.hold()      # both into one round
        long, short = (eng.submit(p, m)
                       for p, m in zip(prompts[:2], max_news[:2]))
        tap.release()
        first = list(short)             # read token by token to the end,
        time.sleep(0.05)                # take a while to come back,
        again = eng.submit(prompts[2], max_news[2])     # and ask again
        outs = [long.result(timeout=120), np.asarray(first, np.int32),
                again.result(timeout=120)]
    finally:
        tap.release()
        eng.close()
    for p, m, out in zip(prompts, max_news, outs):
        assert np.array_equal(out, scan_ref(lm, p, m))
    admits = tap.at("admit")
    assert len(admits) == 3
    # up to the third admission every window held both slots
    before = [tap.log[w][1] for w in tap.at("window") if w < admits[2]]
    assert before and all(len(live) == 2 for live in before)


def test_no_caller_behind_a_freed_slot_costs_one_short_wait(lm, monkeypatch):
    """Nobody asks again: the round after the eviction waits its short
    while, as ``idle``, and the loop goes on with the slots that are
    left."""
    from analytics_zoo_tpu.pipeline.inference import decode

    # long enough to tell from the engine's own 50 ms idle polls
    monkeypatch.setattr(decode, "_RESUBMIT_WAIT_S", 0.2)
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,))
    eng.warmup()
    rng = np.random.default_rng(107)
    prompts = [rng.integers(0, VOCAB, int(n)) for n in (5, 4)]
    try:
        eng.generate(prompts[:1], 2, timeout=120)   # dispatcher started
        idle0 = eng.stats()["loop_idle_s"]
        t0 = time.perf_counter()
        outs = eng.generate(prompts, [30, 4], timeout=120)
        took = time.perf_counter() - t0
        idle = eng.stats()["loop_idle_s"] - idle0
    finally:
        eng.close()
    for p, m, out in zip(prompts, (30, 4), outs):
        assert np.array_equal(out, scan_ref(lm, p, m))
    # the short request's eviction was waited on once (the long one's is
    # still being waited on, or was: a phase counts when it ends)
    assert 0.2 <= idle and took < 0.2 * 2 + 30 * 0.1
