"""The names a device profile goes by (ISSUE 26): the program's host
spans on the profiler's clock (``profile.annotate``), the decode
dispatcher's always-on loop counters, the ``jax.named_scope`` regions,
the pinned program names and the kernels' names; (ISSUE 27) the
dispatcher's count of the slab positions its steps had live and read;
and (ISSUE 32) which branch of the pick a dispatch's steps take.

All on the CPU: a profiler session here records host events only, which
is what the spans are.  Read back with ``jax.profiler.ProfileData``, the
same way the benchmark's ``program_spans.py`` reads a chip's trace."""

import glob
import importlib
import operator
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models import TransformerLM
from analytics_zoo_tpu.observability import profile
from analytics_zoo_tpu.ops.attention import flash_attention
from analytics_zoo_tpu.pipeline.inference import DecodeEngine
from analytics_zoo_tpu.pipeline.inference.decode import LOOP_PHASES
from analytics_zoo_tpu.serving.metrics import registry_families

VOCAB, SEQ, BUCKET = 64, 48, 16


def zoo(name):
    full = profile.SPAN_PREFIX + name
    assert full in profile.SPANS, full      # no name is retyped unpinned
    return full


class Traced:
    """A profiler session around a block (the python tracer off, as the
    benchmark has it); afterwards ``events`` holds the ``zoo/`` host
    events as (thread line, name, start_ns, end_ns, stats)."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.events = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        [path] = glob.glob(self.dir + "/plugins/profile/*/*.xplane.pb")
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            # threads may share a name: a line is one thread
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(profile.SPAN_PREFIX):
                        self.events.append(
                            (thread, ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns, dict(ev.stats)))

    def named(self, name):
        return [e for e in self.events if e[1] == zoo(name)]

    def threads(self, *names):
        return {e[0] for n in names for e in self.named(n)}


def inside(child, parent):
    return (child[0] == parent[0] and parent[2] <= child[2]
            and child[3] <= parent[3])


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                          d_model=32, n_heads=2)
    model.ensure_inference_ready()
    return model


def new_engine(lm, **kw):
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=3,
                       max_len=SEQ, prompt_buckets=(BUCKET,), **kw)
    eng.warmup()
    return eng


def prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, int(k))
            for k in rng.integers(2, BUCKET, n)]


# ------------------------------------------------------------ host spans
def test_annotate_is_inert_without_a_session_and_cheap():
    """No switch: with no profiler session an annotation does nothing,
    for well under the issue's budget (10 us a training step for 3)."""
    t0 = time.perf_counter()
    for _ in range(2000):
        with profile.annotate("decode/dispatch", k=4, live=3):
            pass
    assert (time.perf_counter() - t0) / 2000 < 20e-6
    assert type(profile.annotate("train/step", step_num=1)).__name__ \
        == "StepTraceAnnotation"
    assert type(profile.annotate("train/data_wait")).__name__ \
        == "TraceAnnotation"


def test_decode_spans_land_in_the_profile_with_their_stats(lm, tmp_path):
    eng = new_engine(lm)
    try:
        eng.generate(prompts(2), 4, timeout=120)    # dispatcher started
        with Traced(tmp_path) as tr:
            eng.generate(prompts(5, seed=1), [9, 4, 12, 7, 3],
                         timeout=120)
    finally:
        eng.close()
    assert {e[1] for e in tr.events} <= set(profile.SPANS)
    work = ("decode/admit", "decode/admit_fetch", "decode/dispatch",
            "decode/fetch", "decode/fanout")
    for name in work:
        assert tr.named(name), name
    assert len(tr.threads(*work)) == 1      # the dispatcher's line
    admits = tr.named("decode/admit")
    assert len(admits) == 5
    for ev in admits:
        stats = ev[4]
        assert stats["bucket"] == BUCKET and 0 <= stats["slot"] < 3
        assert 2 <= stats["length"] < BUCKET
        assert stats["queue_wait_us"] >= 0
    # five requests into three slots: the last two waited for an eviction
    assert max(e[4]["queue_wait_us"] for e in admits) > 0
    # a first token is waited for beside its admission, not inside it:
    # after the admit span, and after the dispatch of the window that was
    # queued behind the admit plan (``deferred``: it holds the slot)
    fetches = tr.named("decode/admit_fetch")
    assert len(fetches) == len(admits)
    dispatches = tr.named("decode/dispatch")
    for admit, fetch in zip(sorted(admits, key=operator.itemgetter(2)),
                            sorted(fetches, key=operator.itemgetter(2))):
        assert fetch[4]["deferred"] == 1
        assert not any(inside(fetch, a) for a in admits)
        assert any(admit[3] <= d[2] and d[3] <= fetch[2]
                   for d in dispatches)
    for ev in tr.named("decode/dispatch"):
        stats = ev[4]
        assert stats["k"] in (1, 2, 4) and 1 <= stats["live"] <= 3
        # every live slot has its new row at least, and no more than the
        # slab; off the chip a step reads the whole slab of each
        floor = stats["k"] * stats["live"]
        assert floor <= stats["kv_positions_live"] <= floor * SEQ
        assert stats["kv_positions_read"] == floor * SEQ
        assert stats["pick_sorted"] == 0        # nobody samples
        # slabs all alike and full-length: no ring, nothing skipped to say
        assert "kv_positions_window_skipped" not in stats
    fanouts = tr.named("decode/fanout")
    # first tokens leave beside their admission (admit_fetch); the rest
    # through the fan-out
    assert sum(e[4]["tokens"] for e in fanouts) == 9 + 4 + 12 + 7 + 3 - 5
    assert sum(e[4]["evicted"] for e in fanouts) == 5

def test_decode_wait_for_a_freed_caller_is_a_marked_idle_span(lm, tmp_path):
    """The round after an eviction that finds nothing queued waits a
    short while for the freed caller with a window in flight: a wait for
    work, so ``idle``, marked ``resubmit``."""
    eng = new_engine(lm)
    try:
        eng.generate(prompts(1), 2, timeout=120)    # dispatcher started
        with Traced(tmp_path) as tr:
            eng.generate(prompts(2, seed=2), [14, 3], timeout=120)
    finally:
        eng.close()
    waits = tr.named("decode/idle")
    assert waits and all(e[4]["resubmit"] == 1 for e in waits)
    evictions = [f for f in tr.named("decode/fanout") if f[4]["evicted"]]
    dispatches = tr.named("decode/dispatch")
    # behind the fan-out that freed the slot, before the next dispatch
    assert all(any(f[3] <= w[2] and not any(f[3] <= d[2] <= w[2]
                                            for d in dispatches)
                   for f in evictions) for w in waits)
    assert len(tr.threads("decode/idle", "decode/fanout")) == 1


def test_decode_idle_span(lm, tmp_path):
    eng = new_engine(lm)
    try:
        eng.generate(prompts(1), 2, timeout=120)
        with Traced(tmp_path) as tr:
            time.sleep(0.2)         # nothing to do: the loop waits for work
    finally:
        eng.close()
    idles = tr.named("decode/idle")
    assert idles and len(tr.threads("decode/idle")) == 1
    # the wait is at most 50 ms long and the loop does nothing else
    assert sum(e[3] - e[2] for e in idles) > 0.1e9


def test_fit_spans_land_in_the_profile(tmp_path):
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.train import triggers

    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,)))
    m.add(Dense(2))
    m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    rs = np.random.RandomState(0)
    x = rs.rand(32, 4).astype(np.float32)
    y = rs.randint(0, 2, 32).astype(np.int32)
    m.fit(x, y, batch_size=8, nb_epoch=1)           # compiled
    m.trainer.set_checkpoint(str(tmp_path / "ckpt"),
                             trigger=triggers.SeveralIteration(2))
    first = m.trainer.state.step + 1
    with Traced(tmp_path / "trace") as tr:
        m.fit(x, y, batch_size=8, nb_epoch=1)
    assert {e[1] for e in tr.events} <= set(profile.SPANS)
    steps = tr.named("train/step")
    dispatches = tr.named("train/step_dispatch")
    assert len(dispatches) == 4
    # one span a step, numbered, plus the one whose wait found the
    # epoch's source exhausted
    assert [e[4]["step_num"] for e in steps] \
        == [first, first + 1, first + 2, first + 3, first + 4]
    for name in ("train/data_wait", "train/step_dispatch",
                 "train/ckpt_save"):
        children = tr.named(name)
        assert children, name
        for child in children:
            assert sum(inside(child, s) for s in steps) == 1, name
    assert len(tr.named("train/ckpt_save")) == 2
    [fetch] = tr.named("train/loss_fetch")
    assert fetch[4]["steps"] == 4
    loop = tr.threads("train/step", "train/loss_fetch")
    assert len(loop) == 1
    feeder = tr.threads("input/produce", "input/h2d")
    assert len(feeder) == 1 and feeder != loop
    assert len(tr.named("input/h2d")) == 4


# ---------------------------------------------------- always-on counters
def test_loop_counters_are_monotone_and_sum_to_the_loops_wall():
    keys = ["queue_wait_s"] + [f"loop_{p}_s" for p in LOOP_PHASES]
    # wide enough that a step outweighs the loop's own bookkeeping (what
    # no phase covers: the same few microseconds a round whatever the
    # model, so the narrower the model and the less the loop waits, the
    # larger their share)
    model = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                          d_model=256, n_heads=2)
    model.ensure_inference_ready()
    eng = new_engine(model)

    def snapshot():
        ta = time.perf_counter()
        stats = eng.stats()
        return ta, time.perf_counter(), stats

    try:
        assert [eng.stats()[k] for k in keys] == [0.0] * 7
        snaps = [snapshot()]
        # a backlog keeps the dispatcher busy, so at each snapshot the
        # phase in flight (counted only when it ends) is a short one
        streams = [eng.submit(p, 12) for p in prompts(200)]
        for k in (5, 60, 120, 190):
            streams[k].result(timeout=120)
            snaps.append(snapshot())
        [s.result(timeout=120) for s in streams]
        time.sleep(0.12)        # idle: waits of at most 50 ms
        snaps.append(snapshot())
    finally:
        eng.close()
    for (_, _, a), (_, _, b) in zip(snaps, snaps[1:]):
        for k in keys:
            assert b[k] >= a[k], k
    for k in keys:
        assert snaps[-1][2][k] > 0.0, k
    (t0a, t0b, s0), (t1a, t1b, s1) = snaps[1], snaps[4]
    loop_s = sum(s1[f"loop_{p}_s"] - s0[f"loop_{p}_s"]
                 for p in LOOP_PHASES)
    assert 0.95 * (t1a - t0b) <= loop_s <= 1.05 * (t1b - t0a), \
        (loop_s, t1a - t0b, t1b - t0a)
    assert s1["admitted"] > s0["admitted"]
    assert s1["queue_wait_s"] > s0["queue_wait_s"]


def test_kv_position_counters_add_up_on_a_scripted_run(lm):
    """One request of ``L`` prompt tokens stepped one at a time: step
    ``i`` finds ``L + 1 + i`` live positions (the prompt, the tokens so
    far, its own new row) and, off the chip, reads the whole slab."""
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,), step_fuse=1)
    L, new = 7, 12
    try:
        s0 = eng.stats()
        assert s0["kv_positions_live"] == s0["kv_positions_read"] == 0
        eng.generate([np.arange(L)], new, timeout=120)
        s1 = eng.stats()
    finally:
        eng.close()
    # the loop is one step ahead of what it has fetched: it may have
    # dispatched one step past the request's last
    assert new - 1 <= s1["steps"] <= new
    assert s1["kv_positions_live"] == sum(L + 1 + i
                                          for i in range(s1["steps"]))
    assert s1["kv_positions_read"] == s1["steps"] * SEQ


@pytest.mark.parametrize("spec", [False, True])
def test_a_finished_sampled_request_does_not_hold_the_sort_on(
        lm, tmp_path, spec):
    """A sampled request finishes beside two greedy ones and nobody is
    admitted into its slot, which keeps the request's temperature on the
    device.  The dispatches' ``pick_sorted`` is 1 while the dispatcher
    holds the request, 0 for the rest of the run; it is the flag the
    plan was called with, so the branch the device takes; and
    ``steps_sorted`` is the ``k`` of the sorted dispatches."""
    more = {}
    if spec:
        params = lm.trainer.state.params
        more = dict(draft_params={k: params[k] for k in (
            "tok_embed", "pos_embed", "ln_final", "lm_head")},
            draft_hyper=dict(lm.hyper, n_layers=0, moe_every=0),
            spec_tokens=2)
    eng = new_engine(lm, **more)
    flags = []

    def recording(fn):
        def call(*args):
            flags.append(bool(args[-1]))
            return fn(*args)
        return call

    if spec:
        eng._spec_fn = recording(eng._spec_fn)
    else:
        eng._step_fn = recording(eng._step_fn)
        eng._stepk_fns = {k: recording(f)
                          for k, f in eng._stepk_fns.items()}
    p = prompts(3, seed=2)
    try:
        with Traced(tmp_path) as tr:
            greedy = [eng.submit(q, 30) for q in p[:2]]
            sampled = eng.submit(p[2], 3, temperature=0.9, top_k=8,
                                 seed=4)
            sampled.result(timeout=120)
            [s.result(timeout=120) for s in greedy]
        stats = eng.stats()
        stale = np.asarray(eng._samp[2])
    finally:
        eng.close()
    dispatches = sorted(tr.named("decode/dispatch"), key=lambda e: e[2])
    took = [e[4]["pick_sorted"] for e in dispatches]
    assert took == [int(f) for f in flags]
    last = max(i for i, t in enumerate(took) if t)
    assert 1 in took[:last + 1] and set(took[last + 1:]) == {0}
    assert len(took) - last > 5             # and stayed off
    assert (stale > 0).sum() == 1           # the freed slot's, still there
    assert stats["steps_sorted"] == sum(
        e[4]["k"] for e in dispatches if e[4]["pick_sorted"])
    assert 0 < stats["steps_sorted"] < stats["steps"] == sum(
        e[4]["k"] for e in dispatches)


def test_kv_positions_read_rounds_up_to_the_kernels_block(lm, monkeypatch):
    """Where the kernel runs, a step reads a slot's slab up to the block
    that holds its newest row, and the window's steps one after
    another."""
    import importlib
    A = importlib.import_module("analytics_zoo_tpu.ops.attention")
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    wide = TransformerLM(vocab_size=VOCAB, seq_len=256, n_layers=1,
                         d_model=128, n_heads=2)
    wide.ensure_inference_ready()
    eng = DecodeEngine(wide.trainer.state.params, wide.hyper, capacity=3,
                       max_len=256, prompt_buckets=(BUCKET,))
    try:
        assert eng._kv_kinds == [(256, 128, 1)]     # rows, block, layers
        assert eng._kv_block == 128     # one kind: counted as before kinds

        class Req:
            def __init__(self, length, scheduled):
                self.length, self.scheduled = length, scheduled

        eng._slots = [Req(100, 27), None, Req(10, 1)]
        # lengths 127, 128, 129 and 11, 12, 13
        assert eng._kv_positions(3) == (127 + 128 + 129 + 11 + 12 + 13,
                                        128 + 128 + 256 + 3 * 128, 0)
        eng._slots = [None, Req(200, 55), None]     # clamped to the slab
        assert eng._kv_positions(2) == (255 + 256, 256 + 256, 0)
        eng._slots = [None] * 3
        assert eng._kv_positions(4) == (0, 0, 0)
    finally:
        eng.close()


def test_loop_counters_reach_prometheus(lm):
    from analytics_zoo_tpu.serving import ModelRegistry

    reg = ModelRegistry()
    try:
        reg.deploy("lm", lm, decode_capacity=2,
                   decode_prompt_buckets=(BUCKET,))
        reg.generate("lm", prompts(1), 4)
        fams = {f.name: f for f in registry_families(reg.metrics())}
        reg.generate("lm", prompts(1), 4, temperature=0.7, seed=1)
        after = {f.name: f for f in registry_families(reg.metrics())}
    finally:
        reg.shutdown()
    wait = fams["zoo_decode_queue_wait_seconds_total"]
    assert wait.mtype == "counter" and wait.samples[0][1] >= 0
    loop = fams["zoo_decode_loop_seconds_total"]
    assert {labels["phase"] for labels, _ in loop.samples} \
        == set(LOOP_PHASES)
    assert sum(v for _, v in loop.samples) > 0
    kv = fams["zoo_decode_kv_positions_total"]
    assert kv.mtype == "counter"
    by_kind = {labels["kind"]: v for labels, v in kv.samples}
    assert set(by_kind) == {"live", "read", "window_skipped"}
    assert 0 < by_kind["live"] <= by_kind["read"]
    assert by_kind["window_skipped"] == 0       # no windowed layer here
    moe = fams["zoo_decode_moe_total"]
    assert moe.mtype == "counter"
    assert {labels["kind"]: v for labels, v in moe.samples} == {
        "assignments": 0, "assignments_held": 0, "experts_hit": 0}
    steps = fams["zoo_decode_steps_total"].samples[0][1]
    picked = fams["zoo_decode_steps_sorted_total"]
    assert picked.mtype == "counter" and picked.samples[0][1] == 0 < steps
    assert 0 < after["zoo_decode_steps_sorted_total"].samples[0][1] \
        <= after["zoo_decode_steps_total"].samples[0][1] - steps


# ------------------------------------------------- names inside programs
def test_train_step_holds_its_scopes_and_its_name():
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,)))
    m.add(Dense(2))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    tr = m.trainer
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8,), np.int32)
    lowered = tr.lower_train_step(x, y)
    text = lowered.as_text(debug_info=True)
    assert f"module @{profile.PROGRAM_TRAIN_STEP} " in text
    for scope in (profile.SCOPE_LOSS, profile.SCOPE_OPTIMIZER_UPDATE):
        assert scope in text, scope
    assert profile.SCOPE_GRAD_ACCUM not in text
    tr.accum_steps = 2
    tr.invalidate_compiled()
    text = tr.lower_train_step(x, y).as_text(debug_info=True)
    assert profile.SCOPE_GRAD_ACCUM in text


def lowered_plans(eng):
    """Every jitted decode program of ``eng``, lowered: {module name:
    text with the scopes}."""
    texts = {}
    real = eng._plan

    def capture(name, jitted, arg_specs):
        weights = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            eng._weights)
        text = jitted.lower(*arg_specs, weights).as_text(debug_info=True)
        texts[text.split("module @", 1)[1].split(" ", 1)[0]] = text
        return real(name, jitted, arg_specs)

    eng._plan = capture
    eng.warmup()
    return texts


def test_decode_programs_hold_their_scopes_and_their_names(lm):
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,),
                       prefix_pool=2)
    try:
        texts = lowered_plans(eng)
    finally:
        eng.close()
    draft = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=1,
                          d_model=16, n_heads=2)
    draft.ensure_inference_ready()
    eng = DecodeEngine(lm.trainer.state.params, lm.hyper, capacity=2,
                       max_len=SEQ, prompt_buckets=(BUCKET,),
                       draft_params=draft.trainer.state.params,
                       draft_hyper=draft.hyper, spec_tokens=3)
    try:
        texts.update(lowered_plans(eng))
    finally:
        eng.close()
    decode = set(profile.PROGRAMS) - {profile.PROGRAM_TRAIN_STEP}
    assert set(texts) == decode
    for name in (profile.PROGRAM_STEP, profile.PROGRAM_STEPK,
                 profile.PROGRAM_SPEC):
        for scope in (profile.SCOPE_SAMPLE, profile.SCOPE_DECODE_ATTENTION,
                      profile.SCOPE_MLP):
            assert scope in texts[name], (name, scope)
    for name in (profile.PROGRAM_ADMIT, profile.PROGRAM_PADMIT):
        assert profile.SCOPE_SAMPLE in texts[name], name
    # the pick branches on the device in every plan that picks a token
    for name in decode - {profile.PROGRAM_FILL}:
        assert "stablehlo.case" in texts[name], name
    assert "stablehlo.case" not in texts[profile.PROGRAM_FILL]
    # the admissions are partitioned into parts, the drafted and the
    # prefix-pooled ones too; the program's name says "prefill"
    for name in (profile.PROGRAM_ADMIT, profile.PROGRAM_PADMIT,
                 profile.PROGRAM_FILL):
        assert "zoo_prefill" not in texts[name], name
        assert "" not in located_parts(texts[name], name), name


def located_parts(text, program=profile.PROGRAM_ADMIT):
    """The innermost ``profile.ADMIT_PARTS`` name of every located
    operation of a lowered module's ``main`` (its text with debug info),
    constants and the function's returns aside; ``""`` for an operation
    under none.  A private function's operations are called from one of
    main's and take that call's path: on the chip XLA inlines the call
    under its name."""
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def path(ref):
        loc = locs.get(ref, "")
        named = re.match(r'loc\("([^"]*)"', loc)
        if named:
            return named.group(1)
        call = re.match(r"loc\(callsite\((#loc\d+) at", loc)
        return path(call.group(1)) if call else ""

    main = text.split("func.func public @main", 1)[1].split(
        "\n  func.func ", 1)[0]
    parts = []
    for line in main.splitlines()[1:]:
        op = line.strip()
        if op.startswith(("return ", "stablehlo.return ", "} loc(")) \
                or "stablehlo.constant " in op:
            continue
        ref = re.search(r"loc\((#loc\d+)\)$", op)
        if ref is None:
            continue
        scopes = re.split(r"[/()]", path(ref.group(1)))
        assert scopes[0] == "jit" and scopes[1] == program[4:], op
        names = [s for s in scopes if s in profile.ADMIT_PARTS]
        parts.append(names[-1] if names else "")
    assert parts
    return parts


def admit_and_step_texts(eng, bucket):
    """The lowered (debug-info) text of ``eng``'s admit plan for
    ``bucket`` and of its fused window plan."""
    texts = {}

    def capture(name, jitted, arg_specs):
        weights = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            eng._weights)
        texts[name] = jitted.lower(*arg_specs, weights).as_text(
            debug_info=True)
        return lambda *a: None

    eng._plan = capture
    eng._ensure_step_plans()
    eng._admit_fn_for(bucket)
    return texts[f"admit{bucket}"], texts[f"step{max(eng._fuse_sizes)}"]


def family_engine(family):
    """A tiny served model of each family and an engine of 2 slots over
    its float32 weights."""
    if family == "transformer_lm":
        net = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                            d_model=32, n_heads=2)
        net.ensure_inference_ready()
    else:
        t = importlib.import_module(family)
        net = t.build()
        net.compile("sgd", "class_nll")
        net.trainer.adopt_weights(t.ref.make_params(t.CFG, 7, jnp.float32))
    return DecodeEngine(net.trainer.state.params, net.hyper, capacity=2,
                        max_len=SEQ, prompt_buckets=(BUCKET,), step_fuse=2)


#: per family, the parts of its admission and the scopes its fused
#: window holds (the names accepted readers go by, and ``zoo_mlp``)
FAMILIES = {
    "transformer_lm": (
        {"zoo_embed", "zoo_norm", "zoo_attn_proj", "zoo_attn_core",
         "zoo_mlp", "zoo_head", "zoo_insert", "zoo_sample"},
        (profile.SCOPE_DECODE_ATTENTION, profile.SCOPE_SAMPLE,
         profile.SCOPE_MLP)),
    "test_commandaplus": (
        {"zoo_embed", "zoo_norm", "zoo_attn_proj", "zoo_attn_core",
         "zoo_moe_router", "zoo_moe_experts", "zoo_moe_shared", "zoo_head",
         "zoo_insert", "zoo_sample"},
        (profile.SCOPE_DECODE_ATTENTION, profile.SCOPE_SAMPLE,
         profile.SCOPE_MOE, profile.SCOPE_MOE_ROUTER,
         profile.SCOPE_MOE_EXPERTS, profile.SCOPE_MOE_SHARED)),
    "test_granitehybrid": (
        {"zoo_embed", "zoo_norm", "zoo_attn_proj", "zoo_attn_core",
         "zoo_mlp", "zoo_ssm_proj", "zoo_ssm_conv", "zoo_ssm_scan",
         "zoo_head", "zoo_insert", "zoo_sample"},
        (profile.SCOPE_DECODE_ATTENTION, profile.SCOPE_SAMPLE,
         profile.SCOPE_SSM, profile.SCOPE_SSM_CONV, profile.SCOPE_SSM_SCAN,
         profile.SCOPE_MLP)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_operation_of_an_admission_has_one_part(family):
    """Each located operation of the lowered admit plan lies under a
    name of ``profile.ADMIT_PARTS``, the innermost one its part (off
    the chip the jnp attention stands in for the kernel under
    ``zoo_attn_core``): the device time a profile shows of the plan
    splits into those parts with nothing left over but what the
    compiler adds."""
    eng = family_engine(family)
    try:
        admit, _ = admit_and_step_texts(eng, BUCKET)
    finally:
        eng.close()
    parts = located_parts(admit)
    assert "" not in parts
    assert set(parts) == FAMILIES[family][0]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_step_plans_keep_the_names_the_readers_go_by(family):
    eng = family_engine(family)
    try:
        _, step = admit_and_step_texts(eng, BUCKET)
    finally:
        eng.close()
    for scope in FAMILIES[family][1]:
        assert scope in step, scope
    assert "zoo_decode_mlp" not in step


def test_the_compile_cache_keys_on_the_scopes(monkeypatch, tmp_path):
    """``enable_compile_cache`` puts a program's metadata, its scopes
    among it, into the persistent cache's key: a plan that differs from
    a cached one in its names alone is compiled anew, not answered with
    the other's executable."""
    from analytics_zoo_tpu.common.context import enable_compile_cache
    flag = "jax_compilation_cache_include_metadata_in_key"
    regex = "jax_hlo_source_file_canonicalization_regex"
    was = {f: getattr(jax.config, f) for f in (flag, regex)}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update(flag, False)
        jax.config.update(regex, None)
        assert enable_compile_cache() == str(tmp_path)
        assert getattr(jax.config, flag) is True
        # file names in the metadata count from the checkout
        assert getattr(jax.config, regex)
    finally:
        for f, v in was.items():
            jax.config.update(f, v)


def test_flash_kernels_carry_their_names():
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=True).sum()

    fwd = str(jax.make_jaxpr(loss)(q, q, q))
    assert profile.KERNEL_FLASH_FWD in fwd
    assert profile.KERNEL_FLASH_BWD_DQ not in fwd
    bwd = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for kernel in (profile.KERNEL_FLASH_FWD, profile.KERNEL_FLASH_BWD_DQ,
                   profile.KERNEL_FLASH_BWD_DKV):
        assert kernel in bwd, kernel


def test_decode_step_carries_its_kernels_name(monkeypatch):
    """On the chip the step's attention is the kernel ``zoo_decode_attn``
    (in ``profile.KERNELS``, which a trace's reader goes by), under the
    scope ``zoo_decode_attention``; off the chip there is no kernel."""
    import importlib
    A = importlib.import_module("analytics_zoo_tpu.ops.attention")
    assert profile.KERNEL_DECODE_ATTN == "zoo_decode_attn"
    assert profile.KERNEL_DECODE_ATTN in profile.KERNELS
    wide = TransformerLM(vocab_size=VOCAB, seq_len=128, n_layers=1,
                         d_model=128, n_heads=2)
    wide.ensure_inference_ready()

    def kernel_calls():
        """Scope paths of the step's ``pallas_call``s of that name."""
        eng = DecodeEngine(wide.trainer.state.params, wide.hyper,
                           capacity=2, max_len=128,
                           prompt_buckets=(BUCKET,))
        try:
            closed = jax.make_jaxpr(eng._step_core)(
                *eng._step_specs(), eng._weights)
        finally:
            eng.close()
        # the kernel sits in a jit of its own (``_decode_attn_call``):
        # the scope is on the call, the kernel's name inside it
        return [str(eqn.source_info.name_stack)
                for eqn in closed.jaxpr.eqns
                if f"name={profile.KERNEL_DECODE_ATTN}" in str(eqn)]

    assert kernel_calls() == []
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    [scope] = kernel_calls()        # one layer, one call
    assert profile.SCOPE_DECODE_ATTENTION in scope
