"""Serving fast path: shape-bucketed executable cache, request
coalescing, AOT warmup, and the serving counters.

The pinned contracts:
* bucket selection / padding never changes real-row results — coalesced
  and padded predictions are BIT-identical to solo ``predict()``;
* a repeated-shape request stream compiles exactly once per bucket
  (counter-verified);
* integer inputs keep their dtype through the padded path (embedding
  ids must stay int — the ``_to_ndarray`` contract).
"""

import threading

import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Embedding, Flatten
from analytics_zoo_tpu.pipeline.inference import (
    BucketedExecutableCache, CoalescerClosedError, InferenceModel,
    RequestCoalescer, bucket_ladder)
from analytics_zoo_tpu.pipeline.inference.serving import batch_signature



def _assert_matmul_close(got, x, w):
    """Served (XLA) vs numpy matmul are two differently-compiled
    programs, so agreement is stated, not bitwise: 4 f32 ulp of the
    dot product's term magnitude (|x| @ |w| — the result itself may
    cancel to near zero).  Bit-equality stays for two runs of the SAME
    executable (the coalesced-vs-solo tests below)."""
    tol = 4 * float(np.finfo(np.float32).eps) * (np.abs(x) @ np.abs(w))
    assert got.shape == tol.shape
    assert np.all(np.abs(got - x @ w) <= tol)


# ---------------------------------------------------------------- ladder
def test_bucket_ladder_shapes():
    assert bucket_ladder(32) == (1, 2, 4, 8, 16, 32)
    assert bucket_ladder(5) == (1, 2, 4, 5)
    assert bucket_ladder(1) == (1,)
    assert bucket_ladder(12, growth=3.0) == (1, 3, 9, 12)
    with pytest.raises(ValueError):
        bucket_ladder(0)
    with pytest.raises(ValueError):
        bucket_ladder(8, growth=1.0)


def test_bucket_for_picks_smallest_cover():
    cache = BucketedExecutableCache(lambda x: x, max_batch=32)
    assert cache.bucket_for(1) == 1
    assert cache.bucket_for(3) == 4
    assert cache.bucket_for(17) == 32
    assert cache.bucket_for(33) == 32  # oversize → top bucket (chunked)


def test_explicit_buckets_override_ladder():
    cache = BucketedExecutableCache(lambda x: x, buckets=[4, 16])
    assert cache.buckets == (4, 16)
    assert cache.bucket_for(1) == 4
    assert cache.bucket_for(5) == 16


# ------------------------------------------------------- padding + cache
def _identityish_model():
    """fn whose output row i depends ONLY on input row i, served raw."""
    im = InferenceModel(max_batch_size=8)
    w = np.arange(16, dtype=np.float32).reshape(4, 4)
    im.load_jax(lambda p, x: x @ p["w"], {"w": w})
    return im, w


def test_padded_results_match_unpadded():
    im, w = _identityish_model()
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 5, 7, 8):
        x = rng.normal(size=(n, 4)).astype(np.float32)
        _assert_matmul_close(im.predict(x), x, w)


def test_oversize_batch_is_chunked_through_ladder():
    im, w = _identityish_model()
    x = np.random.default_rng(1).normal(size=(21, 4)).astype(np.float32)
    _assert_matmul_close(im.predict(x), x, w)
    stats = im.serving_stats()
    # 21 rows through max_batch 8: chunks of 8, 8, then 5 → bucket 8 (x2)
    # and bucket 8 again for the padded 5-row tail... the tail pads to 8
    assert stats["misses"] == {8: 1}
    assert stats["hits"][8] == 2


def test_one_compile_per_bucket_counters():
    im, _ = _identityish_model()
    stream = [1, 2, 3, 5, 8, 7, 1, 2, 4, 6, 8, 3]
    for n in stream:
        im.predict(np.zeros((n, 4), np.float32))
    stats = im.serving_stats()
    # exactly one miss (compile) per touched bucket, everything else hits
    assert stats["misses"] == {1: 1, 2: 1, 4: 1, 8: 1}
    assert sum(stats["hits"].values()) == len(stream) - 4
    assert all(t > 0 for t in stats["compile_time_s"].values())


def test_warmup_precompiles_every_bucket():
    im, w = _identityish_model()
    secs = im.warmup((4,))
    assert secs > 0
    stats = im.serving_stats()
    assert stats["misses"] == {1: 1, 2: 1, 4: 1, 8: 1}
    # live traffic after warmup never compiles
    for n in (1, 3, 8):
        im.predict(np.zeros((n, 4), np.float32))
    assert im.serving_stats()["misses"] == stats["misses"]


def test_bucketing_off_uses_exact_path():
    im = InferenceModel(bucketing=False)
    im.load_jax(lambda p, x: x * p["s"], {"s": np.float32(2.0)})
    x = np.ones((3, 2), np.float32)
    np.testing.assert_array_equal(im.predict(x), 2 * x)
    assert im.serving_stats()["buckets"] == ()


# ------------------------------------------------------------ int dtypes
def test_integer_inputs_keep_dtype_through_padded_path():
    seen = {}

    def fn(p, x):
        seen["dtype"] = x.dtype
        return p["table"][x[:, 0]]

    table = np.random.default_rng(0).normal(size=(10, 3)).astype(np.float32)
    im = InferenceModel(max_batch_size=4)
    im.load_jax(fn, {"table": table})
    ids = np.array([[1], [7], [3]], np.int32)
    out = im.predict(ids)
    assert str(seen["dtype"]) == "int32"
    np.testing.assert_array_equal(out, table[ids[:, 0]])


def test_embedding_model_int_ids_through_padded_path():
    """Regression: an embedding-input KerasNet served through the padded
    fast path must receive integer ids (float ids would crash or
    silently round)."""
    m = Sequential()
    m.add(Embedding(20, 6, input_shape=(5,)))
    m.add(Flatten())
    m.add(Dense(3, activation="softmax"))
    # single bucket → solo rows and the batched run share one executable
    im = InferenceModel(max_batch_size=8, buckets=[8]).load_keras_net(m)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 20, size=(3, 5)).astype(np.int32)
    out = im.predict(ids)
    assert out.shape == (3, 3)
    # solo rows, every one bit-identical to the batched padded run
    for i in range(len(ids)):
        np.testing.assert_array_equal(im.predict(ids[i:i + 1])[0], out[i])


# ------------------------------------------------------------ coalescing
def test_coalesced_results_bit_identical_to_solo_under_threads():
    """THE pinning test: concurrent coalesced predictions equal solo
    runs bit-for-bit, for every row, repeatedly.

    Solo and coalesced share the single bucket (buckets=[16]) so both
    run the SAME executable — within one executable, co-batched and
    padded rows must never leak into a real row's bits.  (Across
    buckets XLA may pick different kernels per batch shape; that
    tolerance is pinned separately below.)"""
    m = Sequential()
    m.add(Dense(16, input_shape=(4,), activation="relu"))
    m.add(Dense(3, activation="softmax"))
    solo = InferenceModel(max_batch_size=16,
                          buckets=[16]).load_keras_net(m)
    coal = InferenceModel(supported_concurrent_num=4, max_batch_size=16,
                          buckets=[16], coalescing=True, max_wait_ms=5.0
                          ).load_keras_net(m)
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(1, 4)).astype(np.float32) for _ in range(16)]
    ref = [solo.predict(x) for x in xs]

    results = [[None] * len(xs) for _ in range(3)]
    go = threading.Event()

    def worker(rep, i):
        go.wait()
        results[rep][i] = coal.predict(xs[i])

    threads = [threading.Thread(target=worker, args=(r, i))
               for r in range(3) for i in range(len(xs))]
    [t.start() for t in threads]
    go.set()
    [t.join() for t in threads]
    for rep in range(3):
        for i in range(len(xs)):
            np.testing.assert_array_equal(results[rep][i], ref[i])
    stats = coal.serving_stats()
    # packing actually happened: strictly fewer dispatches than requests
    assert stats["dispatches"] < stats["coalesced_requests"]
    coal.close()


def test_cross_bucket_rows_match_within_float_ulp():
    """Across buckets, XLA may select different kernels per batch shape
    (gemv vs gemm), so cross-bucket equality is pinned at ~1 ulp —
    bucket choice must never change results materially."""
    m = Sequential()
    m.add(Dense(16, input_shape=(4,), activation="relu"))
    m.add(Dense(3, activation="softmax"))
    im = InferenceModel(max_batch_size=16).load_keras_net(m)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(9, 4)).astype(np.float32)  # bucket 16
    batched = im.predict(x)
    for i in range(len(x)):
        solo = im.predict(x[i:i + 1])[0]  # bucket 1
        np.testing.assert_allclose(solo, batched[i], rtol=5e-7, atol=1e-7)


def test_coalescer_mixed_signatures_stay_correct():
    """Requests of different shapes interleaved: groups split on
    signature, every caller still gets its own rows."""
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=8,
                        coalescing=True, max_wait_ms=2.0)
    im.load_jax(lambda p, x: x * p["s"], {"s": np.float32(3.0)})
    shapes = [(1, 2), (1, 5), (2, 2), (1, 5), (1, 2), (2, 5)]
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=s).astype(np.float32) for s in shapes]
    out = [None] * len(xs)

    def worker(i):
        out[i] = im.predict(xs[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(xs))]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for i, x in enumerate(xs):
        np.testing.assert_array_equal(out[i], 3.0 * x)
    im.close()


def test_coalescer_multi_input_models():
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=8,
                        coalescing=True, max_wait_ms=2.0)
    im.load_jax(lambda p, xs: xs[0] + xs[1] * p["s"], {"s": np.float32(2.0)})
    rng = np.random.default_rng(0)
    pairs = [tuple(rng.normal(size=(1, 3)).astype(np.float32)
                   for _ in range(2)) for _ in range(6)]
    out = [None] * len(pairs)

    def worker(i):
        out[i] = im.predict(pairs[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(pairs))]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for i, (a, b) in enumerate(pairs):
        np.testing.assert_array_equal(out[i], a + 2.0 * b)
    im.close()


def test_coalescer_oversize_request_takes_solo_path():
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=4,
                        coalescing=True, max_wait_ms=1.0)
    im.load_jax(lambda p, x: x + p["b"], {"b": np.float32(1.0)})
    x = np.zeros((9, 2), np.float32)  # > max_batch → chunked solo path
    np.testing.assert_array_equal(im.predict(x), x + 1.0)
    im.close()


def test_reload_concurrent_with_predict_never_fails_or_tears():
    """Pinned (ISSUE 2): reload/load_jax under live predict() traffic —
    the old coalescer is drained, never abandoned; every call returns a
    result computed ENTIRELY by one installed version (the fast path is
    published as one atomic triple) and none fails."""
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=8,
                        coalescing=True, max_wait_ms=1.0)

    def fn(p, x):
        return x * p["s"]

    im.load_jax(fn, {"s": np.float32(1.0)})
    x = np.arange(6, dtype=np.float32).reshape(2, 3) + 1.0
    scales = (1.0, 2.0, 3.0, 4.0)
    results, failures = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        while not stop.is_set():
            try:
                out = np.asarray(im.predict(x))
                with lock:
                    results.append(out)
            except Exception as e:  # noqa: BLE001 — asserted empty
                with lock:
                    failures.append(repr(e))

    threads = [threading.Thread(target=client) for _ in range(4)]
    [t.start() for t in threads]
    import time
    try:
        for s in scales[1:]:
            time.sleep(0.1)
            im.load_jax(fn, {"s": np.float32(s)})  # reload mid-traffic
        time.sleep(0.1)
    finally:
        stop.set()  # a failed reload must not strand the clients
        [t.join() for t in threads]
        im.close()

    assert not failures, failures[:5]
    assert results
    seen = set()
    for out in results:
        ratios = out / x
        # entirely one version: a single scale across the whole result
        assert np.allclose(ratios, ratios.flat[0]), ratios
        s = float(ratios.flat[0])
        assert any(np.isclose(s, c) for c in scales), s
        seen.add(round(s))
    assert len(seen) >= 2, seen  # traffic straddled at least one reload


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_coalescer_crash_fails_queued_and_inflight_not_hang():
    """Dispatcher death between enqueue and pack must FAIL waiters, not
    strand them: queued requests, dispatched-but-unresolved groups, and
    later submits all get an exception promptly."""
    gate, entered = threading.Event(), threading.Event()

    def blocking_fn(x):
        entered.set()
        gate.wait(timeout=30)
        return x

    cache = BucketedExecutableCache(blocking_fn, max_batch=2)
    c = RequestCoalescer(cache, max_wait_ms=1.0)
    f1 = c.submit(np.ones((1, 2), np.float32))  # dispatcher blocks in fn
    assert entered.wait(timeout=10)  # f1's group is mid-dispatch

    # sabotage the NEXT gather (instance attr shadows the bound method),
    # then queue two more requests behind the blocked dispatch
    def bad_gather(*a, **k):
        raise RuntimeError("injected dispatcher crash")

    c._gather = bad_gather
    f2 = c.submit(np.ones((1, 2), np.float32))
    f3 = c.submit(np.ones((1, 2), np.float32))
    gate.set()  # unblock the dispatch; next loop iteration crashes

    for f in (f2, f3):
        with pytest.raises(RuntimeError, match="injected"):
            f.result(timeout=10)
    # f1 was dispatched: either it resolved before the crash or the
    # crash net failed it — it must not hang either way
    try:
        f1.result(timeout=10)
    except RuntimeError:
        pass
    c._thread.join(timeout=10)
    assert not c._thread.is_alive()
    assert c.pending == 0  # flushed requests left the live count too
    with pytest.raises(CoalescerClosedError):
        c.submit(np.ones((1, 2), np.float32))


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_coalescer_crash_net_covers_multi_replica_inflight():
    """Crash-net extension for device-parallel serving (ISSUE 5): the
    dispatcher dying with a group in flight ON A REPLICA SLOT must fail
    every waiter and release the slot accounting — same contract as the
    single-device crash net, exercised through the 4-tuple in-flight
    bookkeeping the replica scheduler added."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel

    im = InferenceModel(supported_concurrent_num=2, max_batch_size=2,
                        coalescing=True, max_wait_ms=1.0, replicas=2)
    im.load_jax(lambda p, x: x * p["s"], {"s": np.float32(1.0)})
    im.warmup((2,))
    c = im._coalescer
    assert c._rs is not None and c._rs.n == 2

    gate, entered = threading.Event(), threading.Event()
    orig = c._cache.dispatch_padded

    def blocking_dispatch(batched, spans=(), replica=None):
        entered.set()
        gate.wait(timeout=30)
        return orig(batched, spans, replica=replica)

    c._cache.dispatch_padded = blocking_dispatch  # instance attr shadow
    f1 = c.submit(np.ones((1, 2), np.float32))
    assert entered.wait(timeout=10)  # f1's group mid-dispatch on a slot

    def bad_gather(*a, **k):
        raise RuntimeError("injected dispatcher crash")

    c._gather = bad_gather
    f2 = c.submit(np.ones((1, 2), np.float32))
    f3 = c.submit(np.ones((1, 2), np.float32))
    gate.set()

    for f in (f2, f3):
        with pytest.raises(RuntimeError, match="injected"):
            f.result(timeout=10)
    try:
        f1.result(timeout=10)  # resolved or crash-net-failed, never hung
    except RuntimeError:
        pass
    c._thread.join(timeout=10)
    assert not c._thread.is_alive()
    assert c.pending == 0
    with pytest.raises(CoalescerClosedError):
        c.submit(np.ones((1, 2), np.float32))
    # the crash returned every device-concurrency slot: the solo
    # fallback path (which the model would now take) must not wedge
    out = im._cache.run(np.ones((1, 2), np.float32),
                        sem=im._semaphore)
    np.testing.assert_array_equal(out, np.ones((1, 2), np.float32))


def test_submit_after_dispatcher_exit_raises_not_hangs():
    """A dispatcher that exited (here: a sentinel injected directly,
    bypassing close()) leaves the coalescer refusing submits instead of
    accepting work nobody will serve."""
    from analytics_zoo_tpu.pipeline.inference import serving as serving_mod

    cache = BucketedExecutableCache(lambda x: x, max_batch=4)
    c = RequestCoalescer(cache, max_wait_ms=1.0)
    c._q.put(serving_mod._SHUTDOWN)
    c._thread.join(timeout=10)
    assert not c._thread.is_alive()
    assert c.closed  # even though close() never ran
    with pytest.raises(CoalescerClosedError):
        c.submit(np.ones((1, 2), np.float32))
    c.close()  # still idempotent afterwards


def test_coalescer_close_is_idempotent_and_fails_stragglers():
    cache = BucketedExecutableCache(lambda x: x, max_batch=4)
    c = RequestCoalescer(cache, max_wait_ms=1.0)
    fut = c.submit(np.ones((1, 2), np.float32))
    np.testing.assert_array_equal(fut.result(timeout=10),
                                  np.ones((1, 2), np.float32))
    c.close()
    c.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        c.submit(np.ones((1, 2), np.float32))  # no dispatcher → refuse


def test_batch_signature_distinguishes_dtype_and_shape():
    a = np.zeros((2, 3), np.float32)
    assert batch_signature(a) == batch_signature(np.ones((5, 3), np.float32))
    assert batch_signature(a) != batch_signature(a.astype(np.int32))
    assert batch_signature(a) != batch_signature(np.zeros((2, 4), np.float32))
    assert batch_signature((a, a)) != batch_signature(a)


def test_kerasnet_to_serving_convenience():
    m = Sequential()
    m.add(Dense(4, input_shape=(3,), activation="softmax"))
    im = m.to_serving(supported_concurrent_num=2, max_batch_size=8,
                      warmup_shapes=(3,))
    stats = im.serving_stats()
    assert stats["misses"] == {1: 1, 2: 1, 4: 1, 8: 1}
    x = np.random.default_rng(0).normal(size=(5, 3)).astype(np.float32)
    out = im.predict(x)
    assert out.shape == (5, 4)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)
    assert im.serving_stats()["misses"] == stats["misses"]  # warm


# --------------------------------------------------- runtime sanitizer
def test_coalescer_hot_loop_is_sanitize_clean(zoolint_sanitize):
    """Pinned (ISSUE 3): the coalescer hot loop — concurrent callers,
    dispatcher thread, padded dispatch, fan-out — performs ZERO XLA
    compiles and ZERO implicit transfers once warmed.  The dispatcher
    runs in its own thread, which is exactly why sanitize() sets the
    process-global guard: a thread-local guard would miss it."""
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=8,
                        coalescing=True, max_wait_ms=2.0)
    im.load_jax(lambda p, x: x @ p["w"], {"w": np.eye(4, dtype=np.float32)})
    im.warmup((4,))
    errors = []

    def worker(i):
        try:
            im.predict(np.full((1 + i % 3, 4), float(i), np.float32))
        except Exception as e:  # noqa: BLE001 — asserted empty below
            errors.append(repr(e))

    with zoolint_sanitize(max_compiles=0) as rep:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(12)]
        [t.start() for t in threads]
        [t.join() for t in threads]
    assert not errors, errors[:3]
    assert rep.compiles == 0
    im.close()


def test_sanitize_catches_recompile_injected_into_hot_loop(
        zoolint_sanitize):
    """The negative control for the test above: a deliberately unwarmed
    signature slipped into the same coalesced hot loop IS caught."""
    from analytics_zoo_tpu.tools.zoolint import RecompileDetected
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=8,
                        coalescing=True, max_wait_ms=2.0)
    im.load_jax(lambda p, x: x * p["s"], {"s": np.float32(2.0)})
    im.warmup((4,))
    with pytest.raises(RecompileDetected):
        with zoolint_sanitize(max_compiles=0, transfer_guard=None):
            im.predict(np.ones((1, 4), np.float32))   # warm: clean
            im.predict(np.ones((1, 6), np.float32))   # injected: new sig
    im.close()


def test_sanitize_catches_implicit_transfer_injected_into_dispatch(
        zoolint_sanitize):
    """If the bucketed dispatch ever regresses to handing raw numpy to
    the jit (an implicit host->device transfer per dispatch — what
    explicit device_put in _dispatch prevents), the sanitizer aborts
    the dispatch and the caller sees the violation."""
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=8,
                        coalescing=True, max_wait_ms=2.0)
    im.load_jax(lambda p, x: x + p["b"], {"b": np.float32(1.0)})
    im.warmup((4,))
    fastpath_fn = im._fastpath[0]  # the jit the dispatch path wraps
    with pytest.raises(Exception, match="Disallowed host-to-device"):
        with zoolint_sanitize(max_compiles=0):
            fastpath_fn(np.ones((2, 4), np.float32))  # bypass device_put
    # ...while the REAL dispatch path stays clean under the same guard
    with zoolint_sanitize(max_compiles=0):
        out = im.predict(np.ones((2, 4), np.float32))
    np.testing.assert_array_equal(out, np.full((2, 4), 2.0, np.float32))
    im.close()


# --------------------------------------------------- quantized handles
def test_quantized_handle_skips_padding():
    """int8 activation scales are batch-global — padded filler rows
    would perturb real rows, so quantized handles must stay on the
    exact-shape path."""
    m = Sequential()
    m.add(Dense(8, input_shape=(4,), activation="relu"))
    m.add(Dense(2))
    im = InferenceModel(max_batch_size=8).load_keras_net(m, quantize=True)
    x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
    out = im.predict(x)
    assert out.shape == (3, 2)
    assert im.serving_stats()["buckets"] == ()  # no bucketed cache
