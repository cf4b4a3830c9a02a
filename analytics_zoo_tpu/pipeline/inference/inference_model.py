"""InferenceModel: the thread-safe serving handle.

Parity surface: reference zoo/.../pipeline/inference/
{AbstractInferenceModel.java:30-148, FloatInferenceModel.scala:29-83,
InferenceModelFactory.scala, JTensor.java}.

The reference clones the model N times behind a LinkedBlockingQueue because
BigDL modules carry mutable forward state.  A jitted JAX function is pure
and thread-safe over immutable device arrays, so ONE compiled executable
serves all threads; ``supported_concurrent_num`` is honored with a
semaphore purely to bound concurrent device work (queueing semantics match
the reference's blocking take/offer).
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Sequence

import numpy as np
import jax

from ...observability import profile as _profile
from ...observability import trace as _trace
from .decode import DecodeEngine
from .serving import (BucketedExecutableCache, CoalescerClosedError,
                      ReplicaSet, RequestCoalescer, _execstore, _rows)


class JTensor:
    """Plain data+shape carrier (reference JTensor.java) — accepted and
    returned for POJO-style callers; numpy works everywhere too."""

    def __init__(self, data, shape=None):
        arr = np.asarray(data, dtype=np.float32)
        self.data = arr.ravel()
        self.shape = tuple(shape) if shape is not None else arr.shape

    def to_ndarray(self) -> np.ndarray:
        return self.data.reshape(self.shape)

    @classmethod
    def from_ndarray(cls, arr) -> "JTensor":
        return cls(arr)


def _to_ndarray(x):
    if isinstance(x, JTensor):
        return x.to_ndarray()
    a = np.asarray(x)
    # keep integer dtypes (embedding/gather ids must stay int); float64
    # narrows to the framework's working f32
    if np.issubdtype(a.dtype, np.integer):
        return a
    return a.astype(np.float32, copy=False)


class InferenceModel:
    """load / predict with bounded concurrency
    (reference AbstractInferenceModel API)."""

    def __init__(self, supported_concurrent_num: int = 1,
                 max_batch_size: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 bucket_growth: float = 2.0,
                 bucketing: bool = True,
                 coalescing: bool = False,
                 max_wait_ms: float = 2.0,
                 replicas=1,
                 hedging: bool = False,
                 hedge_quantile: float = 0.99,
                 hedge_min_ms: float = 0.5,
                 decode_capacity: Optional[int] = None,
                 decode_max_len: Optional[int] = None,
                 decode_prompt_buckets: Optional[Sequence[int]] = None,
                 decode_eos_id: Optional[int] = None,
                 decode_prefix_pool: int = 0,
                 decode_draft=None,
                 decode_spec_tokens: int = 4,
                 mesh: Optional[dict] = None,
                 store_tag: Optional[str] = None):
        """``supported_concurrent_num`` bounds concurrent device work
        (reference semantics; PER REPLICA when replicated — the
        effective bound scales with the replica count).  The serving
        fast path adds:

        * ``bucketing`` — pad each batch up to a geometric ladder of
          batch sizes (1, 2, … ``max_batch_size`` scaled by
          ``bucket_growth``, or an explicit ``buckets`` list) so a
          ragged request stream hits a handful of compiled executables
          instead of compiling per shape.  Disabled automatically for
          int8-quantized handles (their dynamic activation scales are
          batch-global, so padding would perturb real rows).
        * ``coalescing`` — concurrent ``predict()`` callers are packed
          by a dispatcher thread into ONE padded device batch per
          dispatch (amortizing the per-dispatch floor), waiting at
          most ``max_wait_ms`` to fill ``max_batch_size`` rows; results
          fan back out bit-identical to solo runs.
        * ``replicas`` — ``"all"`` or an int N: place each bucket
          executable on that many local devices (compiled ONCE,
          serialized, loaded per device — see
          :class:`~.serving.ReplicaSet`), params copied per device, and
          route dispatches across the replicas.  Clamped to the local
          device count; 1 (the default) keeps the single-device path.
          Quantized handles stay single-device (their exact-shape path
          has no bucket executables to replicate).
        * ``hedging`` — p99 straggler mitigation (coalesced,
          multi-replica only): a dispatched group whose in-flight time
          exceeds the ``hedge_quantile`` of observed group latencies
          (floored at ``hedge_min_ms``) is re-dispatched to a second
          healthy replica and the first result wins — bit-exact either
          way (same serialized executable on every replica).  No-ops
          with fewer than 2 eligible replicas.
        * ``decode_capacity`` — attach a continuous-batching
          :class:`~.decode.DecodeEngine` with that many slots when a
          language model (a net with ``generate`` + a transformer
          ``hyper``) is loaded, enabling :meth:`generate` /
          :meth:`generate_stream` with iteration-level scheduling.
          ``decode_max_len`` / ``decode_prompt_buckets`` /
          ``decode_eos_id`` configure it (see the engine's docstring).
          The engine is warmed at load — every (bucket, capacity)
          plan compiles before the handle serves, never under a live
          stream.
        * ``decode_prefix_pool`` — > 0 enables the engine's on-device
          prefix-KV LRU pool with that many entries (shared-prefix
          admissions skip the prefix prefill; decode.py module doc).
        * ``decode_draft`` — a small generation-capable draft net (or
          a ``(params, hyper)`` pair) enables speculative decoding of
          up to ``decode_spec_tokens`` tokens per dispatch.
        * ``mesh`` — a sharded-serving spec dict (see
          :func:`analytics_zoo_tpu.serving.shardgroup.normalize_mesh_spec`):
          replicas become replica GROUPS, each a sharded executable
          over a sub-mesh of that shape with the weight tree
          partitioned by the spec's rule table — how a model bigger
          than one chip serves.  ``replicas`` is ignored (the spec's
          ``groups`` controls the group count), and the decode engine,
          when configured, shards its slot arrays over the same mesh.
        """
        # per-model accounting tag for the persistent executable store
        # (``stat --by-model``): metadata on every entry this handle
        # persists, never part of a fingerprint
        self.store_tag = store_tag
        self.concurrent_num = int(supported_concurrent_num)
        self._semaphore = threading.Semaphore(self.concurrent_num)
        self._sem_capacity = self.concurrent_num
        self._replicas_req = replicas
        self._predict_fn = None
        self._params = None
        self._state = None
        self._graph = None
        self.max_batch_size = int(max_batch_size)
        self._buckets = buckets
        self._bucket_growth = float(bucket_growth)
        self._bucketing = bool(bucketing)
        self._coalescing = bool(coalescing)
        self.max_wait_ms = float(max_wait_ms)
        self._hedging = bool(hedging)
        self._hedge_quantile = float(hedge_quantile)
        self._hedge_min_ms = float(hedge_min_ms)
        self._decode_capacity = (None if decode_capacity is None
                                 else int(decode_capacity))
        self._decode_max_len = decode_max_len
        self._decode_prompt_buckets = decode_prompt_buckets
        self._decode_eos_id = decode_eos_id
        self._decode_prefix_pool = int(decode_prefix_pool)
        self._decode_draft = decode_draft
        self._decode_spec_tokens = int(decode_spec_tokens)
        # sharded serving: normalized once here so a malformed spec
        # fails the CONSTRUCTOR (deploy-time), not the first install
        if mesh is not None:
            from ...serving.shardgroup import normalize_mesh_spec
            mesh = normalize_mesh_spec(mesh)
        self._mesh = mesh
        self._decode_engine: Optional[DecodeEngine] = None
        self._cache: Optional[BucketedExecutableCache] = None
        self._coalescer: Optional[RequestCoalescer] = None
        # (predict_fn, cache, coalescer) published as ONE tuple: a
        # predict() racing reload() snapshots a consistent path — never
        # the new forward with the old bucket cache or vice versa
        self._fastpath = None

    # ---- loading (reference load/loadCaffe/loadTF surface) ----
    def load(self, model_path: str, weight_path: Optional[str] = None,
             quantize: Optional[bool] = None):
        """Load a model saved with save_model (the framework's own
        format; reference ``load`` reads BigDL format).  ``quantize=True``
        serves the int8 inference variant (reference loads ``*-quantize``
        models)."""
        from ..api.keras.engine import KerasNet
        net = KerasNet.load_model(model_path)
        trainer = net.ensure_inference_ready()
        if weight_path is not None:
            trainer.load_weights(weight_path)
        return self.load_keras_net(net, quantize=quantize)

    def load_keras_net(self, net, quantize: Optional[bool] = None):
        """Serve an in-memory KerasNet/ZooModel."""
        if quantize is None:
            # reload() must not silently flip a quantized handle back to
            # float: default to however this handle was last loaded
            quantize = getattr(self, "_quantize_flag", None)
        if quantize is None:
            # honor the registry's '<arch>-quantize' naming convention
            # (a saved ImageClassifier('resnet-50-quantize') must serve
            # int8 without an explicit flag)
            name = getattr(net, "hyper", {}).get("model_name", "")
            quantize = isinstance(name, str) and name.endswith("-quantize")
        self._quantize_flag = bool(quantize)
        if quantize:
            net = net.quantize()
        trainer = net.ensure_inference_ready()
        # build + warm the decode engine BEFORE publishing the predict
        # plane: a reload whose engine build fails (non-LM path, warmup
        # crash) must leave the handle fully on the OLD version — a
        # half-swapped handle (new predict, stale generate) is the one
        # state no caller can reason about
        engine = self._build_decode_engine(net, trainer)
        self._attach(net.to_graph(), trainer.state.params,
                     trainer.state.model_state)
        if self._decode_capacity is not None:
            old, self._decode_engine = self._decode_engine, engine
            if old is not None:
                # close AFTER the swap (the reload discipline of
                # ``_install``): the old engine's active streams drain
                # on the old plans while new submits hit the new ones
                old.close()
        return self

    def _build_decode_engine(self, net, trainer):
        """Validate, build, and warm the continuous-batching decode
        engine when ``decode_capacity`` is configured and the loaded
        net is a generation-capable LM.  Pure — publishes nothing;
        any failure here leaves the handle untouched."""
        if self._decode_capacity is None:
            return None
        hyper = getattr(net, "hyper", None)
        if (not callable(getattr(net, "generate", None))
                or not isinstance(hyper, dict)
                or "n_layers" not in hyper):
            raise ValueError(
                "decode_capacity needs a generation-capable language "
                f"model (TransformerLM-like), got {type(net).__name__}")
        if getattr(self, "_quantize_flag", False):
            raise ValueError(
                "decode_capacity is not supported for quantized "
                "handles (the decode math reads float params by name)")
        draft_params = draft_hyper = None
        draft = self._decode_draft
        if draft is not None:
            if isinstance(draft, tuple):
                draft_params, draft_hyper = draft
            else:
                dtrainer = draft.ensure_inference_ready()
                draft_params = dtrainer.state.params
                draft_hyper = draft.hyper
        engine = DecodeEngine(
            trainer.state.params, hyper,
            capacity=self._decode_capacity,
            max_len=self._decode_max_len,
            prompt_buckets=self._decode_prompt_buckets,
            eos_id=self._decode_eos_id,
            prefix_pool=self._decode_prefix_pool,
            draft_params=draft_params, draft_hyper=draft_hyper,
            spec_tokens=self._decode_spec_tokens,
            mesh=self._mesh,
            store_tag=self.store_tag)
        engine.warmup()
        return engine

    def load_tf(self, path: Optional[str] = None, net=None,
                input_names=None, output_names=None):
        """Serve a frozen TF graph or imported keras model (reference
        AbstractInferenceModel.loadTF): ``path`` loads an export folder /
        .pb via TFNet, or pass an existing TFNet (e.g. from
        Net.load_keras / Net.from_tf_keras) as ``net``."""
        from ..api.tfgraph.net import TFNet
        if net is None:
            if path is None:
                raise ValueError("load_tf: pass path= (export folder / "
                                 ".pb) or net= (an existing TFNet)")
            net = TFNet(path=path, input_names=input_names,
                        output_names=output_names)
        params = net.init_params(jax.random.PRNGKey(0), None)

        def run(p, x):
            xs = x if isinstance(x, (tuple, list)) else (x,)
            # frozen graphs may retain dropout nodes; pin the key (same
            # policy as TFNet.predict)
            out = net.fn(p, *xs, rng=jax.random.PRNGKey(0))
            if isinstance(out, (tuple, list)) and len(out) == 1:
                return out[0]  # single-output graphs return the array
            return out

        return self.load_jax(run, params)

    def load_graph(self, graph, params, state=None):
        """Serve a prebuilt pure graph (``graph.apply(params, state,
        x, training=False)``) with an explicit param/state tree — the
        weight pager's keras-side fault-in path: a cold deployment
        keeps the graph plus HOST numpy weights, and this call places
        them exactly once (the replica set's ``device_put``; the
        placed-tree discipline of :meth:`load_jax`)."""
        self._quantize_flag = False
        self._attach(graph, params, state)
        return self

    def load_jax(self, fn, params):
        """Serve a raw jax function fn(params, x) (the TFNet-equivalent
        import path for externally-defined computations)."""
        self._graph = None
        self._params = jax.device_put(params)
        self._state = None
        # a raw jax fn is not a quantized registry handle — a stale flag
        # from a previous quantized load must not disable the fast path
        self._quantize_flag = False
        # hand the PLACED tree on: device_put of an array already
        # committed to the target device is a no-op, so replica 0 shares
        # these buffers instead of pinning a second copy of the weights
        # in device-0 memory
        self._install(fn, self._params)
        return self

    def _attach(self, graph, params, state):
        self._graph = graph
        self._params = params
        self._state = state

        def forward(bundle, x):
            out, _ = graph.apply(bundle["params"], bundle["state"], x,
                                 training=False)
            return out

        self._install(forward, {"params": params, "state": state})

    def _resolve_replicas(self) -> int:
        """The effective replica count: the request ("all" or an int),
        clamped to the local device count."""
        req = self._replicas_req
        avail = len(jax.local_devices())
        if isinstance(req, str):
            if req.lower() != "all":
                raise ValueError(
                    f'replicas must be "all" or an int, got {req!r}')
            return avail
        n = int(req)
        if n < 1:
            raise ValueError(f"replicas must be >= 1, got {n}")
        return min(n, avail)

    def _install(self, fn, params):
        """Install the forward ``fn(params, x)`` and (re)build the
        serving fast path for it: bucketed executable cache (optionally
        replicated across local devices) + optional coalescer.

        The weights are a runtime ARGUMENT of the one jitted forward —
        bound here for the single-device path, placed per device by the
        ReplicaSet — never closed-over constants: a closure bakes a
        private copy of the weights into every bucket's executable (N
        buckets = N copies in device memory, compiles that grow with
        the model, entries no compilation cache can hold).

        Quantized handles stay on
        the exact-shape path — their dynamic activation scales are
        batch-global, so padded filler rows would change real-row
        outputs.

        Reload ordering (the zero-downtime contract): the NEW path is
        fully built and published first, THEN the old coalescer is
        closed — its already-queued requests drain through the OLD
        executables while new traffic flows to the new ones.  No request
        is ever abandoned or served by a half-swapped path."""
        forward = jax.jit(fn)

        def predict_fn(x):
            return forward(params, x)

        old_coalescer = self._coalescer
        cache = None
        coalescer = None
        replica_set = None
        if self._bucketing and not getattr(self, "_quantize_flag", False):
            n_rep = self._resolve_replicas()
            # the raw-dispatch ReplicaSet path engages for N > 1
            # devices, and ALSO single-device whenever the persistent
            # executable store is enabled: the store serves serialized
            # raw executables, and only the replica path dispatches
            # them — this is what makes a warm-store deploy()
            # zero-compile even on one device.  Store off, one device:
            # the plain jitted forward (``predict_fn``).
            store_on = _execstore().current() is not None
            if self._mesh is not None:
                # sharded serving: the mesh spec (not ``replicas``)
                # decides how many groups the local device set carves
                # into; one sharded compile, every further group is a
                # device-assignment rewrite
                from ...serving.shardgroup import ShardGroupSet
                replica_set = ShardGroupSet(
                    fn, params, self._mesh,
                    devices=jax.local_devices(), tag=self.store_tag)
            elif n_rep > 1 or store_on:
                replica_set = ReplicaSet(
                    fn, params,
                    devices=jax.local_devices()[:n_rep],
                    tag=self.store_tag)
            cache = BucketedExecutableCache(
                predict_fn, max_batch=self.max_batch_size,
                buckets=self._buckets, growth=self._bucket_growth,
                replica_set=replica_set)
        # the concurrency budget is per replica: N devices can carry N
        # times the concurrent device work of one.  The semaphore is
        # REUSED when the capacity is unchanged: a reload under traffic
        # must keep old-path drains and new-path traffic on one shared
        # budget (a fresh semaphore would let them stack to 2x during
        # the drain window).  Only a genuine capacity change — the
        # replica count moved — warrants a new budget.
        n_active = replica_set.n if replica_set is not None else 1
        cap = self.concurrent_num * n_active
        if cap != self._sem_capacity:
            self._semaphore = threading.Semaphore(cap)
            self._sem_capacity = cap
        if cache is not None and self._coalescing:
            # pipeline two dispatches when the concurrency budget
            # allows — the device computes group k while group k+1
            # is gathered and dispatched behind it.  (The coalescer
            # widens this to one slot per replica when replicated.)
            coalescer = RequestCoalescer(
                cache, max_wait_ms=self.max_wait_ms,
                semaphore=self._semaphore,
                pipeline_depth=min(2, self.concurrent_num),
                hedging=self._hedging,
                hedge_quantile=self._hedge_quantile,
                hedge_min_ms=self._hedge_min_ms)
        # one assignment publishes the whole new path (GIL-atomic)
        self._fastpath = (predict_fn, cache, coalescer)
        self._predict_fn = predict_fn
        self._cache = cache
        self._coalescer = coalescer
        if old_coalescer is not None:
            # graceful drain: queued requests complete on the old
            # executables; anything racing the shutdown gets
            # CoalescerClosedError and the caller falls back
            old_coalescer.close()

    @property
    def n_replicas(self) -> int:
        """Total replica count (1 on the single-device path)."""
        fastpath = self._fastpath
        if fastpath is None:
            return 1
        _, cache, _ = fastpath
        if cache is None or cache.replica_set is None:
            return 1
        return cache.replica_set.n

    @property
    def active_replicas(self) -> int:
        """Replicas currently in the scheduled (elastic) set."""
        fastpath = self._fastpath
        if fastpath is None:
            return 1
        _, cache, _ = fastpath
        if cache is None or cache.replica_set is None:
            return 1
        return cache.replica_set.n_active

    def placement_complete(self) -> bool:
        """True when every replica (group) of the installed set holds
        every placed executable — the pager's group-atomic install
        guard.  Handles without a replica set are trivially complete
        (one device, one executable)."""
        fastpath = self._fastpath
        if fastpath is None:
            return False
        _, cache, _ = fastpath
        if cache is None or cache.replica_set is None:
            return True
        return cache.replica_set.placement_complete()

    def set_active_replicas(self, n: int) -> int:
        """Resize the scheduled replica set (the autoscaler's lever) —
        joining replicas are primed on every placed signature BEFORE
        they take traffic, so a scale-up never serves cold and never
        compiles.  Returns the resulting active count; no-ops (returns
        1) on the single-device path."""
        fastpath = self._fastpath
        if fastpath is None:
            raise RuntimeError("InferenceModel: no model loaded")
        _, cache, _ = fastpath
        if cache is None or cache.replica_set is None:
            return 1
        return cache.replica_set.set_active(n)

    # ---- serving fast path surface ----
    def warmup(self, sample_shapes, dtypes=None) -> float:
        """AOT-compile every ladder bucket for the given per-sample
        input shape(s) (no batch axis; list of shapes for multi-input
        models, ``dtypes`` element-wise).  Returns compile seconds —
        call once at deploy time so live traffic never pays a trace."""
        if self._predict_fn is None:
            raise RuntimeError("InferenceModel: no model loaded")
        if self._cache is None:
            raise RuntimeError(
                "warmup needs the bucketed path (bucketing=True and a "
                "non-quantized handle)")
        return self._cache.warmup(sample_shapes, dtypes)

    def serving_stats(self) -> dict:
        """Per-bucket hit/miss/compile-time counters plus coalescer
        dispatch stats (consumed directly and re-exported per model by
        the serving control plane's metrics snapshot)."""
        out = {"buckets": (), "hits": {}, "misses": {},
               "compile_time_s": {}, "dispatches": 0,
               "coalesced_requests": 0, "coalescer_pending": 0,
               "replicas": 1}
        # snapshot the triple so a metrics read during reload() never
        # pairs the new cache's counters with the old coalescer's
        fastpath = self._fastpath
        if fastpath is None:
            return out
        _, cache, coalescer = fastpath
        if cache is not None:
            out["buckets"] = cache.buckets
            out.update(cache.stats.snapshot())
            if cache.replica_set is not None:
                out.update(cache.replica_set.stats())
        if coalescer is not None:
            out["dispatches"] = coalescer.dispatches
            out["coalesced_requests"] = coalescer.coalesced_requests
            out["coalescer_pending"] = coalescer.pending
            if coalescer.hedging:
                out["hedges"] = coalescer.hedge_stats()
        engine = self._decode_engine
        if engine is not None:
            out["decode"] = engine.stats()
        return out

    # ---- continuous-batching generation ----
    @property
    def decode_engine(self) -> Optional[DecodeEngine]:
        """The attached continuous-batching engine (None unless the
        handle was built with ``decode_capacity`` and loaded an LM)."""
        return self._decode_engine

    def _require_engine(self) -> DecodeEngine:
        engine = self._decode_engine
        if engine is None:
            raise RuntimeError(
                "no decode engine: construct the InferenceModel with "
                "decode_capacity= and load a generation-capable LM")
        return engine

    def generate(self, prompt_ids, max_new_tokens,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed=0):
        """Continuous-batching decode: each prompt (a (B, L) array or
        a list of ragged 1-D id rows) is bucketed, prefilled, and
        slot-scheduled per decode step alongside every other live
        request — a short request never pays a long neighbor's latency.
        Returns each row's generated continuation (list of 1-D int32
        arrays; EOS included when hit).  ``max_new_tokens`` (and
        ``seed``) may be per-row.  Greedy (``temperature == 0``,
        default) is token-identical to ``TransformerLM.generate``'s
        compiled scan for the same prompt; ``temperature > 0`` samples
        (top-k/top-p truncated) from the per-request ``(seed, token
        index)`` fold_in stream — same request, same stream, at any
        engine occupancy."""
        return self._require_engine().generate(
            prompt_ids, max_new_tokens, eos_id=eos_id, timeout=timeout,
            span=_trace.current_span(), temperature=temperature,
            top_k=top_k, top_p=top_p, seed=seed)

    def generate_stream(self, prompt_ids, max_new_tokens: int,
                        eos_id: Optional[int] = None,
                        temperature: float = 0.0,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None, seed: int = 0):
        """Streaming single-prompt decode: returns a
        :class:`~.decode.TokenStream` immediately — iterate it for
        per-token delivery, or ``.result()`` for the full
        continuation."""
        span = _trace.current_span()
        return self._require_engine().submit(
            prompt_ids, max_new_tokens, eos_id=eos_id, span=span,
            temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed)

    def close(self):
        """Stop the coalescer and decode dispatcher threads (no-op
        without them)."""
        if self._coalescer is not None:
            self._coalescer.close()
        if self._decode_engine is not None:
            self._decode_engine.close()

    def reload(self, model_path: str, weight_path: Optional[str] = None,
               quantize: Optional[bool] = None):
        """Hot-swap the served model; keeps the previous quantize mode
        unless overridden."""
        return self.load(model_path, weight_path, quantize=quantize)

    # ---- prediction (AbstractInferenceModel.predict:112-126) ----
    def predict(self, inputs) -> Any:
        """Accepts one batch array, a JTensor, a list of per-sample inputs,
        or a list of input-lists for multi-input models; returns
        predictions in the matching container type."""
        fastpath = self._fastpath  # ONE read: consistent under reload()
        if fastpath is None:
            raise RuntimeError("InferenceModel: no model loaded")
        predict_fn, cache, coalescer = fastpath
        # the whole tracing cost when disabled is this one branch
        # (current_span checks a module flag before touching the
        # contextvar); every phase call below guards on span is None
        span = _trace.current_span()
        batched, single, jtensor = self._normalize(inputs)
        if cache is None:
            # exact-shape path (bucketing off, or quantized handle whose
            # batch-global activation scales forbid padding).  Explicit
            # device_put for the same reason as the bucketed dispatch:
            # the upload must be visible to transfer guards.
            with self._semaphore:
                if span is not None:
                    span.phase_start("device_put")
                xb = jax.device_put(batched)
                _profile.note_transfer("h2d")
                if span is not None:
                    span.phase_start("execute")
                out = predict_fn(xb)
            out = np.asarray(jax.device_get(out))
            _profile.note_transfer("d2h")
            if span is not None:
                span.phase_end()
        else:
            out = None
            if (coalescer is not None and not coalescer.closed
                    and _rows(batched) <= cache.max_batch):
                try:
                    out = np.asarray(
                        coalescer.submit(batched, span=span).result())
                except CoalescerClosedError:
                    out = None  # closed between check and submit
            if out is None:
                # the snapshotted cache — a racing reload() may have
                # already nulled self._cache
                out = np.asarray(cache.run(batched, sem=self._semaphore,
                                           span=span))
        if jtensor:
            tensors = [JTensor.from_ndarray(o) for o in out]
            return tensors[0] if single else tensors
        return out[0] if single else out

    def _normalize(self, inputs):
        jtensor = False
        single = False
        if isinstance(inputs, JTensor):
            inputs, jtensor, single = [inputs], True, True
        if isinstance(inputs, np.ndarray):
            return inputs, False, False
        if isinstance(inputs, tuple):
            # tuple = multi-input batch (one array per model input);
            # _to_ndarray keeps integer dtypes — embedding/gather inputs
            # must stay int
            return tuple(
                a if isinstance(a, np.ndarray) else _to_ndarray(a)
                for a in inputs), False, False
        if isinstance(inputs, list):
            if inputs and isinstance(inputs[0], JTensor):
                jtensor = True
                arrs = [_to_ndarray(t) for t in inputs]
                return np.stack(arrs), single, jtensor
            if inputs and isinstance(inputs[0], (list, tuple)):
                # list of per-sample input-lists (multi-input models):
                # stack column-wise into one batch array per input
                n_inputs = len(inputs[0])
                return tuple(
                    np.stack([_to_ndarray(sample[i]) for sample in inputs])
                    for i in range(n_inputs)), single, jtensor
            arrs = [_to_ndarray(t) for t in inputs]
            return np.stack(arrs), single, jtensor
        return _to_ndarray(inputs), False, False

    def __repr__(self):
        loaded = self._predict_fn is not None
        return (f"InferenceModel(concurrent={self.concurrent_num}, "
                f"loaded={loaded})")


class AbstractInferenceModel(InferenceModel):
    """Name-parity alias for the POJO-style entry class."""
