"""Serving fast path: shape-bucketed executables + request coalescing.

Two walls motivate this module:

* **Compile-per-shape.** A jitted forward re-traces for every distinct
  batch size, so a live request stream with ragged batch sizes compiles
  continuously.  ``BucketedExecutableCache`` pads every batch up to a
  small geometric ladder of batch sizes (1, 2, 4, … max_batch by
  default) so the whole stream is served by a handful of pre-compilable
  executables, with per-bucket hit/miss/compile-time counters and an
  AOT ``warmup``.
* **Per-dispatch floor.** A dispatched computation pays a fixed host
  cost whatever its size (not measured on today's chip), so one device
  call per request caps throughput for small models.
  ``RequestCoalescer`` packs concurrent ``predict()`` callers into ONE
  padded device batch per dispatch and fans the rows back out —
  amortizing the floor across every rider.

Padding safety: rows are independent under inference-mode forward
passes (BatchNorm uses running stats, softmax is row-wise), so padded
filler rows cannot perturb real rows and un-padded results are
bit-identical to a solo run.  Computations with BATCH-GLOBAL terms —
int8 dynamic activation scales — are NOT row-independent; callers must
keep those on the exact-shape path (``InferenceModel`` does).

A third wall falls with ``ReplicaSet`` (multi-replica serving): the
per-request path above is structurally single-device — one executable,
one device, N-1 chips idle.  A ``ReplicaSet`` places the SAME compiled
executable on every local device (compile once, ``serialize`` the
executable, ``deserialize`` it per device — milliseconds against a
multi-hundred-ms compile) with a per-device copy of the params, and the
coalescer's dispatcher routes each group to the replica with the fewest
undelivered groups — cross-replica pipelining that generalizes the
one-deep dispatch pipeline to depth N.
"""

from __future__ import annotations

import collections
import contextlib
import queue
import threading
import time
from concurrent.futures import (FIRST_COMPLETED, Future,
                                ThreadPoolExecutor)
from concurrent.futures import wait as _futures_wait
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jaxlib import xla_client as _xla_client

from ...common.utils import pad_leading as _pad_rows
from ...observability import profile as _profile
from ...observability import trace as _trace
from ...observability.log import get_logger as _get_logger
from ...observability.metrics import LatencyWindow as _LatencyWindow

_slog = _get_logger("zoo.serving")


def _execstore():
    """The persistent-executable-store module, imported lazily: the
    data plane must stay importable on its own, and the store is
    consulted only at compile/warmup time anyway."""
    from ...serving import execstore
    return execstore


def bucket_ladder(max_batch: int, growth: float = 2.0,
                  min_batch: int = 1) -> Tuple[int, ...]:
    """The geometric ladder of padded batch sizes: ``min_batch`` scaled
    by ``growth`` until ``max_batch`` (always included)."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if growth <= 1.0:
        raise ValueError(f"bucket growth must be > 1, got {growth}")
    out: List[int] = []
    b = float(max(1, min_batch))
    while int(b) < max_batch:
        if not out or int(b) != out[-1]:
            out.append(int(b))
        b *= growth
    out.append(int(max_batch))
    return tuple(out)


def _rows(batched) -> int:
    first = batched[0] if isinstance(batched, (tuple, list)) else batched
    return int(np.asarray(first).shape[0])


def _slice_rows(tree, start: int, stop: int):
    return jax.tree_util.tree_map(lambda a: a[start:stop], tree)


def _concat_trees(trees: Sequence):
    """Concatenate result trees (arrays or tuples of arrays) row-wise."""
    if len(trees) == 1:
        return trees[0]
    first = trees[0]
    if isinstance(first, (tuple, list)):
        return type(first)(
            np.concatenate([t[i] for t in trees])
            for i in range(len(first)))
    return np.concatenate(trees)


def batch_signature(batched) -> Tuple:
    """Everything but the batch row count: per-input trailing shape +
    dtype.  Two batches coalesce / share a bucket executable iff their
    signatures match."""
    def one(a):
        a = np.asarray(a)
        return (tuple(a.shape[1:]), str(a.dtype))

    if isinstance(batched, (tuple, list)):
        return tuple(one(a) for a in batched)
    return (one(batched),)


class BucketStats:
    """Per-bucket serving counters (thread-safe snapshots via dict copy)."""

    def __init__(self):
        self.hits: Dict[int, int] = {}
        self.misses: Dict[int, int] = {}
        self.compile_time_s: Dict[int, float] = {}

    def snapshot(self) -> Dict[str, Dict[int, Any]]:
        return {"hits": dict(self.hits), "misses": dict(self.misses),
                "compile_time_s": dict(self.compile_time_s)}


class Replica:
    """One device's share of a :class:`ReplicaSet`: the device, its own
    copy of the params (flattened, pre-placed), and per-replica serving
    counters.  Counter writes happen under the owning cache's lock (the
    same lock as the bucket counters); ``healthy``, ``active`` and the
    probe-backoff fields flip under the replica set's lock.

    ``healthy`` tracks fault state (a dispatch raised; restored by a
    successful health re-probe).  ``active`` tracks the ELASTIC set: a
    deactivated replica keeps its placed executables and params — warm,
    idle, off the scheduler — so re-activation is a prime, never a
    compile."""

    __slots__ = ("index", "device", "params_flat", "healthy", "active",
                 "probe_at", "probe_backoff",
                 "dispatches", "bucket_dispatches")

    def __init__(self, index: int, device, params_flat: List):
        self.index = index
        self.device = device
        self.params_flat = params_flat
        self.healthy = True
        self.active = True
        self.probe_at = 0.0        # perf_counter time of the next probe
        self.probe_backoff = 0.0   # current backoff step (seconds)
        self.dispatches = 0
        self.bucket_dispatches: Dict[int, int] = {}

    def __repr__(self):
        return (f"Replica({self.index}, {self.device}, "
                f"healthy={self.healthy}, active={self.active})")


class ReplicaSet:
    """Compile-once / place-everywhere: one executable per padded input
    signature, loaded onto EVERY local device, each device holding its
    own copy of the params.

    The mechanism (and why it is one compile, counter-verified): a
    jitted forward re-COMPILES per device placement — jax's executable
    cache keys on input shardings, so serving N devices through N jits
    pays N identical XLA compiles per bucket.  Here the forward is
    traced and lowered ONCE (``jax.jit(fn).lower(...).compile()`` — the
    single monitored ``backend_compile``), then the compiled executable
    is ``serialize``d and ``deserialize``d onto each remaining device
    with only its device assignment rewritten.  Deserialization is a
    load, not a compile (~3-10 ms against a multi-hundred-ms compile)
    and fires no compile event — which is exactly the accounting the
    sanitizer and test_serving_replicas' one-compile-per-bucket pin hold.

    Dispatch bypasses the jit wrapper entirely: inputs are uploaded to
    the replica's device via explicit ``device_put`` (transfer-guard
    visible, like the single-device path) and handed straight to the
    replica's loaded executable.  Unused inputs pruned by XLA
    (``kept_var_idx``) are dropped to match the executable's parameter
    list.

    Persistence: with the executable store enabled
    (:mod:`analytics_zoo_tpu.serving.execstore`), ``ensure_compiled``
    is read-through/write-behind against it — a process whose store
    already holds this (graph, weights, signature, jax version,
    device kind) fingerprint LOADS the executable in milliseconds and
    fires no compile event at all, which is what makes a second
    process's ``deploy()`` zero-compile.

    Fault handling: a replica whose dispatch raises is marked unhealthy
    and the failed dispatch is retried once on another healthy replica
    by the owning cache.  Recovery is structured, not luck: an
    unhealthy replica is RE-PROBED with a cheap warmed no-op execute on
    an exponential backoff (``maybe_reprobe``, driven from the
    coalescer loop and the solo scheduler), and a probe that returns
    flips it healthy again — so ``zoo_replica_unhealthy`` goes back to
    0 without waiting for a hot-swap or a lucky retry.  When EVERY
    replica is unhealthy the set still falls back to serving through
    all of them — availability over purity, the gauge shows red until
    a probe succeeds.

    Elasticity: ``set_active(n)`` shrinks or grows the SCHEDULED set
    (the autoscaler's lever).  Deactivated replicas keep executables
    and params placed; re-activation primes every placed signature on
    the joining replica BEFORE it takes traffic (the registry's
    warm-before-activate discipline at runtime), so a scale-up never
    serves a cold replica and never compiles.
    """

    def __init__(self, fn: Callable, params, devices=None,
                 probe_backoff_s: float = 0.5,
                 probe_backoff_max_s: float = 30.0,
                 store="auto", tag: Optional[str] = None):
        self._fn = fn
        # per-model accounting tag for the executable store (stat
        # --by-model): rides every entry's header meta, never the key
        self._tag = tag
        # the set's placement units: one device per replica here, one
        # device GROUP (sub-mesh) per replica in ShardGroupSet — every
        # hook below keys off the unit, so the compile-once/
        # place-everywhere machinery is shared verbatim
        units = self._carve_units(devices)
        self._backend = self._unit_devices(units[0])[0].client
        # one jit wrapper for the whole set: every bucket's lowering
        # comes from it (a per-compile jax.jit would re-trace per call)
        self._jit = self._make_jit(units)
        # params are placed per unit ONCE at construction — the
        # per-dispatch upload is the padded batch alone
        placed0 = self._place_params(params, units[0])
        self._params_r0 = placed0
        # persistent executable store (read-through under
        # ensure_compiled, write-behind after each compile): "auto"
        # resolves the process store — None when none is configured,
        # which keeps every store branch below inert
        if store == "auto":
            store = _execstore().current()
        self._store = store
        # the weights are runtime ARGUMENTS of the replica executable,
        # so the compiled code is weight-agnostic — but the store key
        # must rotate on a weight change anyway: a redeploy with new
        # weights must never be answered by an entry recorded against
        # old ones.  Hashed once per set, at construction.
        self._wdigest = (_execstore().params_digest(placed0)
                         if store is not None else None)
        replicas = [self._make_replica(0, units[0], placed0)]
        for i, u in enumerate(units[1:], start=1):
            replicas.append(self._make_replica(
                i, u, self._place_params(params, u)))
        self.replicas: Tuple[Replica, ...] = tuple(replicas)
        self._n_param_leaves = len(self.replicas[0].params_flat)
        # per-signature executables: key -> (exe per replica, kept
        # indices or None, out treedef); published under _lock AFTER the
        # compile so readers never see a half-built entry
        self._exes: Dict[Tuple, Tuple] = {}
        self._kept: Dict[Tuple, Optional[Tuple[int, ...]]] = {}
        self._out_tree: Dict[Tuple, Any] = {}
        self._out_avals: Dict[Tuple, List] = {}
        self._lock = threading.Lock()
        self._compile_locks: Dict[Tuple, threading.Lock] = {}
        self._rr = 0
        self.probe_backoff_s = float(probe_backoff_s)
        self.probe_backoff_max_s = float(probe_backoff_max_s)
        # fast-path gate for maybe_reprobe: scanning the replica tuple
        # per dispatch is cheap, but one int compare is cheaper
        self._unhealthy_count = 0
        # serializes probes (dispatcher + solo threads may both ask)
        self._probe_guard = threading.Lock()

    # ---- placement-unit hooks (overridden by ShardGroupSet) ----
    # A "unit" is whatever one replica executes on: a single device
    # here, a (devices, mesh) sub-mesh in serving/shardgroup.py.  The
    # base class stays the single-device fast path — no mesh objects,
    # no sharding branches on its dispatch.

    def _carve_units(self, devices) -> List:
        devs = list(devices) if devices else list(jax.local_devices())
        if not devs:
            raise ValueError("ReplicaSet needs at least one device")
        return devs

    @staticmethod
    def _unit_devices(unit) -> Tuple:
        """The concrete devices behind one unit (backend access)."""
        return (unit,)

    def _make_jit(self, units):
        return jax.jit(self._fn)

    def _place_params(self, params, unit):
        return jax.device_put(params, unit)

    def _make_replica(self, index: int, unit, placed) -> "Replica":
        return Replica(index, unit, jax.tree_util.tree_leaves(placed))

    def _input_sharding(self):
        """The sharding batch inputs carry on replica 0 — the AOT
        lowering's input placement (and, in ShardGroupSet, the
        per-dispatch upload target)."""
        return jax.sharding.SingleDeviceSharding(self.replicas[0].device)

    def _fp_parts(self) -> Tuple:
        """Leading fingerprint components: the entry kind plus any
        layout extras that must rotate the store key.  ShardGroupSet
        appends the canonical mesh spec here so two deploys differing
        only in mesh shape / partition rules never share an entry."""
        return ("replica-forward",)

    def _store_meta(self) -> Dict[str, Any]:
        """Header metadata every store entry of this set carries
        (beyond kept/n_in/model, added by ensure_compiled)."""
        return {"kind": "replica-forward"}

    def span_labels(self, replica: "Replica") -> Dict[str, Any]:
        """Labels the dispatch path stamps on request spans for this
        unit.  ShardGroupSet adds ``group`` so a trace distinguishes
        which replica group served the request."""
        return {"replica": replica.index}

    def _place_serialized(self, ser: bytes, replica: "Replica"):
        """Rehydrate serialized-executable bytes onto one replica's
        unit.  The base maps a replica to its single device; the
        sharded set rewrites the assignment to span the whole group."""
        return self._load_serialized(ser, (replica.device,))

    @property
    def n(self) -> int:
        return len(self.replicas)

    @property
    def n_active(self) -> int:
        return sum(1 for r in self.replicas if r.active)

    @staticmethod
    def _key(batched) -> Tuple:
        leaves = jax.tree_util.tree_leaves(batched)
        return tuple((tuple(np.asarray(a).shape), str(np.asarray(a).dtype))
                     for a in leaves)

    @staticmethod
    def key_from(bucket: int, signature: Tuple) -> Tuple:
        """The placement key, derived from a cache-level
        ``(bucket, batch_signature)`` pair the dispatch path has
        already computed — equivalent to ``_key`` on the padded batch
        (every leaf's leading axis IS the bucket) without walking the
        input tree a second time."""
        return tuple(((bucket,) + tuple(shape), dtype)
                     for shape, dtype in signature)

    def compiled_keys(self) -> int:
        """How many distinct signatures hold a placed executable."""
        return len(self._exes)

    def placement_complete(self, key: Optional[Tuple] = None) -> bool:
        """True when every replica holds an executable for ``key`` (or
        for every placed key when None).  ensure_compiled publishes
        full tuples under the lock, so this holds by construction on
        any healthy set — it is the PAGER's install guard: a faulted-in
        model whose replica (group) placement is incomplete must never
        be published as resident, because for a sharded group partial
        residency means wrong answers, not degraded capacity."""
        with self._lock:
            keys = [key] if key is not None else list(self._exes)
            return all(len(self._exes[k]) == len(self.replicas)
                       for k in keys if k in self._exes)

    @staticmethod
    def _unwrap(compiled) -> Tuple[Any, Optional[Sequence[int]]]:
        """(PJRT executable, kept-input indices) of a jax ``Compiled``
        for the raw dispatch path.  ``runtime_executable()`` is public;
        the set of inputs XLA did not prune (``_kept_var_idx``) has no
        public accessor — with ``_load_serialized`` below, the only
        reach under jax's public surface, kept side by side."""
        return (compiled.runtime_executable(),
                getattr(compiled._executable, "_kept_var_idx", None))

    def _load_serialized(self, ser: bytes, devices: Sequence):
        """Load serialized-executable bytes onto ``devices`` (one
        replica, ``len(devices)`` partitions) with only the device
        assignment rewritten — also how a store entry rehydrates (it
        works with no original executable in hand).  A load, not a
        compile: no ``backend_compile`` event fires.

        ``jax.experimental.serialize_executable`` pickles device ids
        with the executable and cannot relocate it, so the PJRT client
        is called directly."""
        opts = _xla_client.CompileOptions()
        opts.device_assignment = _xla_client.DeviceAssignment.create(
            np.array([[d.id for d in devices]], dtype=np.int32))
        return self._backend.deserialize_executable(
            ser, _xla_client.DeviceList(tuple(devices)), opts)

    def ensure_compiled(self, batched, key: Optional[Tuple] = None
                        ) -> float:
        """Make the executable for ``batched``'s signature available
        on every replica — compiled once, or LOADED from the
        persistent executable store when a prior process (or deploy)
        already compiled the identical computation.  Returns the wall
        seconds spent (0.0 when the signature was already placed).
        Safe to call from several threads — concurrent DIFFERENT
        signatures compile in parallel (warmup's thread pool relies on
        this), the same signature compiles exactly once.  Callers on
        the dispatch path call this UNCONDITIONALLY (warm cost: one
        dict membership check): placement here is the authority, not
        any caller-side seen-bit — a concurrent cold dispatch may
        still be mid-compile, and a compile that failed once must be
        retryable.

        Store protocol (read-through / write-behind): the fingerprint
        covers the lowered HLO (graph + padded signature), the weights
        digest, and the runtime environment, so a hit is the SAME
        computation by construction; the entry carries
        ``_kept_var_idx`` so the raw dispatch path rehydrates without
        touching the compiled object's jax wrapper.  Any lookup or
        load failure falls back to the compile below — the store can
        cost a recompile, never serve a wrong executable.  Lookups
        happen only HERE, on the placement miss path — never on a
        per-dispatch hot path."""
        if key is None:
            key = self._key(batched)
        if key in self._exes:
            return 0.0
        with self._lock:
            klock = self._compile_locks.setdefault(key, threading.Lock())
        with klock:
            if key in self._exes:
                return 0.0
            t0 = time.perf_counter()
            dev0 = self.replicas[0].device
            s0 = self._input_sharding()
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.asarray(a).shape, np.asarray(a).dtype, sharding=s0),
                batched)
            # tracing + lowering runs on BOTH paths (it fires no
            # backend_compile event): on a store hit it only feeds the
            # fingerprint, on a miss it is the compile's input
            lowered = self._jit.lower(self._params_r0, specs)
            n_in = self._n_param_leaves \
                + len(jax.tree_util.tree_leaves(specs))
            store = self._store
            fp = None
            exe0 = None
            kept_t: Optional[Tuple[int, ...]] = None
            ser: Optional[bytes] = None
            if store is not None:
                fp = store.fingerprint(
                    *self._fp_parts(), _execstore().hlo_digest(lowered),
                    self._wdigest, key, device=dev0)
                ent = store.lookup(fp)
                if ent is not None:
                    try:
                        kept_t = ent.meta.get("kept")
                        if kept_t is not None:
                            # () is legitimate — an executable whose
                            # inputs all constant-folded away keeps
                            # zero of them; only out-of-RANGE indices
                            # indict the entry
                            kept_t = tuple(int(i) for i in kept_t)
                            if any(i < 0 or i >= n_in
                                   for i in kept_t):
                                raise ValueError(
                                    f"kept indices {kept_t} out of "
                                    f"range for {n_in} inputs")
                        ser = ent.payload
                        exe0 = self._place_serialized(
                            ser, self.replicas[0])
                    except Exception as e:  # noqa: BLE001 — ANY load
                        # failure (truncated bytes, foreign artifact,
                        # bad metadata) must fall back to a fresh
                        # compile: the store may cost a recompile,
                        # never a wrong executable
                        store.note_invalid(fp, e)
                        exe0, kept_t, ser = None, None, None
            if exe0 is None:
                # the ONE traced lowering + XLA compile for this
                # signature (this is the call the backend_compile
                # counter sees)
                exe0, kept = self._unwrap(lowered.compile())
                kept_t = (None if kept is None or len(kept) == n_in
                          else tuple(sorted(kept)))
                if len(self.replicas) > 1:
                    # multi-replica placement REQUIRES the bytes: a
                    # serialize failure here fails the deploy exactly
                    # as it did pre-store
                    ser = self._backend.serialize_executable(exe0)
                elif store is not None:
                    # store-only serialization is best-effort: a
                    # backend that cannot serialize must not fail a
                    # deploy that just compiled successfully
                    try:
                        ser = self._backend.serialize_executable(exe0)
                    except Exception as e:  # noqa: BLE001
                        ser = None
                        _slog.error("execstore_serialize_failed",
                                    error=f"{type(e).__name__}: {e}")
                if store is not None and ser is not None:
                    # write-behind: the device-0 serialization the
                    # multi-replica path produces anyway, plus the
                    # metadata the raw dispatch path needs back
                    meta = dict(self._store_meta())
                    meta.update({"kept": kept_t, "n_in": n_in})
                    if self._tag is not None:
                        meta["model"] = self._tag
                    store.put(fp, ser, meta=meta)
            exes = [exe0]
            # place everywhere: one serialization (from the compile or
            # from the store entry), loaded per unit with only the
            # device assignment rewritten — a load, not a compile
            for rep in self.replicas[1:]:
                exes.append(self._place_serialized(ser, rep))
            out_shapes = jax.eval_shape(self._fn, self._params_r0, specs)
            out_tree = jax.tree_util.tree_structure(out_shapes)
            out_avals = jax.tree_util.tree_leaves(out_shapes)
            with self._lock:
                self._kept[key] = kept_t
                self._out_tree[key] = out_tree
                self._out_avals[key] = out_avals
                self._exes[key] = tuple(exes)  # publish last
            return time.perf_counter() - t0

    def dispatch(self, replica: Replica, batched, spans: Sequence = (),
                 key: Optional[Tuple] = None):
        """Upload one exactly-bucket-sized host batch to ``replica``'s
        device and run its executable; returns the DEVICE result tree
        (fetch via :func:`fetch_rows`).  The signature must already be
        placed (``ensure_compiled``) — dispatch itself never compiles.
        ``spans`` get the ``device_put`` -> ``execute`` transitions
        (``execute`` stays open until the fetch, like the single-device
        path).  ``key`` skips re-deriving the signature when the caller
        already holds it (the per-dispatch hot path does)."""
        if key is None:
            key = self._key(batched)
        exe = self._exes[key][replica.index]
        for s in spans:
            s.phase_start("device_put")
        dev = replica.device
        dev_x = [jax.device_put(a, dev)
                 for a in jax.tree_util.tree_leaves(batched)]
        _profile.note_transfer("h2d")
        args = replica.params_flat + dev_x
        kept = self._kept[key]
        if kept is not None:
            args = [args[i] for i in kept]
        for s in spans:
            s.phase_start("execute")
        outs = exe.execute(args)
        return jax.tree_util.tree_unflatten(self._out_tree[key], outs)

    # ---- elasticity ----
    def _zeros_for(self, key: Tuple) -> List[np.ndarray]:
        """A host batch matching a placed signature — the key IS the
        full per-leaf (shape, dtype) list, so a warmed no-op input
        needs no remembered sample."""
        return [np.zeros(shape, dtype) for shape, dtype in key]

    def _prime(self, replica: Replica) -> None:
        """Execute every placed signature once on ``replica`` —
        warm-before-activate (and the probe body).  Never compiles:
        the executables were placed at ensure_compiled time (placement
        covers INACTIVE replicas too, exactly so this stays a load).
        Fetches via explicit device_get — priming must not leave work
        in flight behind the activation flip."""
        for key in list(self._exes):
            jax.device_get(self.dispatch(replica, self._zeros_for(key),
                                         key=key))

    def set_active(self, n: int) -> int:
        """Resize the scheduled replica set to ``n`` replicas (clamped
        to [1, total]); returns the active count.  Selection is
        HEALTH-AWARE, lowest index first: a dead replica must not hold
        a seat — or fail the whole resize from inside its prime —
        while healthy spares sit deactivated, so when healthy replicas
        run short the remainder fills with unhealthy ones, unprimed
        (the scheduler routes around them until their probe heals;
        placement already covered them, so healing never compiles).
        Healthy joiners are primed BEFORE the flag flips, so the
        scheduler never routes to a replica whose first request would
        pay lazy init; a joiner whose prime raises is marked unhealthy
        and the resize carries on with the rest.  Deactivation only
        unschedules: in-flight groups resolve normally and the replica
        keeps its warm state."""
        n = max(1, min(int(n), len(self.replicas)))
        chosen = {r.index for r in
                  sorted(self.replicas,
                         key=lambda r: (not r.healthy, r.index))[:n]}
        joining = [r for r in self.replicas
                   if r.index in chosen and not r.active]
        leaving = [r for r in self.replicas
                   if r.active and r.index not in chosen]
        for r in joining:
            if not r.healthy:
                continue  # never dispatch a prime to a red device
            try:
                self._prime(r)
            except RuntimeError as e:
                self.mark_unhealthy(r, e)
        with self._lock:
            for r in self.replicas:
                r.active = r.index in chosen
        if joining or leaving:
            _slog.info("replica_set_active", active=n,
                       total=len(self.replicas),
                       joined=[r.index for r in joining],
                       left=[r.index for r in leaving])
        return n

    # ---- health / scheduling ----
    def healthy_indices(self) -> List[int]:
        """Replica indices eligible for dispatch: active AND healthy.
        Falls back to the active set when every active replica is
        marked unhealthy (a fully-red set keeps serving — and keeps
        showing red — rather than bricking), then to ALL replicas."""
        out = [r.index for r in self.replicas if r.healthy and r.active]
        if out:
            return out
        out = [r.index for r in self.replicas if r.active]
        return out if out else [r.index for r in self.replicas]

    def mark_unhealthy(self, replica: Replica, exc: BaseException):
        now = time.perf_counter()
        with self._lock:
            if replica.healthy:
                replica.healthy = False
                self._unhealthy_count += 1
            replica.probe_backoff = max(replica.probe_backoff,
                                        self.probe_backoff_s)
            replica.probe_at = now + replica.probe_backoff
        _slog.error("replica_unhealthy", replica=replica.index,
                    device=str(replica.device),
                    probe_in_s=round(replica.probe_backoff, 3),
                    error=f"{type(exc).__name__}: {exc}")

    def maybe_reprobe(self) -> None:
        """Time-gated health re-probe of unhealthy replicas: a cheap
        warmed no-op execute per due replica, on exponential backoff
        (``probe_backoff_s`` doubling to ``probe_backoff_max_s``).  A
        probe that returns flips the replica healthy — recovery no
        longer depends on live-traffic retry luck.  Cost when all
        replicas are healthy: one int compare.

        The probe itself runs on a DETACHED daemon thread: this method
        is driven from the coalescer dispatcher and solo request
        threads, and a device that fails SLOWLY (wedged rather than
        raising) must stall the probe thread, not live traffic on the
        healthy replicas.  The non-blocking guard (held by the probe
        thread until it finishes) keeps concurrent dispatch paths from
        stacking probes."""
        if not self._unhealthy_count:
            return
        now = time.perf_counter()
        due = [r for r in self.replicas
               if not r.healthy and r.probe_at <= now]
        if not due:
            return
        if not self._probe_guard.acquire(blocking=False):
            return
        threading.Thread(target=self._probe_due, args=(due,),
                         name="zoo-replica-probe", daemon=True).start()

    def _probe_due(self, due: List[Replica]) -> None:
        """Probe-thread body: probe each due replica, then release the
        guard (the guard is acquired by maybe_reprobe and handed to
        this thread)."""
        try:
            for r in due:
                self._probe(r)
        finally:
            self._probe_guard.release()

    def _probe(self, replica: Replica) -> bool:
        """One health probe: execute the smallest placed signature on
        ``replica`` and fetch the result.  Success restores health
        (and resets the backoff); device-side failure doubles it."""
        with self._lock:
            keys = list(self._exes)
        if not keys:
            return False  # nothing placed yet — nothing warm to probe
        key = min(keys, key=lambda k: k[0][0][0] if k and k[0][0] else 0)
        try:
            jax.device_get(self.dispatch(replica, self._zeros_for(key),
                                         key=key))
        except RuntimeError as e:
            with self._lock:
                replica.probe_backoff = min(replica.probe_backoff * 2.0
                                            or self.probe_backoff_s,
                                            self.probe_backoff_max_s)
                replica.probe_at = (time.perf_counter()
                                    + replica.probe_backoff)
            _slog.info("replica_probe_failed", replica=replica.index,
                       next_probe_in_s=round(replica.probe_backoff, 3),
                       error=f"{type(e).__name__}: {e}")
            return False
        with self._lock:
            if not replica.healthy:
                replica.healthy = True
                self._unhealthy_count -= 1
            replica.probe_backoff = self.probe_backoff_s
        _slog.info("replica_recovered", replica=replica.index,
                   device=str(replica.device))
        return True

    def retry_target(self, failed: Replica) -> Optional[Replica]:
        """A healthy replica other than ``failed`` (round-robin), or
        None when there is nowhere left to retry.  Inactive-but-healthy
        replicas are eligible — they are warm and idle, the best
        possible place for a one-off retry."""
        with self._lock:
            cands = [r for r in self.replicas
                     if r.healthy and r is not failed]
            if not cands:
                return None
            self._rr += 1
            return cands[self._rr % len(cands)]

    def pick(self) -> Replica:
        """Round-robin over active healthy replicas — the solo
        (non-coalesced) path's scheduler.  The coalescer's dispatcher
        uses least-outstanding-work instead (it owns the in-flight
        counts).  Also the solo path's probe driver: each pick gives
        due unhealthy replicas their time-gated recovery probe."""
        self.maybe_reprobe()
        with self._lock:
            idxs = [r.index for r in self.replicas
                    if r.healthy and r.active]
            if not idxs:
                idxs = [r.index for r in self.replicas if r.active] \
                    or [r.index for r in self.replicas]
            self._rr += 1
            return self.replicas[idxs[self._rr % len(idxs)]]

    def stats(self) -> Dict[str, Any]:
        return {
            "replicas": len(self.replicas),
            "replicas_active": self.n_active,
            "replica_dispatches": {r.index: r.dispatches
                                   for r in self.replicas},
            "replica_unhealthy": {r.index: (not r.healthy)
                                  for r in self.replicas},
            "replica_active": {r.index: r.active
                               for r in self.replicas},
            "replica_bucket_dispatches": {
                r.index: dict(r.bucket_dispatches)
                for r in self.replicas},
        }


class BucketedExecutableCache:
    """Pad batches to a bucket ladder so a ragged request stream hits a
    handful of compiled executables.

    ``fn`` is the (jitted underneath) forward over one host batch; the
    jit's own shape cache holds the executables — this layer guarantees
    only ladder shapes ever reach it, tracks hit/miss/compile-time per
    bucket, and un-pads results.  Batches larger than the top bucket are
    served in top-bucket chunks (the tail padded), so arbitrarily large
    inputs still hit only ladder shapes.
    """

    def __init__(self, fn: Callable, max_batch: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 growth: float = 2.0,
                 replica_set: Optional[ReplicaSet] = None):
        self._fn = fn
        self.buckets = (tuple(sorted(set(int(b) for b in buckets)))
                        if buckets else bucket_ladder(max_batch, growth))
        if self.buckets[0] < 1:
            raise ValueError(f"buckets must be >= 1, got {self.buckets}")
        self.max_batch = self.buckets[-1]
        self.stats = BucketStats()
        # device-parallel backend: when set, dispatches route to one of
        # its replicas (compile-once/place-everywhere) instead of the
        # single jitted ``fn``
        self.replica_set = replica_set
        self._seen: set = set()
        self._lock = threading.Lock()

    def bucket_for(self, n: int) -> int:
        """Smallest ladder bucket >= n (top bucket for oversized n)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def _note_lookup(self, bucket: int, signature: Tuple) -> bool:
        """Hit/miss bookkeeping for one bucket lookup — the ONE counter
        protocol shared by the dispatch path and warmup.  Returns True
        when this (bucket, signature) is new to the cache."""
        sig = (bucket, signature)
        with self._lock:
            fresh = sig not in self._seen
            if fresh:
                self._seen.add(sig)
                self.stats.misses[bucket] = \
                    self.stats.misses.get(bucket, 0) + 1
            else:
                self.stats.hits[bucket] = self.stats.hits.get(bucket, 0) + 1
        return fresh

    def _note_compile(self, bucket: int, secs: float):
        with self._lock:
            self.stats.compile_time_s[bucket] = \
                self.stats.compile_time_s.get(bucket, 0.0) + secs

    def _dispatch(self, batched, bucket: int, spans: Sequence = (),
                  replica: Optional[Replica] = None):
        """Run one exactly-bucket-sized padded batch, with counters.
        ``spans`` are the riders' trace spans: each gets the
        ``device_put`` -> ``execute`` phase transitions and its padded
        bucket as a label (``execute`` stays open — it ends when the
        owner starts ``depad`` after the fetch).  With a replica set the
        batch routes to ``replica`` (or the round-robin pick), retried
        once on another replica if the dispatch raises."""
        signature = batch_signature(batched)
        fresh = self._note_lookup(bucket, signature)
        for s in spans:
            s.set_label("bucket", bucket)
        if self.replica_set is not None:
            return self._dispatch_replica(self.replica_set, batched,
                                          bucket, signature, fresh,
                                          spans, replica)
        for s in spans:
            s.phase_start("device_put")
        # explicit upload: handing numpy straight to the jit is an
        # IMPLICIT host->device transfer per dispatch — same bytes
        # moved, but invisible to jax's transfer guards.  device_put
        # keeps the hot loop clean under zoolint.sanitize() (and on a
        # real TPU makes the per-dispatch upload an auditable event).
        batched = jax.device_put(batched)
        _profile.note_transfer("h2d")
        for s in spans:
            s.phase_start("execute")
        if fresh:
            t0 = time.perf_counter()
            # the dispatcher thread has no contextvar span, so the XLA
            # profile hook would drop this compile's span event;
            # activating the group's lead span here (cold path only)
            # keeps the docstring promise that an unwarmed shape shows
            # up IN the request's trace
            with _trace.activate(spans[0] if spans else None):
                out = jax.block_until_ready(self._fn(batched))
            self._note_compile(bucket, time.perf_counter() - t0)
            return out
        return self._fn(batched)

    def _dispatch_replica(self, rs: ReplicaSet, batched, bucket: int,
                          signature: Tuple, fresh: bool,
                          spans: Sequence,
                          replica: Optional[Replica]):
        """Replica-path half of ``_dispatch``: ensure the signature is
        compiled-and-placed, route to a replica, and retry ONCE on
        another healthy replica when the dispatch raises a runtime
        error (the failed one is marked unhealthy).

        ``ensure_compiled`` runs UNCONDITIONALLY — the ``fresh``
        hit/miss bit only attributes the compile's span event.  Gating
        placement on it would race: a second request can see
        fresh=False while the first is still mid-compile, and a compile
        that raised once would leave the signature poisoned forever.
        The warm-path cost is one dict membership check."""
        key = ReplicaSet.key_from(bucket, signature)
        with _trace.activate(spans[0] if (fresh and spans) else None):
            # on the cold path the lead span is active so the compile's
            # backend_compile event attributes to the request paying it
            secs = rs.ensure_compiled(batched, key=key)
        if secs:
            self._note_compile(bucket, secs)
        if replica is None:
            replica = rs.pick()
        for s in spans:
            for lk, lv in rs.span_labels(replica).items():
                s.set_label(lk, lv)
        try:
            out = rs.dispatch(replica, batched, spans, key=key)
        except RuntimeError as e:
            # RuntimeError covers device-side failures (XlaRuntimeError
            # subclasses it) — those indict the REPLICA.  Host-side
            # errors (TypeError/ValueError on a malformed input, or
            # KeyboardInterrupt) propagate untouched: one bad request
            # must not flip healthy hardware red.
            rs.mark_unhealthy(replica, e)
            alt = rs.retry_target(replica)
            if alt is None:
                raise
            for s in spans:
                for lk, lv in rs.span_labels(alt).items():
                    s.set_label(lk, lv)
                s.event("replica_retry", failed=replica.index,
                        error=type(e).__name__)
            try:
                out = rs.dispatch(alt, batched, spans, key=key)
            except RuntimeError as e2:
                # the retry replica is just as dead — say so in the
                # gauge before surfacing the error (no second retry:
                # a model-level fault would loop over every replica)
                rs.mark_unhealthy(alt, e2)
                raise
            replica = alt
        with self._lock:
            replica.dispatches += 1
            replica.bucket_dispatches[bucket] = \
                replica.bucket_dispatches.get(bucket, 0) + 1
        return out

    def run(self, batched, sem: Optional[threading.Semaphore] = None,
            span=None):
        """Serve one host batch of any row count; returns HOST numpy
        results with padding rows removed.  ``sem`` (the owner's
        device-concurrency bound) is held around the DISPATCH only —
        the blocking host fetch happens outside it, so concurrent
        callers' dispatches overlap each other's result transfers.
        ``span`` (the request's trace span, if tracing) records the
        pad/device_put/execute/depad phases — once per chunk for
        oversized batches."""
        guard = sem if sem is not None else contextlib.nullcontext()
        spans = (span,) if span is not None else ()
        n = _rows(batched)
        if n == 0:
            # run the smallest bucket and keep zero rows — the output
            # structure/shape contract stays intact for empty inputs
            with guard:
                out = self._dispatch(_pad_rows(batched, self.buckets[0]),
                                     self.buckets[0], spans)
            return fetch_rows(out, 0, span=span)
        outs = []
        start = 0
        while start < n:
            take = min(self.max_batch, n - start)
            chunk = _slice_rows(batched, start, start + take) \
                if (start or take < n) else batched
            bucket = self.bucket_for(take)
            if span is not None:
                span.phase_start("pad")
            padded = _pad_rows(chunk, bucket - take)
            with guard:
                out = self._dispatch(padded, bucket, spans)
            outs.append(fetch_rows(out, take, span=span))
            start += take
        return _concat_trees(outs)

    def dispatch_padded(self, batched, spans: Sequence = (),
                        replica: Optional[Replica] = None):
        """Async single dispatch: pad to the bucket and return the
        DEVICE result tree without fetching.  jax dispatch is
        asynchronous, so the caller can overlap host work (gathering
        the next batch) with this compute and fetch later via
        ``fetch_rows``.  One bucket only — rows must fit ``max_batch``.
        ``replica`` pins the dispatch to one replica of the replica set
        (the coalescer's least-outstanding-work scheduler passes it)."""
        n = _rows(batched)
        if n > self.max_batch:
            raise ValueError(
                f"dispatch_padded: {n} rows exceed the top bucket "
                f"{self.max_batch}; use run() for chunked serving")
        bucket = self.bucket_for(max(n, 1))
        for s in spans:
            s.phase_start("pad")
        return self._dispatch(_pad_rows(batched, bucket - n), bucket,
                              spans, replica=replica)

    def warmup(self, sample_shapes, dtypes=None,
               buckets: Optional[Sequence[int]] = None) -> float:
        """AOT-compile the ladder for one input signature — and, with a
        replica set, place + prime every replica's executable.

        ``sample_shapes``: per-sample shape (no batch axis) for a
        single-input model, or a list of them for multi-input;
        ``dtypes`` matches element-wise (default float32).  Returns the
        total compile wall seconds spent (wall, not CPU: bucket
        compiles overlap in a small thread pool — XLA compiles release
        the GIL, so the ladder compiles concurrently and the hot-swap
        blip a deploy pays shrinks accordingly).  Per-bucket compile
        milliseconds go through the structured logger."""
        multi = (sample_shapes and
                 isinstance(sample_shapes[0], (tuple, list)))
        shapes = list(sample_shapes) if multi else [sample_shapes]
        if dtypes is None:
            dts = [np.float32] * len(shapes)
        elif isinstance(dtypes, (tuple, list)):
            dts = list(dtypes)
        else:
            dts = [dtypes] * len(shapes)
        rs = self.replica_set
        ladder = list(buckets or self.buckets)

        def warm_one(b: int) -> float:
            arrs = tuple(np.zeros((b,) + tuple(s), dt)
                         for s, dt in zip(shapes, dts))
            batched = arrs if multi else arrs[0]
            if rs is None:
                tb = time.perf_counter()
                self._dispatch(batched, b)
                ms = (time.perf_counter() - tb) * 1e3
            else:
                # replica path: compile + place via ensure_compiled
                # (same counter protocol as the dispatch path, via
                # _note_lookup), then prime EVERY replica's executable
                # so no replica's first live request pays lazy init.
                # Priming bypasses the dispatch counters — warmup must
                # not skew the scheduler-balance metrics — and the
                # logged compile_ms is the COMPILE alone, not the N
                # priming executions.
                self._note_lookup(b, batch_signature(batched))
                secs = rs.ensure_compiled(batched)
                if secs:
                    self._note_compile(b, secs)
                for rep in rs.replicas:
                    jax.block_until_ready(rs.dispatch(rep, batched))
                ms = secs * 1e3
            _slog.info("warmup_bucket", bucket=b,
                       compile_ms=round(ms, 3),
                       replicas=(rs.n if rs is not None else 1))
            return ms

        t0 = time.perf_counter()
        if len(ladder) > 1:
            with ThreadPoolExecutor(
                    max_workers=min(len(ladder), 4),
                    thread_name_prefix="zoo-warmup") as pool:
                list(pool.map(warm_one, ladder))
        else:
            for b in ladder:
                warm_one(b)
        return time.perf_counter() - t0


def fetch_rows(device_tree, n: int, span=None):
    """Block on a ``dispatch_padded`` result and strip the padding.
    With a ``span`` the blocking fetch closes the open ``execute``
    phase (``depad`` starts once the bytes are on the host)."""
    host = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)), device_tree)
    _profile.note_transfer("d2h")
    if span is not None:
        span.phase_start("depad")
    out = _slice_rows(host, 0, n)
    if span is not None:
        span.phase_end()
    return out


class _StagingArena:
    """Zero-alloc staging for the dispatcher thread: reusable host
    buffers, one ring per (slot, bucket, signature), that coalesced
    riders are gathered into directly — eliminating the per-group
    ``np.concatenate`` + pad allocations on the hot path.

    OWNERSHIP RULE: single-owner, dispatcher thread only — no locks by
    design.  Reuse safety: ``device_put`` of a host buffer may be
    ZERO-COPY (the device array aliases the buffer until the execution
    consumes it), so a buffer must not be rewritten while its dispatch
    is still in flight.  Each slot's ring holds ``depth`` buffers,
    rotated per dispatch, and the coalescer (a) caps per-slot in-flight
    groups at ``depth`` and (b) resolves FIFO — so by the time a buffer
    rotates back around, the dispatch that used it has been fetched.
    """

    __slots__ = ("depth", "_bufs", "_turn", "_pending")

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self._bufs: Dict[Tuple, List] = {}
        self._turn: Dict[Tuple, int] = {}
        self._pending: Optional[Tuple] = None

    def buffers_allocated(self) -> int:
        """Total staging buffers currently held (introspection)."""
        return sum(1 for ring in self._bufs.values()
                   for b in ring if b is not None)

    def commit(self):
        """Advance the ring of the last ``pack``ed key — called by the
        dispatcher ONLY after its dispatch succeeded.  A failed
        dispatch leaves the turn in place (that buffer is free to
        rewrite), keeping rotation in lock-step with the in-flight cap:
        advancing on failure would desync them and let a later pack
        land on a buffer whose dispatch is still in flight."""
        key = self._pending
        if key is not None:
            self._pending = None
            self._turn[key] = (self._turn[key] + 1) % self.depth

    def pack(self, group: Sequence["_Request"], bucket: int, slot: int):
        """Gather ``group``'s rows into the current staging buffer for
        (slot, bucket), zero the padding tail, and return the padded
        batch tree (exactly ``bucket`` rows) — same structure as the
        riders' batches, backed by arena memory.  The ring only
        advances on ``commit()``."""
        head = group[0]
        key = (slot, bucket, head.sig)
        ring = self._bufs.get(key)
        if ring is None:
            ring = self._bufs[key] = [None] * self.depth
            self._turn[key] = 0
        turn = self._turn[key]
        self._pending = key
        leaves0, treedef = jax.tree_util.tree_flatten(head.batched)
        bufs = ring[turn]
        if bufs is None:
            bufs = ring[turn] = [
                np.zeros((bucket,) + tuple(np.asarray(l).shape[1:]),
                         np.asarray(l).dtype)
                for l in leaves0]
        off = 0
        for r in group:
            leaves = (leaves0 if r is head
                      else jax.tree_util.tree_leaves(r.batched))
            for buf, leaf in zip(bufs, leaves):
                buf[off:off + r.n] = leaf
            off += r.n
        if off < bucket:
            for buf in bufs:
                buf[off:bucket] = 0
        return jax.tree_util.tree_unflatten(treedef, bufs)


class _Request:
    # ``span`` is the EXPLICIT cross-thread trace handoff: contextvars
    # do not propagate into the dispatcher thread (started long before
    # this request existed), so the pending request carries its span
    # and the dispatcher records phases on it directly.
    __slots__ = ("batched", "n", "sig", "future", "span")

    def __init__(self, batched, n, sig, span=None):
        self.batched = batched
        self.n = n
        self.sig = sig
        self.span = span
        self.future: Future = Future()


_SHUTDOWN = object()


class CoalescerClosedError(RuntimeError):
    """The dispatcher is gone — this request was (or would be) never
    served.  Distinct type so callers can fall back to the solo path
    without masking genuine model-execution errors (XlaRuntimeError is
    a RuntimeError subclass)."""


class RequestCoalescer:
    """Pack concurrent predict() calls into one device dispatch, with
    the NEXT batch gathered while the current one computes.

    Callers ``submit()`` into a bounded queue; a single dispatcher
    thread takes the head request, gathers same-signature riders until
    ``max_batch`` rows are packed, ``max_wait_ms`` elapses, or the
    queue momentarily drains, concatenates them into one padded batch,
    and dispatches it through the bucketed ``cache`` WITHOUT fetching —
    jax dispatch is asynchronous, so the dispatcher goes straight back
    to gathering the next group while the device computes, then fetches
    and fans rows back onto each caller's Future (one-deep pipeline:
    the serving-side analog of the data path's double-buffered
    prefetch).  A signature mismatch ends a group — the odd request
    leads the next one, so mixed streams stay correct, just un-packed
    across shapes.

    With a multi-replica cache the pipeline generalizes from depth
    ``pipeline_depth`` on one device to depth N across devices: every
    replica owns ONE in-flight slot, and each group routes to the
    healthy replica with the fewest undelivered groups
    (least-outstanding-work), so group k+1 executes on replica B while
    group k's fetch from replica A is still in flight.

    Groups are staged through a :class:`_StagingArena` (reusable
    dispatcher-owned buffers) instead of a fresh concatenate+pad per
    dispatch — the steady-state hot path allocates nothing on the host
    side.

    ``semaphore`` (the owner's ``supported_concurrent_num`` bound) is
    held from dispatch to fetch so coalesced work counts against the
    same device-concurrency budget as solo calls.
    """

    # forced loser-drain budget: a pending hedge loser still in flight
    # past this is treated as WEDGED (its replica marked unhealthy)
    # instead of blocking the dispatcher indefinitely.  Class-level so
    # tests can shrink it per instance.
    _WEDGE_TIMEOUT_S = 30.0
    # the hedge threshold quantile is recomputed every N group
    # resolves, not per group (see _hedge_threshold_s)
    _HEDGE_THR_REFRESH = 32

    def __init__(self, cache: BucketedExecutableCache,
                 max_batch: Optional[int] = None,
                 max_wait_ms: float = 2.0,
                 semaphore: Optional[threading.Semaphore] = None,
                 pipeline_depth: int = 2,
                 queue_size: int = 1024,
                 hedging: bool = False,
                 hedge_quantile: float = 0.99,
                 hedge_min_ms: float = 0.5,
                 hedge_min_samples: int = 20):
        self._cache = cache
        self.max_batch = int(max_batch or cache.max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self._sem = semaphore
        self.pipeline_depth = max(1, int(pipeline_depth))
        rs = cache.replica_set
        # one slot per replica (cap 1 each) when device-parallel; one
        # slot with the legacy pipeline depth as its cap otherwise
        self._rs = rs if (rs is not None and rs.n > 1) else None
        self._n_slots = self._rs.n if self._rs is not None else 1
        self._slot_cap = 1 if self._rs is not None else self.pipeline_depth
        self._slot_inflight = [0] * self._n_slots
        self._slot_rr = 0
        self._arena = _StagingArena(self._slot_cap)
        # ---- p99 hedging (device-parallel only: a hedge needs a
        # second replica to win on).  The threshold derives from the
        # observed group resolve-latency quantile, so "straggler"
        # means straggler RELATIVE to this model's own distribution.
        self.hedging = bool(hedging) and self._rs is not None
        self.hedge_quantile = float(hedge_quantile)
        self.hedge_min_ms = float(hedge_min_ms)
        self.hedge_min_samples = int(hedge_min_samples)
        self._group_lat = _LatencyWindow(maxlen=512)
        # dispatcher-thread-owned threshold cache (see
        # _hedge_threshold_s): (value, window count at compute)
        self._hedge_thr: Optional[float] = None
        self._hedge_thr_at = -1
        # dispatcher-thread-owned counters (read via dict copy)
        self._hedges = {"fired": 0, "primary_won": 0, "hedge_won": 0,
                        "skipped_no_replica": 0}
        self._hedge_pool: Optional[ThreadPoolExecutor] = None
        # loser futures already reported as wedged (bounded: entries
        # leave when their loser retires)
        self._wedged_reported: set = set()
        # hedge losers still aliasing a staging buffer: (primary_slot,
        # future, hedge_replica_index|None).  The primary slot's
        # in-flight count is held until the losing fetch returns (the
        # PR 5 retry-window ownership rule — see _drain_losers); the
        # third element releases the hedge replica's own in-flight
        # count when the pending loser IS the hedge
        self._pending_losers: List[Tuple[int, Future,
                                         Optional[int]]] = []
        self._q: "queue.Queue" = queue.Queue(maxsize=queue_size)
        self._carry: Optional[_Request] = None
        self.dispatches = 0
        self.coalesced_requests = 0
        # live-request accounting: _outstanding counts submitted-but-
        # unresolved requests; _inflight_n the subset already dispatched.
        # Their difference is every rider that could still arrive — once
        # a group holds them all, waiting any longer is pure latency.
        self._outstanding = 0
        self._out_lock = threading.Lock()
        self._inflight_n = 0
        self._closed = False
        # makes (closed-check + enqueue) atomic against close()'s
        # (set-closed + sentinel + drain): a submit can never slip into
        # the queue after the drain.  Separate from _out_lock — a put
        # blocking on a full queue must not deadlock the dispatcher's
        # _done() accounting.
        self._submit_lock = threading.Lock()
        self._flush_lock = threading.Lock()
        self._crashed = False
        self._inflight: "collections.deque" = collections.deque()
        self._thread = threading.Thread(
            target=self._loop, name="zoo-serving-dispatch", daemon=True)
        self._thread.start()

    @property
    def closed(self) -> bool:
        """True once close() ran or the dispatcher died — submits would
        never be served."""
        return self._closed or not self._thread.is_alive()

    @property
    def pending(self) -> int:
        """Submitted-but-unresolved request count (queued + in flight)."""
        with self._out_lock:
            return self._outstanding

    def hedge_stats(self) -> Dict[str, int]:
        """Copy of the hedge outcome counters (dispatcher-owned ints;
        the copy is GIL-atomic enough for a metrics scrape)."""
        return dict(self._hedges)

    def submit(self, batched, span=None) -> Future:
        n = _rows(batched)
        if n > self.max_batch:
            raise ValueError(
                f"coalesced request of {n} rows exceeds max_batch "
                f"{self.max_batch} — send it through the solo path")
        if span is not None:
            # open here, on the caller's thread: coalesce_wait covers
            # queue time + group gathering, ending when the dispatcher
            # starts the group's pad phase
            span.phase_start("coalesce_wait")
        req = _Request(batched, n, batch_signature(batched), span)
        with self._submit_lock:
            if self.closed:
                raise CoalescerClosedError(
                    "RequestCoalescer is closed — no dispatcher is "
                    "serving this queue")
            with self._out_lock:
                self._outstanding += 1
            self._q.put(req)
        if self._crashed or not self._thread.is_alive():
            # the dispatcher died between the aliveness check and the
            # enqueue — its crash-net drain may already have run, so
            # nobody would ever serve (or fail) this request.  Flush it
            # (and anything else stranded) ourselves.  ``_crashed`` is
            # set BEFORE the crash net's flush, so even a put that was
            # blocked on a full queue (and only completed because that
            # flush freed a slot, while the crashing thread still reads
            # as alive) observes it here.
            self._flush_queue(CoalescerClosedError(
                "RequestCoalescer dispatcher died"))
        return req.future

    def _done(self, k: int):
        with self._out_lock:
            self._outstanding -= k

    def _flush_queue(self, exc: BaseException):
        """Fail every queued (never-dispatched) request with ``exc``.
        Only safe once no dispatcher owns the queue: closed-and-joined,
        crashed, or from the crash net itself.  ``_flush_lock``
        serializes the crash net against a concurrent submit-side flush
        (both may race to fail the same carry)."""
        with self._flush_lock:
            leftovers, self._carry = (
                [self._carry] if self._carry is not None else []), None
            try:
                while True:
                    r = self._q.get_nowait()
                    if r is not _SHUTDOWN:
                        leftovers.append(r)
            except queue.Empty:
                pass
            # flushed requests leave the live count too — ``pending``
            # must not report phantom requests on a dead coalescer
            self._done(len(leftovers))
            for r in leftovers:
                if not r.future.done():
                    r.future.set_exception(exc)

    def close(self, timeout: float = 5.0):
        """Stop the dispatcher: already-queued requests are SERVED (the
        shutdown sentinel sits behind them in the queue — this is the
        graceful drain reload()/the registry rely on), then anything
        racing the shutdown fails with CoalescerClosedError
        (idempotent)."""
        with self._submit_lock:
            already = self._closed
            self._closed = True
            if not already and self._thread.is_alive():
                self._q.put(_SHUTDOWN)
        if self._thread.is_alive():
            self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            # the dispatcher is wedged mid-group (e.g. a long compile) —
            # it still owns _carry and the queue, so leave both alone;
            # it will drain to the sentinel and exit on its own
            return
        self._flush_queue(CoalescerClosedError("RequestCoalescer closed"))

    # ---- dispatcher ----
    def _gather(self, block: bool,
                pipeline_busy: bool = False) -> Tuple[List[_Request], bool]:
        """One group: head + same-signature riders until the batch is
        full, the wait budget lapses, or the queue momentarily drains.
        The drain condition is the important one: callers are blocked
        on their futures, so once the queue is empty, holding a partial
        batch for the rest of ``max_wait_ms`` cannot attract closed-loop
        riders — it only adds their wait to every row.  A short grace
        (max_wait/8) still absorbs staggered arrivals.  Returns
        (group, shutdown_seen); with ``block`` False the head wait is
        bounded by the grace too (a dispatch is in flight — the
        dispatcher must come back to fetch it promptly)."""
        grace = max(min(self.max_wait_ms / 8.0, 0.5), 0.05) / 1000.0
        head = self._carry
        self._carry = None
        if head is None:
            try:
                head = (self._q.get() if block
                        else self._q.get(timeout=grace))
            except queue.Empty:
                return [], False
            if head is _SHUTDOWN:
                return [], True
        group, count, rows = [head], 1, head.n
        deadline = time.perf_counter() + self.max_wait_ms / 1000.0
        while rows < self.max_batch:
            # every live request not yet dispatched is either in this
            # group or could still ride it; once the group holds them
            # all, no grace wait can attract another — dispatch now.
            # Only when the device is idle, though: with a dispatch in
            # flight there is no urgency, and the about-to-resolve
            # riders will want seats on THIS group
            if not pipeline_busy \
                    and count >= self._outstanding - self._inflight_n:
                break
            remaining = deadline - time.perf_counter()
            try:
                nxt = (self._q.get_nowait() if remaining <= 0
                       else self._q.get(timeout=min(remaining, grace)))
            except queue.Empty:
                break
            if nxt is _SHUTDOWN:
                return group, True
            if nxt.sig != head.sig or rows + nxt.n > self.max_batch:
                self._carry = nxt
                break
            group.append(nxt)
            count += 1
            rows += nxt.n
        return group, False

    def _acquire_slot(self, inflight):
        """Take one device-concurrency slot without deadlocking: the
        dispatcher itself may hold every slot via unfetched dispatches,
        so on contention it resolves its oldest in-flight group (which
        releases a slot) before blocking."""
        if self._sem is None:
            return
        while not self._sem.acquire(blocking=False):
            if inflight:
                self._resolve(inflight.popleft())
            else:
                self._sem.acquire()  # held by solo callers — just wait
                return

    def _pick_slot(self) -> int:
        """Least-outstanding-work: the healthy replica with the fewest
        undelivered groups (dispatcher thread only — the counts are
        single-owner state).  Ties rotate round-robin so a lightly
        loaded stream (every dispatch resolved before the next) still
        spreads across replicas instead of camping on index 0.  Slot 0
        when not device-parallel.

        ONLY below-cap slots are eligible — this is the arena-safety
        invariant, not a preference.  The healthy set can shrink
        between the caller's capacity check and this pick (a SOLO-path
        dispatch on another thread may mark a replica unhealthy at any
        time), so an at-cap "least loaded healthy" slot is possible
        here; picking it would rewrite a staging buffer whose
        zero-copy dispatch is still in flight.  The in-flight counts
        themselves only change on this thread, so a below-cap slot the
        caller saw is still below cap — falling back to ANY below-cap
        slot (even an unhealthy one: its buffer is free, and the
        cache's fault retry re-routes the execution) always succeeds."""
        if self._rs is None:
            return 0
        idxs = [i for i in self._rs.healthy_indices()
                if self._slot_inflight[i] < self._slot_cap]
        if not idxs:
            idxs = [i for i in range(self._n_slots)
                    if self._slot_inflight[i] < self._slot_cap]
        rr = self._slot_rr
        slot = min(idxs, key=lambda i: (self._slot_inflight[i],
                                        (i - rr) % self._n_slots))
        self._slot_rr = (slot + 1) % self._n_slots
        return slot

    def _has_free_capacity(self) -> bool:
        """True when some eligible slot is below its in-flight cap —
        i.e. a new group can be staged without rewriting an arena
        buffer that is still in flight."""
        if self._rs is None:
            return len(self._inflight) < self._slot_cap
        return any(self._slot_inflight[i] < self._slot_cap
                   for i in self._rs.healthy_indices())

    def _capacity(self) -> int:
        """Total undelivered-group capacity across eligible slots."""
        if self._rs is None:
            return self._slot_cap
        return len(self._rs.healthy_indices()) * self._slot_cap

    def _dispatch_group(self, group: List[_Request], inflight):
        """Stage into the arena + async dispatch; returns an in-flight
        entry (group, rows, device_out, slot, t_dispatch, padded_batch,
        placement_key) or None when the dispatch itself failed.  The
        caller guarantees a free slot (arena-reuse safety — see
        :class:`_StagingArena`).  The padded batch and placement key
        ride along so a later hedge can re-dispatch the SAME staged
        buffer to another replica without re-packing."""
        try:
            spans = tuple(r.span for r in group if r.span is not None)
            for s in spans:
                s.phase_start("pad")  # ends coalesce_wait; covers staging
            n = sum(r.n for r in group)
            slot = self._pick_slot()
            bucket = self._cache.bucket_for(max(n, 1))
            batched = self._arena.pack(group, bucket, slot)
            replica = (self._rs.replicas[slot]
                       if self._rs is not None else None)
            key = (ReplicaSet.key_from(bucket, group[0].sig)
                   if self._rs is not None else None)
            self._acquire_slot(inflight)
            try:
                dev = self._cache.dispatch_padded(batched, spans,
                                                  replica=replica)
            except BaseException:
                if self._sem is not None:
                    self._sem.release()
                raise
            self._arena.commit()  # dispatch succeeded: rotate the ring
            self.dispatches += 1
            self.coalesced_requests += len(group)
            self._inflight_n += len(group)
            # charged to the PICKED slot even if the cache's fault
            # retry actually executed on another replica: the slot
            # count is what guards this slot's staging buffer against
            # rewrite-while-in-flight, and the buffer belongs to the
            # picked slot regardless of where execution landed.  The
            # scheduling skew (retry replica briefly carries two
            # groups) is bounded to the rare fault window and
            # self-corrects at resolve.
            self._slot_inflight[slot] += 1
            return group, n, dev, slot, time.perf_counter(), batched, key
        except BaseException as e:
            self._done(len(group))
            for r in group:
                if not r.future.done():
                    r.future.set_exception(e)
            return None

    # ---- resolve (plain + hedged) ----
    def _fetch_slot(self, dev, n: int, slot: int):
        """Blocking host fetch of a dispatched group.  A method (not a
        bare ``fetch_rows`` call) so a test can patch a per-slot
        straggler delay in — the injection point of the hedging tests
        (test_serving_elastic)."""
        return fetch_rows(dev, n)

    def _fetch_hedge(self, dev, n: int, replica_index: int):
        """Blocking host fetch of a hedge re-dispatch (separate patch
        point: a test can delay the hedge to pin primary-wins)."""
        return fetch_rows(dev, n)

    def _hedge_threshold_s(self) -> Optional[float]:
        """The in-flight age past which a group is hedged: the
        ``hedge_quantile`` of observed group resolve latencies, floored
        by ``hedge_min_ms``.  None until ``hedge_min_samples`` groups
        have resolved — hedging from an unseeded distribution would
        fire on noise.  The quantile is recomputed only every
        ``_HEDGE_THR_REFRESH`` resolves: ``percentile`` sorts the whole
        window under its lock, and a quantile over a 512-sample window
        barely moves across 32 adds — per-group sorting on the
        dispatcher's hot path bought nothing."""
        c = self._group_lat.count
        if c < self.hedge_min_samples:
            return None
        if (self._hedge_thr is None
                or c - self._hedge_thr_at >= self._HEDGE_THR_REFRESH):
            q = self._group_lat.percentile(self.hedge_quantile * 100.0)
            if q is None:
                return None
            self._hedge_thr = max(q, self.hedge_min_ms / 1e3)
            self._hedge_thr_at = c
        return self._hedge_thr

    def _hedge_target(self, slot: int) -> Optional[Replica]:
        """A healthy, ACTIVE replica other than the primary's — the
        least-loaded one.  None when fewer than 2 replicas are
        eligible: hedging no-ops rather than re-dispatching onto the
        same straggler (or a red/retired replica)."""
        rs = self._rs
        cands = [r for r in rs.replicas
                 if r.healthy and r.active and r.index != slot]
        if not cands:
            return None
        return min(cands, key=lambda r: self._slot_inflight[r.index])

    def _hedge_executor(self) -> ThreadPoolExecutor:
        if self._hedge_pool is None:
            # sized so pending loser fetches can never starve the next
            # group's primary+hedge pair of workers: every in-flight
            # slot (n_slots * slot_cap) could be holding a straggling
            # loser, plus the pair itself
            self._hedge_pool = ThreadPoolExecutor(
                max_workers=self._n_slots * self._slot_cap + 2,
                thread_name_prefix="zoo-serving-hedge")
        return self._hedge_pool

    def _swallow_loser(self, fut: Future):
        """Consume a losing fetch's outcome.  Its result is moot (the
        winner already served the group) and its error must not
        propagate — the hedge existed precisely because that replica
        was misbehaving."""
        try:
            fut.result()
        except BaseException as e:  # noqa: BLE001 — deliberate sink
            _slog.info("hedge_loser_error",
                       error=f"{type(e).__name__}: {e}")

    def _drain_losers(self, block: bool = False) -> bool:
        """Retire finished hedge losers and release their slot
        ownership.  ARENA-OWNERSHIP RULE: a losing dispatch's zero-copy
        ``device_put`` aliases the SAME staging buffer as the primary
        (the hedge re-dispatched the staged batch), so the primary
        slot's in-flight count — which is what guards that buffer
        against rewrite — stays held until the losing execute+fetch
        returns, exactly like the PR 5 retry-window rule.  ``block``
        (used when every slot is pinned and nothing else can free one)
        waits for whichever pending loser finishes FIRST — never the
        oldest specifically, which could wedge behind a dead fetch
        while a newer done loser sat ready to free a slot — bounded by
        ``_WEDGE_TIMEOUT_S``: past it the still-pending losers'
        replicas are marked unhealthy instead of stalling the
        dispatcher forever.  Returns whether any loser was retired."""
        retired = False
        remaining: List[Tuple[int, Future, Optional[int]]] = []
        for slot, fut, alt_idx in self._pending_losers:
            if fut.done():
                self._swallow_loser(fut)
                self._wedged_reported.discard(id(fut))
                if 0 <= slot < len(self._slot_inflight):
                    self._slot_inflight[slot] -= 1
                if alt_idx is not None:
                    # the pending loser was the hedge: its replica's
                    # own in-flight count releases with it
                    self._slot_inflight[alt_idx] -= 1
                retired = True
            else:
                remaining.append((slot, fut, alt_idx))
        self._pending_losers = remaining
        if block and not retired and remaining:
            done, _ = _futures_wait([f for _, f, _ in remaining],
                                    timeout=self._WEDGE_TIMEOUT_S,
                                    return_when=FIRST_COMPLETED)
            if done:
                return self._drain_losers()
            self._mark_wedged_losers()
        return retired

    def _mark_wedged_losers(self):
        """Every pending loser outlived the wedge budget: mark each
        one's replica unhealthy (one-way, once per loser) so
        scheduling, hedging, and the recovery probe treat the device
        as red.  The slot counts stay held — the wedged dispatch still
        aliases its staging buffer (arena-ownership rule), so only its
        fetch returning can release the buffer for rewrite."""
        if self._rs is None:
            return
        for slot, fut, alt_idx in self._pending_losers:
            if id(fut) in self._wedged_reported:
                continue
            self._wedged_reported.add(id(fut))
            idx = alt_idx if alt_idx is not None else slot
            if 0 <= idx < len(self._rs.replicas):
                self._rs.mark_unhealthy(
                    self._rs.replicas[idx],
                    RuntimeError(
                        f"hedge loser fetch wedged for more than "
                        f"{self._WEDGE_TIMEOUT_S:g}s"))

    def _resolve(self, item):
        """Fetch a dispatched group's device result and fan rows out.
        ``item`` is a ``_dispatch_group`` in-flight entry."""
        group, n, dev, slot, t0, batched, key = item
        if self.hedging:
            thr = self._hedge_threshold_s()
            if thr is not None:
                self._resolve_hedged(group, n, dev, slot, t0, batched,
                                     key, thr)
                return
            # unseeded window: a hedge cannot fire, so don't pay the
            # pool submit + cross-thread wakeup — fetch inline below
        try:
            out = self._fetch_slot(dev, n, slot)
            err = None
        except BaseException as e:
            out, err = None, e
        self._group_lat.add(time.perf_counter() - t0)
        self._retire(group, slot)
        self._fan_out(group, out, err)

    def _resolve_hedged(self, group: List[_Request], n: int, dev,
                        slot: int, t0: float, batched, key,
                        thr: float):
        """First-wins resolve: wait for the primary fetch until the
        group's in-flight age crosses ``thr`` (the quantile-derived
        hedge threshold); past it, re-dispatch the SAME staged batch to
        a second healthy replica and take whichever result lands first.
        Results are bit-exact either way (same serialized executable on
        every replica — the PR 5 pin), so the race is free of output
        tearing by construction.  The loser's slot accounting is
        deferred to :meth:`_drain_losers` (arena-ownership rule)."""
        pool = self._hedge_executor()
        fut_p = pool.submit(self._fetch_slot, dev, n, slot)
        # the latency window learns the PRIMARY's true latency, win or
        # lose — recording the group's first-wins latency would feed
        # the threshold its own output (hedged groups resolve at the
        # fast replica's speed, the quantile sinks toward it, and a
        # persistent straggler ends up hedged on nearly every dispatch
        # instead of only at the tail)
        fut_p.add_done_callback(
            lambda _f, _t0=t0: self._group_lat.add(
                time.perf_counter() - _t0))
        fut_h = None
        alt = None
        remaining = (t0 + thr) - time.perf_counter()
        done, _ = _futures_wait([fut_p], timeout=max(remaining, 0.0))
        if not done:
            alt = self._hedge_target(slot)
            if alt is None:
                # <2 eligible replicas: hedging must no-op (there
                # is nowhere independent to win on)
                self._hedges["skipped_no_replica"] += 1
            else:
                try:
                    dev2 = self._rs.dispatch(alt, batched, key=key)
                except RuntimeError as e:
                    # a failed hedge never fails the group — the
                    # primary is still in flight and authoritative
                    self._rs.mark_unhealthy(alt, e)
                    alt = None
                else:
                    self._hedges["fired"] += 1
                    # hedge work is real load: the schedulers
                    # (least-outstanding-work + _hedge_target) must
                    # see it in flight, and operators must see it in
                    # the per-replica dispatch counters
                    self._slot_inflight[alt.index] += 1
                    bucket = _rows(batched)
                    with self._cache._lock:
                        alt.dispatches += 1
                        alt.bucket_dispatches[bucket] = \
                            alt.bucket_dispatches.get(bucket, 0) + 1
                    fut_h = pool.submit(self._fetch_hedge, dev2, n,
                                        alt.index)
        winner, loser = fut_p, None
        if fut_h is not None:
            done, _ = _futures_wait([fut_p, fut_h],
                                    return_when=FIRST_COMPLETED)
            winner = fut_p if fut_p in done else fut_h
            loser = fut_h if winner is fut_p else fut_p
        try:
            out = winner.result()
            err = None
        except BaseException as e:
            if loser is not None:
                # the winner crashed first — the other dispatch may
                # still deliver the group.  Bounded wait: a WEDGED
                # loser (the very failure hedging routes around) must
                # not stall the dispatcher forever on .result()
                _futures_wait([loser], timeout=self._WEDGE_TIMEOUT_S)
                if loser.done():
                    try:
                        out, err = loser.result(), None
                    except BaseException as e2:
                        out, err = None, e2
                    # the other future actually delivered (or crashed
                    # last): IT is the winner for outcome attribution,
                    # and nothing is left in flight to track
                    winner, loser = loser, None
                else:
                    # both dispatches failed the group: the crash is
                    # the answer.  The wedged fetch stays the tracked
                    # loser (pending-loser path below), holding its
                    # slot so the aliased buffer is never rewritten —
                    # and its replica goes red NOW (the budget already
                    # elapsed; don't wait for a forced drain to notice)
                    out, err = None, e
                    idx = alt.index if loser is fut_h else slot
                    self._wedged_reported.add(id(loser))
                    self._rs.mark_unhealthy(
                        self._rs.replicas[idx],
                        RuntimeError(
                            f"hedge fetch wedged for more than "
                            f"{self._WEDGE_TIMEOUT_S:g}s"))
            else:
                out, err = None, e
        if fut_h is not None and err is None:
            # outcome recorded AFTER the result was actually delivered
            # — a hedge that completed first by CRASHING must not count
            # as (or trace as) a win the primary then served
            outcome = ("primary_won" if winner is fut_p
                       else "hedge_won")
            self._hedges[outcome] += 1
            for r in group:
                if r.span is not None:
                    r.span.event("hedge", outcome=outcome,
                                 primary_slot=slot,
                                 hedge_replica=alt.index)
        self._inflight_n -= len(group)
        alt_released = fut_h is None  # no hedge → nothing to release
        if loser is not None and not loser.done():
            # slot stays owned until the losing execute returns — its
            # zero-copy upload still aliases this slot's buffer
            pend_alt = None
            if loser is fut_h:
                pend_alt = alt.index  # _drain_losers releases it
                alt_released = True
            self._pending_losers.append((slot, loser, pend_alt))
        else:
            if loser is not None:
                self._swallow_loser(loser)
            if 0 <= slot < len(self._slot_inflight):
                self._slot_inflight[slot] -= 1
        if not alt_released:
            # the hedge future has fully resolved (it won, or was
            # consumed): its replica's in-flight count releases now
            self._slot_inflight[alt.index] -= 1
        self._done(len(group))
        self._fan_out(group, out, err)

    def _retire(self, group: List[_Request], slot: int):
        """Un-count a resolved group (live count, slot, outstanding).
        Runs BEFORE waking callers, so their resubmissions aren't
        double-counted against the next gather's early-dispatch
        check."""
        self._inflight_n -= len(group)
        if 0 <= slot < len(self._slot_inflight):
            self._slot_inflight[slot] -= 1
        self._done(len(group))

    def _fan_out(self, group: List[_Request], out, err):
        """Fan a fetched group's rows (or its error) onto each caller's
        future and release the device-concurrency slot."""
        try:
            if err is None:
                off = 0
                for r in group:
                    if r.span is not None:
                        r.span.phase_start("depad")
                    rows = _slice_rows(out, off, off + r.n)
                    if r.span is not None:
                        # close depad BEFORE waking the caller so the
                        # future-wake slack reads as span tail, not as
                        # an inflated depad
                        r.span.phase_end()
                    if not r.future.done():  # close() may have raced us
                        r.future.set_result(rows)
                    off += r.n
            else:
                for r in group:
                    if r.span is not None:
                        r.span.phase_end()
                    if not r.future.done():
                        r.future.set_exception(err)
        finally:
            if self._sem is not None:
                self._sem.release()

    def _loop(self):
        try:
            self._loop_inner()
        except BaseException as e:  # crash net: never strand a caller
            # mark closed BEFORE draining so a submit racing this drain
            # either sees closed (and raises) or enqueues before the
            # drain starts (and is flushed here).  acquire with a
            # timeout: a submitter blocked on a full queue holds
            # _submit_lock and would never release it once we're dead —
            # submit()'s own post-put aliveness check covers that case.
            got = self._submit_lock.acquire(timeout=1.0)
            self._closed = True
            self._crashed = True  # before the flush — see submit()
            if got:
                self._submit_lock.release()
            self._flush_queue(e)
            # dispatched-but-unresolved groups die with us too: fail
            # their callers and return their device-concurrency slots
            # (a leaked slot would wedge the solo fallback path)
            while self._inflight:
                group = self._inflight.popleft()[0]
                self._done(len(group))
                for r in group:
                    if not r.future.done():
                        r.future.set_exception(e)
                if self._sem is not None:
                    self._sem.release()
            raise
        finally:
            # the dispatcher owns the hedge pool; once it exits no
            # buffer is ever staged again, so in-flight loser fetches
            # may finish unobserved (wait=False keeps a wedged fetch
            # from hanging shutdown)
            if self._hedge_pool is not None:
                self._hedge_pool.shutdown(wait=False)

    def _loop_inner(self):
        # instance-held so the crash net can fail dispatched groups
        inflight = self._inflight
        shutdown = False
        while True:
            if self._pending_losers:
                # retire finished hedge losers first: each one done
                # releases a slot (arena-ownership rule)
                self._drain_losers()
            if self._rs is not None:
                # due unhealthy replicas get their recovery probe (one
                # int compare when everything is green)
                self._rs.maybe_reprobe()
            group: List[_Request] = []
            if not shutdown:
                if inflight and self._carry is None and self._q.empty():
                    # nothing to gather and dispatches in flight: every
                    # closed-loop caller is blocked on a future — fetch
                    # and fan the oldest out NOW so they can resubmit,
                    # instead of grace-waiting on a queue that cannot fill
                    self._resolve(inflight.popleft())
                # gathering overlaps the in-flight groups' device
                # compute.  Single-device: any in-flight group means no
                # urgency; device-parallel: urgency ends only once every
                # replica's slot is occupied.
                busy = (bool(inflight) if self._rs is None
                        else len(inflight) >= self._capacity())
                group, shutdown = self._gather(
                    block=not inflight, pipeline_busy=busy)
            elif self._carry is not None:
                # a mismatched rider was pulled before the shutdown
                # sentinel — it still must be served
                group, _ = self._gather(block=False)
            if group:
                # arena-reuse safety: never stage while every eligible
                # slot is at its in-flight cap — resolve FIFO (or wait
                # out a hedge loser) until one frees (also how an
                # unhealthy replica's stragglers get delivered before
                # traffic re-routes around it)
                while not self._has_free_capacity():
                    if inflight:
                        self._resolve(inflight.popleft())
                    elif self._pending_losers:
                        self._drain_losers(block=True)
                    else:
                        break  # counts only come from the two above
                disp = self._dispatch_group(group, inflight)
                if disp is not None:
                    inflight.append(disp)
            # fetch the oldest group when the pipeline is full, or when
            # there was nothing to gather (its callers are waiting and
            # no new work arrived to overlap with)
            if inflight and (not group
                             or len(inflight) >= self._capacity()):
                self._resolve(inflight.popleft())
            if shutdown and not inflight and self._carry is None:
                while self._pending_losers:
                    if not self._drain_losers(block=True):
                        # the wedge budget elapsed with zero progress:
                        # abandoning the wedged fetches beats hanging
                        # shutdown forever — no buffer is ever staged
                        # again after return, and the hedge pool shuts
                        # down wait=False
                        _slog.info("shutdown_abandons_wedged_losers",
                                   n=len(self._pending_losers))
                        break
                return
