"""Continuous batching for autoregressive decode (ORCA-style).

The serving stack batches fixed-shape one-shot requests; the dominant
LLM workload is token streaming, where requests join and leave the
batch at EVERY decode step.  Naive batch-of-requests decoding makes
every rider pay the longest sequence's latency: a batch finishes when
its slowest member does, and short requests idle in finished rows.

``DecodeEngine`` is the iteration-level alternative:

* **Bucketed prefill.**  Each admitted prompt is right-padded to a
  small geometric ladder of prompt lengths and run through ONE batched
  causal forward (the training-shaped compute), writing its per-layer
  K/V into a free slot of the decode state — one ``admit`` executable
  per (prompt bucket, capacity), compiled once.
* **A single persistent slot-array decode executable.**  The decode
  state is a fixed-capacity slot array — per-layer K/V slabs of shape
  ``(capacity, max_len, heads * d_head)`` (``ops.attention.kv_*`` owns
  the layout) plus per-slot current token and write position — stepped
  by ONE jitted function whose shapes
  never depend on occupancy.  Attention masks derive from per-slot
  positions, so occupied and free slots coexist in the same dispatch:
  admission and eviction are state writes, never recompiles.  Exactly
  one compile per (bucket, capacity) across a whole serving run — the
  zoolint sanitizer's compile counter pins this at every occupancy.
* **Per-step admission / eviction.**  A dispatcher thread loops:
  drain finished slots (EOS or max tokens), admit queued requests into
  free slots, step once, fan the step's tokens out to per-request
  :class:`TokenStream` futures.  A short request admitted next to a
  long one leaves as soon as ITS tokens are done; the freed slot is
  re-filled on the very next iteration.

Decode math: :mod:`analytics_zoo_tpu.models.generation`'s
``_prefill`` / ``_decode_step`` — the same per-row-position (ragged)
formulation ``TransformerLM.generate`` compiles into its scan, so a
slot stepped one token at a time is pinned token-identical to the
scan path (tests/test_serving_decode.py).

Decode engine v2 (ISSUE 14) extends the slot array with three
independently-gated stages, all preserving the
one-compile-per-(bucket, capacity, plan), sanitize-clean, and
bit-exact-replay invariants:

* **Per-slot sampling.**  temperature/top-k/top-p ride the slot array
  as DYNAMIC per-slot values (static configs would recompile the step
  per sampling mix), and each slot draws from its own
  ``fold_in(PRNGKey(request seed), absolute token index)`` key — the
  trainer's absolute-step fold_in discipline applied per stream.
  Because a slot's logits depend only on its own cache (masked
  attention) and its key only on (seed, index), streams are
  independent, bit-replayable, and occupancy-invariant; a
  ``temperature == 0`` slot selects the bare argmax, bit-identical to
  the pre-sampling greedy engine.
* **Prefix-KV pool.**  Prompts are split at the largest prompt-bucket
  boundary <= their length; the prefix block's per-layer K/V (and its
  last hidden state) is content-hash cached in a small on-device LRU
  pool, so a shared-system-prompt admission is a
  ``dynamic_update_slice`` memcpy plus a short tail prefill instead
  of a full-prompt recompute.  A pool hit copies bits a previous
  prefix-prefill produced and a miss recomputes them with the same
  plan, so hit and miss streams are bit-identical by construction;
  eviction (LRU beyond the pool bound) just recomputes — never a
  wrong prefix (the key is the prefix CONTENT hash).
* **Speculative decoding.**  A small draft model proposes
  ``spec_tokens - 1`` tokens per slot (a scan inside ONE dispatch);
  the target then takes one EXACT single-query step (the same traced
  body as the non-speculative plan — the bit-exact fallback token)
  and verifies the proposals with a k-query windowed forward
  (training-shaped matmuls).  Accepted proposals emit up to
  ``spec_tokens`` tokens per dispatch; a rejection falls back to the
  exact step's token, bit-identical to the non-speculative stream BY
  CONSTRUCTION (full rejection degrades to exactly the plain
  engine's computation).  Accepted window tokens are selected from
  the verify pass's own logits, which match the single-query step to
  ~1 ulp — identical selections on this backend (test_serving_decode
  pins spec ≡ plain empirically); a near-tie flip under a backend
  whose window kernels round differently is the only theoretical
  divergence channel.  Sampled verification draws each window
  position from the same per-slot fold_in key the non-speculative
  path would use.

Data movement is explicit (``device_put`` in, ``device_get`` out) so
the whole loop runs clean under ``zoolint.sanitize()`` transfer
guards; the decode state itself never leaves the device — the per-step
host traffic is one (capacity,) token fetch (plus the (spec_tokens,
capacity) token matrix and acceptance vector per speculative window).
"""

from __future__ import annotations

import collections
import hashlib
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...models.generation import (_decode_step, _decode_window,
                                  _embed_token, _head_logits, _prefill,
                                  _prefill_ext, _sample, family_of)
from ...observability import profile as _profile
from ...ops.attention import kv_insert, kv_slab_spec, kv_slab_zeros
from ...observability.log import get_logger as _get_logger
from .serving import _execstore, bucket_ladder

_slog = _get_logger("zoo.serving.decode")


class DecodeEngineClosedError(RuntimeError):
    """The decode dispatcher is gone — this request was (or would be)
    never served."""


class TokenStream:
    """Per-request streaming handle: tokens arrive one decode step at a
    time; iterate for streaming, or :meth:`result` for the full
    continuation.

    Thread contract: the engine's dispatcher is the only writer; any
    number of consumer threads may iterate / ``result()``.  The
    producer fast path is ONE list append (GIL-atomic) — the condition
    variable is only touched once a consumer actually iterates
    (``_live``), so blocking callers cost the dispatcher nothing per
    token.  This is hot-loop-relevant: at thousands of tokens/s a
    locked queue put per token was ~15% of the engine's wall.
    """

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._tokens: List[int] = []
        self._error: Optional[BaseException] = None
        self._finished = threading.Event()
        self._live = False  # a consumer is iterating — notify pushes
        self._cond = threading.Condition()

    # ---- producer side (dispatcher thread only) ----
    def _push(self, tok: int):
        self._tokens.append(tok)
        if self._live:
            with self._cond:
                self._cond.notify_all()

    def _finish(self, error: Optional[BaseException] = None):
        self._error = error
        self._finished.set()
        if self._live:
            with self._cond:
                self._cond.notify_all()

    # ---- consumer side ----
    @property
    def done(self) -> bool:
        return self._finished.is_set()

    def __iter__(self):
        self._live = True
        i = 0
        while True:
            # catch up lock-free (append-only list, single writer)
            while i < len(self._tokens):
                yield int(self._tokens[i])
                i += 1
            if self._finished.is_set():
                if i < len(self._tokens):
                    continue  # tokens landed after the done flag
                if self._error is not None:
                    raise self._error
                return
            with self._cond:
                if i >= len(self._tokens) \
                        and not self._finished.is_set():
                    # bounded wait: _live may have been observed False
                    # by a push racing this first iteration
                    self._cond.wait(0.05)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request finishes; returns the generated
        continuation as a 1-D int32 array (EOS included when hit)."""
        if not self._finished.wait(timeout=timeout):
            raise TimeoutError(
                f"decode request {self.request_id} still streaming "
                f"after {timeout}s")
        if self._error is not None:
            raise self._error
        return np.asarray(self._tokens, np.int32)


class _DecodeRequest:
    # ``span`` is the explicit cross-thread trace handoff (same
    # convention as the coalescer's _Request): the dispatcher records
    # prefill/decode_step phases on it directly.
    # ``scheduled`` counts tokens covered by dispatched (possibly not
    # yet processed) steps — the pipelined loop plans fused windows
    # from it, since ``produced`` lags by the in-flight dispatch.
    __slots__ = ("prompt", "length", "bucket", "max_new", "eos_id",
                 "stream", "span", "produced", "scheduled", "slot",
                 "temperature", "top_k", "top_p", "seed", "t_submit")

    def __init__(self, prompt: np.ndarray, length: int, bucket: int,
                 max_new: int, eos_id: Optional[int], stream: TokenStream,
                 span=None, temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0):
        self.prompt = prompt
        self.length = length
        self.bucket = bucket
        self.max_new = max_new
        self.eos_id = eos_id
        self.stream = stream
        self.span = span
        self.produced = 0
        self.scheduled = 0
        self.slot = -1
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed
        # built in submit(): the queue wait counts from here
        self.t_submit = time.perf_counter()


def _pick(logits, seed, index, temperature, top_k, top_p):
    """One row's token: :func:`_sample` with the key
    ``fold_in(PRNGKey(seed), index)``, ``index`` the token's absolute
    index in its stream."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    return _sample(logits, key, temperature, top_k,
                   top_p).astype(jnp.int32)


def _pick_tokens(logits, seed, index, temperature, top_k, top_p,
                 pick_sorted):
    """The next token of every row of ``logits`` ((rows, V) with
    per-row knobs, or one row (V,) with scalars), branching ON THE
    DEVICE on the scalar ``pick_sorted``: false, every row is greedy
    and the tokens are :func:`_sample`'s static-greedy plan, a bare
    ``argmax(logits)`` and nothing else (no sort, no ``exp``, no
    cumulative sum, no key, no uniform); true, each row goes through
    :func:`_pick`, whose in-graph select keeps a ``temperature == 0``
    row's token the same bare argmax.  So a row's token does not depend
    on the branch as long as ``pick_sorted`` is true whenever a row that
    matters samples.

    The ``lax.cond`` stands OUTSIDE the ``vmap`` over rows: inside it a
    ``cond`` on a per-row predicate lowers to a select and both branches
    run."""
    pick = _pick if logits.ndim == 1 else jax.vmap(_pick)
    return lax.cond(
        pick_sorted,
        lambda: pick(logits, seed, index, temperature, top_k, top_p),
        lambda: _sample(logits, None, 0.0).astype(jnp.int32))


# The pick of the next token is a jitted function of its own INSIDE the
# plans, named ``zoo_sample``: a device profile then shows its
# operations (either branch's) under ``jit(zoo_sample)``.  A
# ``jax.named_scope`` would not do here: scopes are metadata, jax leaves
# metadata out of the persistent compilation cache's key, and a plan
# whose instructions did not change is answered from the cache with the
# metadata (or none) of whoever compiled it first.  The function's
# symbol is part of the program, so it cannot go stale; XLA inlines the
# call.
_pick_tokens = jax.jit(_profile.named(_profile.SCOPE_SAMPLE, _pick_tokens))


#: what the dispatcher thread can be doing: host work (``admit``,
#: ``dispatch``, ``fanout``), waiting on the device (``admit_fetch``,
#: ``fetch``) or waiting for work (``idle``)
LOOP_PHASES = ("admit", "admit_fetch", "dispatch", "fetch", "fanout",
               "idle")


class _LoopPhase:
    """One phase of the dispatcher's loop, as a context manager: the
    host span ``zoo/decode/<phase>`` on the profiler's clock AND the
    always-on counter ``loop_<phase>_s``, opened and closed together so
    the two cannot drift apart.  The counter holds SELF time: what a
    nested phase (``admit_fetch`` inside ``admit``) took is its own, so
    the phases sum to the loop's wall.  Entering returns the annotation
    (``set_metadata`` adds the counts known only at the end)."""

    __slots__ = ("engine", "key", "ann", "t0", "outer")

    def __init__(self, engine, phase, stats):
        self.engine = engine
        self.key = "loop_" + phase + "_s"
        self.ann = _profile.annotate("decode/" + phase, **stats)

    def __enter__(self):
        self.t0 = time.perf_counter()
        eng = self.engine
        self.outer, eng._nested_s = eng._nested_s, 0.0
        self.ann.__enter__()
        return self.ann

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        eng = self.engine
        took = time.perf_counter() - self.t0
        eng._counters[self.key] += took - eng._nested_s
        eng._nested_s = self.outer + took


class _PrefixEntry:
    """One pooled prefix: the per-layer (k, v) device blocks of a
    prefix-prefill plus its last position's hidden state (the logits
    source for a prompt that IS exactly the prefix)."""

    __slots__ = ("kv", "h_last", "p_len")

    def __init__(self, kv, h_last, p_len: int):
        self.kv = kv
        self.h_last = h_last
        self.p_len = p_len


class _PrefixPool:
    """Dispatcher-owned LRU of prefix-KV blocks, keyed on the prefix
    CONTENT hash (sha256 over (prefix length, token bytes)) — a
    collision-free key means an entry can only ever serve the exact
    prefix it was computed from.  Eviction (beyond ``size`` entries)
    drops the device arrays; a later admission of that prefix simply
    recomputes (counted, never wrong).  Single-threaded by protocol
    (only the dispatcher touches it), like the slot bookkeeping."""

    def __init__(self, size: int):
        self.size = int(size)
        self.entries: "collections.OrderedDict[str, _PrefixEntry]" = \
            collections.OrderedDict()

    @staticmethod
    def key(prefix_ids: np.ndarray) -> str:
        ids = np.ascontiguousarray(prefix_ids, np.int32)
        h = hashlib.sha256()
        h.update(repr(ids.shape).encode())
        h.update(ids.tobytes())
        return h.hexdigest()

    def get(self, key: str) -> Optional[_PrefixEntry]:
        ent = self.entries.get(key)
        if ent is not None:
            self.entries.move_to_end(key)
        return ent

    def put(self, key: str, entry: _PrefixEntry) -> int:
        """Insert (most-recent) and trim to ``size``; returns how many
        entries the bound evicted (their device arrays are freed with
        the last reference — memory pressure resolves to a later
        recompute, never a wrong block)."""
        self.entries[key] = entry
        self.entries.move_to_end(key)
        evicted = 0
        while len(self.entries) > self.size:
            self.entries.popitem(last=False)
            evicted += 1
        return evicted


_SHUTDOWN = object()


class DecodeEngine:
    """KV-cache-slotted continuous-batching decode engine (module doc).

    Args:
        params: the model's param tree (``trainer.state.params``) —
            placed on ``device`` once at construction.
        hyper: the model's hyper dict (``n_layers``/``n_heads``/
            ``d_model``/``max_len``/``moe_every``...).  Its ``family``
            key (``models.generation.family_of``) says whose prefill
            and decode step the plans trace and how the cache is laid
            out: none for a ``TransformerLM``; ``"cohere2_moe"``
            (``CommandAPlusLM``) brings slabs of two lengths in the
            weights' dtype and a step that hands back the chosen
            experts, and refuses ``prefix_pool``, drafts and ``mesh``.
        capacity: decode slots — the fixed batch width of the
            persistent step executable.
        max_len: per-slot cache length (default the model's
            ``max_len``); every request needs
            ``prompt_len + max_new_tokens <= max_len``.
        prompt_buckets: the prompt-length ladder (default: a geometric
            ladder up to ``max_len - 1``).  One admit executable
            compiles per bucket actually used.
        eos_id: default end-of-sequence token id (per-request
            override via ``submit``); ``None`` decodes to
            ``max_new_tokens`` always.
        max_queue: bound on submitted-but-unadmitted requests.
        step_fuse: fused-window size K — when no admission or
            eviction could land inside the next K steps, they
            dispatch as ONE compiled scan, amortizing per-dispatch
            overhead without giving up iteration-level scheduling
            (1 disables fusion; see ``_choose_fuse``).
        prefix_pool: > 0 keeps that many prefix-KV blocks in an
            on-device LRU pool — admissions whose prompt shares a
            bucket-aligned prefix with a pooled block skip the
            prefix's prefill compute (module docstring §Prefix-KV
            pool).  0 (default) disables: admission is the monolithic
            single-plan prefill, bit-identical to the v1 engine.
        draft_params / draft_hyper: a small draft model (same vocab)
            enables speculative decoding — up to ``spec_tokens``
            tokens per dispatch (module docstring §Speculative).
            Mutually exclusive with ``prefix_pool`` for now.
        spec_tokens: tokens per speculative window (1 exact + up to
            ``spec_tokens - 1`` certified draft proposals).
        device: jax device for the decode state (default: the first
            local device).
    """

    def __init__(self, params, hyper: Dict[str, Any], capacity: int = 8,
                 max_len: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None, max_queue: int = 256,
                 step_fuse: int = 4, prefix_pool: int = 0,
                 draft_params=None, draft_hyper: Optional[Dict] = None,
                 spec_tokens: int = 4, device=None,
                 mesh: Optional[dict] = None,
                 store_tag: Optional[str] = None):
        # per-model accounting tag for execstore entries (stat
        # --by-model); metadata only, never part of the fingerprint
        self._store_tag = store_tag
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if (draft_params is None) != (draft_hyper is None):
            raise ValueError(
                "speculative decoding needs BOTH draft_params and "
                "draft_hyper (or neither)")
        if int(prefix_pool) < 0:
            raise ValueError(
                f"prefix_pool must be >= 0, got {prefix_pool}")
        if draft_params is not None and prefix_pool:
            raise ValueError(
                "draft (speculative) and prefix_pool are mutually "
                "exclusive in this engine version — the pooled prefix "
                "blocks would need a draft-cache twin")
        if draft_params is not None and spec_tokens < 2:
            raise ValueError(
                f"spec_tokens must be >= 2 (1 exact + >=1 proposed), "
                f"got {spec_tokens}")
        if draft_hyper is not None \
                and int(draft_hyper["vocab_size"]) != int(
                    hyper["vocab_size"]):
            raise ValueError(
                "draft and target must share a vocabulary "
                f"({draft_hyper['vocab_size']} vs "
                f"{hyper['vocab_size']})")
        # the model's family: whose embed / prefill / decode step / head
        # the plans trace, and how its cache is laid out
        self._fam = family_of(hyper)
        for what, given in (("prefix_pool", bool(prefix_pool)),
                            ("draft", draft_params is not None),
                            ("mesh", mesh is not None)):
            if given and what in self._fam.refuses:
                raise ValueError(
                    f"the decode engine does not support {what} for the "
                    f"{self._fam.name} family yet: its prefix blocks, "
                    "draft window and slot sharding are written for "
                    "TransformerLM's block and cache")
        self.capacity = int(capacity)
        self.step_fuse = max(1, int(step_fuse))
        self._hyper = dict(hyper)
        self.max_len = int(max_len or hyper["max_len"])
        if self.max_len > int(hyper["max_len"]):
            raise ValueError(
                f"max_len ({self.max_len}) exceeds the model's "
                f"positional table ({hyper['max_len']})")
        if prompt_buckets:
            self.prompt_buckets: Tuple[int, ...] = tuple(
                sorted(set(int(b) for b in prompt_buckets)))
        else:
            top = max(1, self.max_len - 1)
            self.prompt_buckets = bucket_ladder(
                top, growth=2.0, min_batch=min(8, top))
        if self.prompt_buckets[-1] >= self.max_len:
            raise ValueError(
                f"largest prompt bucket ({self.prompt_buckets[-1]}) "
                f"must leave room to decode (max_len {self.max_len})")
        self.eos_id = eos_id
        self._device = device or jax.local_devices()[0]
        # ---- mesh-sharded slot state (big-LM continuous batching):
        # the CAPACITY axis shards over the group's mesh, so each
        # device steps its own contiguous slice of the slots while the
        # per-slot decode math — attention over that slot's own cache
        # line, sampling from that slot's own logits — stays entirely
        # on one device.  No cross-slot term exists in the step, so
        # the partitioned program is a pure per-device map: bit-exact
        # vs the unsharded engine BY CONSTRUCTION (test_serving_shardgroup
        # pins it).  Params replicate across the group (the weights
        # ride the forward unsharded; rule-sharded decode weights
        # would put collectives inside the step — a later engine
        # version's trade).
        self._mesh_spec = None
        self._mesh = None
        self._mesh_cfg = None
        if mesh is not None:
            from ...serving.shardgroup import (carve_groups,
                                               mesh_spec_canonical,
                                               normalize_mesh_spec)
            if device is not None:
                raise ValueError(
                    "pass mesh= or device=, not both — the mesh spec "
                    "carves the engine's device group itself")
            if prefix_pool or draft_params is not None:
                raise ValueError(
                    "mesh-sharded decode does not support prefix_pool "
                    "or speculative drafts in this engine version — "
                    "their pool/draft caches would need the same slot "
                    "sharding twin")
            spec = normalize_mesh_spec(mesh)
            gdevs, gmesh = carve_groups(jax.local_devices(), spec)[0]
            if self.capacity % len(gdevs):
                raise ValueError(
                    f"capacity ({self.capacity}) must divide evenly "
                    f"over the mesh's {len(gdevs)} devices")
            self._mesh_spec = spec
            self._mesh_cfg = mesh_spec_canonical(spec)
            self._mesh = gmesh
            self._device = gdevs[0]
        # device_put target for replicated inputs (params, admission
        # scalars, prompts): the bare device unsharded, the group-
        # replicated NamedSharding under a mesh
        self._rep = (self._device if self._mesh is None
                     else NamedSharding(self._mesh, P()))
        self._params = jax.device_put(params, self._rep)
        self._n_layers = int(hyper["n_layers"])
        self.spec_tokens = int(spec_tokens)
        self._draft_hyper = (None if draft_hyper is None
                             else dict(draft_hyper))
        if self._draft_hyper is not None:
            if int(self._draft_hyper["max_len"]) < self.max_len:
                raise ValueError(
                    f"draft positional table "
                    f"({self._draft_hyper['max_len']}) is shorter than "
                    f"the engine's max_len ({self.max_len})")
            self._draft_params = jax.device_put(draft_params,
                                                self._rep)
        else:
            self._draft_params = None
        # every plan takes the weights as its LAST runtime argument
        # (bound by ``_plan``), never as closed-over constants: a
        # closure bakes a private copy of the weights into each
        # executable — at GPT-2-small widths 1.4 GB and ~55 s of
        # compile per plan on a v5e, seven copies in HBM, and nothing
        # the compilation cache could hold (chip_smoke.py, PR 21)
        self._weights = (self._params, self._draft_params)

        # ---- device state: the persistent slot array.  jnp.zeros
        # builds ON the device (a fill, not a transfer); tok/pos for
        # free slots are don't-cares — their writes land in cache
        # positions a future occupant always overwrites before
        # attending (write-then-attend, see _build_step_fn).
        with jax.default_device(self._device):
            self._slab_dtype = self._fam.slab_dtype(self._params)
            caches = [kv_slab_zeros(*dims, dtype=self._slab_dtype)
                      for dims in self._layer_slab_dims()]
            dcaches = []
            if self._draft_hyper is not None:
                dh = self._draft_hyper
                dcaches = [kv_slab_zeros(*self._slab_dims(dh))
                           for _ in range(int(dh["n_layers"]))]
            tok = jnp.zeros((self.capacity,), jnp.int32)
            pos = jnp.zeros((self.capacity,), jnp.int32)
            # per-slot sampling state: request seed, absolute token
            # index (the fold_in counter), and the dynamic sampling
            # knobs (temperature == 0 -> argmax, top_k == 0 / top_p
            # == 1 -> disabled) — slot writes at admission, never a
            # recompile
            samp = (jnp.zeros((self.capacity,), jnp.int32),
                    jnp.zeros((self.capacity,), jnp.int32),
                    jnp.zeros((self.capacity,), jnp.float32),
                    jnp.zeros((self.capacity,), jnp.int32),
                    jnp.ones((self.capacity,), jnp.float32))
        # COMMIT the initial state (device_put of an on-device array is
        # a no-op copy-wise but flips it committed): the live loop's
        # state is always committed — its producers take committed
        # device_put inputs — and the jit cache keys on committedness,
        # so an uncommitted first call would cost every admit plan a
        # SECOND compile the first time it sees steady-state inputs,
        # breaking the one-compile-per-(bucket, capacity) invariant
        self._caches = jax.device_put(caches, self._slot_sharding(3))
        self._dcaches = jax.device_put(dcaches, self._slot_sharding(3))
        # positions of a slot's slab that one step reads at a time (the
        # decode kernel's block, or the whole slab where it does not
        # run): what ``kv_positions_read`` rounds a length up to
        # by kind of slab: (rows, read block, layers counted).  Where
        # every layer's slab is alike and full-length (TransformerLM) it
        # is one block and nothing a ring could skip: ``_kv_block``, and
        # a dispatch then counts as it did before there were kinds
        self._kv_kinds = self._fam.kv_kinds(
            hyper, self.capacity, self.max_len, self._slab_dtype)
        self._kv_block = None
        if len(self._kv_kinds) == 1:
            [(rows, block, layers)] = self._kv_kinds
            if rows >= self.max_len and layers == 1:
                self._kv_block = block
        self._tok = jax.device_put(tok, self._slot_sharding(1))
        self._pos = jax.device_put(pos, self._slot_sharding(1))
        self._samp = jax.device_put(samp, self._slot_sharding(1))
        # the two values of the step plans' ``pick_sorted`` input
        # (_select), put once: a dispatch passes one of them
        self._pick_flags = tuple(jax.device_put(np.bool_(b), self._rep)
                                 for b in (False, True))

        # one AOT-compiled single-step plan plus a halving ladder of
        # fused window plans (step_fuse, step_fuse/2, ... 2) per
        # engine; one admit plan per prompt bucket — built in
        # warmup() (or lazily at the first unwarmed dispatch), cached,
        # and NEVER rebuilt inside the dispatcher loop (zoolint
        # ZL101), so a serving run compiles exactly once per
        # (bucket, capacity) plan no matter how occupancy moves.
        # Plans are explicit lower()+compile() rather than lazy jit:
        # the AOT split is what lets the persistent executable store
        # answer the compile with a disk load (zero-compile warmup in
        # a process whose store is warm).
        self._fuse_sizes: Tuple[int, ...] = tuple(
            sorted({k for k in (self.step_fuse, self.step_fuse // 2)
                    if k > 1}, reverse=True))
        self._step_fn: Any = None
        self._stepk_fns: Dict[int, Any] = {}
        self._admit_fns: Dict[int, Any] = {}
        self._spec_fn: Any = None
        self._pfxfill_fns: Dict[int, Any] = {}
        self._pfxadmit_fns: Dict[Tuple[int, int], Any] = {}
        self._prefix_pool = (_PrefixPool(prefix_pool) if prefix_pool
                             else None)
        # persistent executable store: resolved once; None keeps every
        # store branch inert.  The plans are weight-agnostic (the
        # weights are a runtime argument), but the weights digest rides
        # every plan fingerprint anyway — a redeploy with new weights
        # must never be answered by an entry recorded against old ones
        # — and the draft digest and the sampling-static config ride
        # alongside.
        self._store = _execstore().current()
        self._wdigest = (_execstore().params_digest(self._params)
                         if self._store is not None else None)
        self._ddigest = (_execstore().params_digest(self._draft_params)
                         if self._store is not None
                         and self._draft_params is not None else None)
        self._samp_cfg = ("samp-v2",
                          self.spec_tokens
                          if self._draft_hyper is not None else 0,
                          bool(self._prefix_pool))

        # host-side slot bookkeeping (dispatcher-thread-owned)
        self._slots: List[Optional[_DecodeRequest]] = \
            [None] * self.capacity
        self._free: collections.deque = collections.deque(
            range(self.capacity))

        # counters (dispatcher-owned ints; reads copy — GIL-atomic
        # enough for a metrics scrape, same convention as the
        # coalescer's hedge counters)
        self._counters = {"tokens": 0, "steps": 0, "steps_sorted": 0,
                          "prefills": 0,
                          "admitted": 0, "evicted": 0,
                          "fused_dispatches": 0, "sampled_tokens": 0,
                          "prefix_hits": 0, "prefix_misses": 0,
                          "prefix_evictions": 0, "spec_windows": 0,
                          "spec_proposed": 0, "spec_accepted": 0,
                          # positions of the target model's slabs that
                          # the dispatched steps had live, and that they
                          # read (_kv_positions)
                          "kv_positions_live": 0, "kv_positions_read": 0,
                          # positions a full-length slab would have held
                          # for those steps and a windowed layer's ring
                          # did not (_kv_positions)
                          "kv_positions_window_skipped": 0,
                          # a routed family's live slots (_count_routed):
                          # (token, expert) pairs routed, those that fell
                          # on experts held here, and held experts with at
                          # least one token, summed over layers and steps
                          "moe_assignments": 0, "moe_assignments_held": 0,
                          "moe_experts_hit": 0,
                          # submit -> admission, summed (beside
                          # ``admitted``), and the dispatcher thread's
                          # time by what it was doing (_LoopPhase)
                          "queue_wait_s": 0.0,
                          **{f"loop_{p}_s": 0.0 for p in LOOP_PHASES}}
        self._nested_s = 0.0    # _LoopPhase: time of the phases inside
        self._bucket_stats: Dict[str, Dict[int, Any]] = {
            "hits": {}, "misses": {}, "compile_time_s": {}}
        self._occupancy = 0

        self._q: "queue.Queue" = queue.Queue(maxsize=int(max_queue))
        self._next_id = 0
        self._id_lock = threading.Lock()
        self._closed = False
        self._submit_lock = threading.Lock()
        self._crashed = False
        # the dispatcher starts LAZILY (first submit), not here:
        # warmup() runs on the caller thread and rebinds the shared
        # donated state, so a dispatcher stepping concurrently would
        # race it into use-after-donate — deferring the start makes
        # construct -> warmup -> serve safe by construction.  The
        # condition guards only the handshake FLAGS (the decode state
        # itself is single-owner by protocol: warmup's thread before
        # start, the dispatcher after)
        self._started = False
        self._warming = False
        self._start_cond = threading.Condition()
        self._thread = threading.Thread(
            target=self._decode_loop, name="zoo-decode-dispatch",
            daemon=True)

    def _slab_dims(self, hyper):
        """(capacity, max_len, heads, d_head) of a model's slabs."""
        n_heads = int(hyper["n_heads"])
        return (self.capacity, self.max_len, n_heads,
                int(hyper["d_model"]) // n_heads)

    def _layer_slab_dims(self):
        """The target model's slab dims, layer by layer, as its family
        lays them out (``_slab_dims`` is the draft's, a TransformerLM)."""
        return self._fam.slab_dims(self._hyper, self.capacity, self.max_len)

    # ---- placement shardings --------------------------------------------
    def _rep_sharding(self):
        """Spec sharding for group-replicated inputs (scalars,
        prompts, prefix blocks): the engine's single device unsharded,
        the whole group under a mesh."""
        if self._mesh is not None:
            return NamedSharding(self._mesh, P())
        return jax.sharding.SingleDeviceSharding(self._device)

    def _slot_sharding(self, rank: int):
        """Sharding for slot-state arrays (leading axis == capacity):
        under a mesh the slot axis shards over EVERY mesh axis (the
        sub-mesh exists to split the slots), remaining dims
        replicated."""
        if self._mesh is None:
            return jax.sharding.SingleDeviceSharding(self._device)
        axes = tuple(self._mesh.axis_names)
        return NamedSharding(self._mesh,
                             P(axes, *([None] * (rank - 1))))

    def _ensure_started(self):
        with self._start_cond:
            while self._warming:  # let an in-flight warmup finish
                self._start_cond.wait()
            if not self._started:
                self._started = True
                self._thread.start()

    # ---- compiled plans -------------------------------------------------
    def _select(self, logits, samp, pick_sorted, offset: int = 0):
        """Per-slot token selection over (capacity, V) logits: each
        slot draws with ``fold_in(PRNGKey(seed), step + offset)`` —
        the absolute-token-index RNG that makes streams independent,
        replayable, and occupancy-invariant — through the SAME
        :func:`_sample` implementation the compiled-scan path uses.
        ``temperature == 0`` slots select the bare argmax
        (bit-identical to the v1 greedy step).

        A dispatch whose live slots are all greedy does no more than
        that argmax: ``pick_sorted`` (a device scalar the dispatcher
        hands every step plan, :meth:`_any_sampled`) selects, in an XLA
        ``conditional``, between the argmax alone and the per-slot
        :func:`_sample` (sort of the vocabulary, two cumulative sums,
        one uniform), which runs only when a live slot samples.
        Sampling stays a STATE write plus this one runtime scalar: one
        step plan at every sampling mix, never a recompile.  The
        predicate is the dispatcher's, not ``any(temp > 0)`` on the
        device, because eviction is host-side only: a slot freed by a
        sampled request keeps its temperature until the next admission
        overwrites it, and must not hold the sorted branch on."""
        seed, stepc, temp, topk, topp = samp
        return _pick_tokens(logits, seed, stepc + offset, temp, topk,
                            topp, pick_sorted)

    def _step_core(self, caches, tok, pos, samp, pick_sorted, weights):
        """ONE slot-array decode step over ALL ``capacity`` slots —
        the body the step, fused, and speculative plans all trace, so
        every plan's per-token numerics are identical by construction.
        Free slots compute garbage that is never read: their (clamped)
        position's cache line is rewritten by the step itself before
        it is attended, and admission overwrites ``[0, bucket)``
        wholesale.  Shapes depend on (capacity, max_len) only — never
        occupancy."""
        params, hyper, max_len = weights[0], self._hyper, self.max_len
        fam = self._fam
        posc = jnp.minimum(pos, max_len - 1)
        emb = fam.embed(params, tok, posc)
        # a routed family's step also hands back the experts it chose
        logits, caches, *routed = fam.decode_step(
            params, hyper, caches, emb, posc, mesh=self._mesh)
        nxt = self._select(logits, samp, pick_sorted)
        seed, stepc, temp, topk, topp = samp
        return (caches, nxt, jnp.minimum(pos + 1, max_len),
                (seed, stepc + 1, temp, topk, topp), *routed)

    def _step_body(self, caches, tok, pos, samp, pick_sorted, weights):
        return self._step_core(caches, tok, pos, samp, pick_sorted,
                               weights)

    def _samp_specs(self):
        s0 = self._slot_sharding(1)
        ispec = jax.ShapeDtypeStruct((self.capacity,), jnp.int32,
                                     sharding=s0)
        fspec = jax.ShapeDtypeStruct((self.capacity,), jnp.float32,
                                     sharding=s0)
        return (ispec, ispec, fspec, ispec, fspec)

    def _scalar_specs(self):
        """(seed, temperature, top_k, top_p) admission scalars."""
        s0 = self._rep_sharding()
        i0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=s0)
        f0 = jax.ShapeDtypeStruct((), jnp.float32, sharding=s0)
        return (i0, f0, i0, f0)

    def _draft_specs(self):
        """Draft slot-cache ShapeDtypeStructs ([] without a draft —
        the plans carry the empty pytree so every engine flavor shares
        one plan signature)."""
        if self._draft_hyper is None:
            return []
        dh = self._draft_hyper
        pair = kv_slab_spec(*self._slab_dims(dh),
                            sharding=self._slot_sharding(3))
        return [pair for _ in range(int(dh["n_layers"]))]

    def _state_specs(self):
        """ShapeDtypeStructs matching the persistent decode state —
        the AOT lowering inputs for the step/admit plans (committed to
        the engine's device — or slot-sharded over its mesh — exactly
        like the live state)."""
        caches = [kv_slab_spec(*dims, sharding=self._slot_sharding(3),
                               dtype=self._slab_dtype)
                  for dims in self._layer_slab_dims()]
        ispec = jax.ShapeDtypeStruct((self.capacity,), jnp.int32,
                                     sharding=self._slot_sharding(1))
        return caches, ispec, ispec, self._samp_specs()

    def _step_specs(self):
        """The step plans' inputs: the state, then ``pick_sorted``
        (:meth:`_select`)."""
        return self._state_specs() + (jax.ShapeDtypeStruct(
            (), jnp.bool_, sharding=self._rep_sharding()),)

    def _plan(self, name: str, jitted, arg_specs):
        """AOT-build one decode plan: lower, consult the persistent
        executable store (read-through), compile + persist on a miss
        (write-behind).  ``jitted`` takes the plan's state arguments
        then the weights bundle; the returned callable is the
        jax-level ``Compiled`` with the engine's weights bound as that
        last argument — plan calls in the decode loop execute a fixed
        binary, never trace.  The fingerprint covers the lowered HLO
        text (graph + every shape), the weights digest (the compiled
        code is weight-agnostic, but a redeploy with new weights must
        never be answered by an entry recorded against old ones),
        the (capacity, max_len) tuple, and the runtime environment; a
        corrupt or unloadable entry counts ``invalid`` and falls back
        to the compile — never to a wrong executable."""
        weights = self._weights
        lowered = jitted.lower(*arg_specs, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            weights))

        def bound(compiled):
            return lambda *args: compiled(*args, weights)

        store = self._store
        fp = None
        if store is not None:
            es = _execstore()
            fp = store.fingerprint(
                "decode-plan", name, es.hlo_digest(lowered),
                self._wdigest, self._ddigest, self._samp_cfg,
                self._mesh_cfg,
                (self.capacity, self.max_len),
                device=self._device)
            ent = store.lookup(fp)
            if ent is not None:
                try:
                    return bound(es.rehydrate(
                        ent.payload,
                        (self._device,) if self._mesh is None
                        else self._mesh.devices.flat))
                except Exception as e:  # noqa: BLE001 — fall back to
                    # the compile below on any rehydration failure
                    store.note_invalid(fp, e)
        compiled = lowered.compile()
        if store is not None:
            try:
                meta = {"kind": "decode-plan", "name": name,
                        "capacity": self.capacity,
                        "max_len": self.max_len}
                if self._mesh_spec is not None:
                    meta["mesh"] = {
                        "axes": dict(self._mesh_spec["axes"]),
                        "strategy": self._mesh_spec["strategy"]}
                if self._store_tag is not None:
                    meta["model"] = self._store_tag
                store.put(fp, _execstore().serialize_compiled(compiled),
                          meta=meta)
            except Exception as e:  # noqa: BLE001 — persisting is
                # best-effort: serving proceeds on the fresh compile
                _slog.error("decode_plan_store_failed", plan=name,
                            error=f"{type(e).__name__}: {e}")
        return bound(compiled)

    def _build_step_plan(self):
        """The persistent single-step plan: (caches, tok, pos, samp,
        pick_sorted) -> (caches', tok', pos', samp')."""
        # the caches are DONATED: without donation every step copies
        # the whole (capacity, max_len, heads * d_head) slab per
        # layer just to update one position — the in-place update the
        # scan path gets for free from its loop carry.  Measured ~40%
        # off the per-step wall on CPU; the loop always rebinds the
        # returned caches, so the invalidated buffers are never
        # touched again.  tok/pos/samp are NOT donated: the pipelined
        # loop still holds the previous step's token vector for its
        # deferred fetch, and donating would invalidate that buffer
        # mid-flight (they are (capacity,) scalars — the copy is
        # free).
        # jitted under a name of its own: from the bound method the
        # module would be ``jit__step_body``
        def step(caches, tok, pos, samp, pick_sorted, weights):
            return self._step_body(caches, tok, pos, samp, pick_sorted,
                                   weights)

        return self._plan(
            "step1", jax.jit(_profile.named(_profile.PROGRAM_STEP, step),
                             donate_argnums=(0,)),
            self._step_specs())

    def _build_stepk_plan(self, k: int):
        """One fused window plan: ``k`` consecutive decode steps as
        ONE dispatch (a compiled ``lax.scan`` over
        :meth:`_step_body`), returning the (k, capacity) token matrix.
        Per-dispatch overhead — the python call, XLA's per-execution
        fixed cost, the host fetch — amortizes across k tokens, which
        is most of the single-step path's deficit against
        ``TransformerLM.generate``'s monolithic scan.  The dispatcher
        picks the window so scheduling NEVER changes inside it (see
        ``_choose_fuse``), so batching stays iteration-level exactly
        when iteration-level matters."""

        def stepk(caches, tok, pos, samp, pick_sorted, weights):
            def body(carry, _):
                c, t, p, sm = carry
                c, t, p, sm, *routed = self._step_body(
                    c, t, p, sm, pick_sorted, weights)
                return (c, t, p, sm), (t, *routed) if routed else t

            (caches, tok, pos, samp), toks = lax.scan(
                body, (caches, tok, pos, samp), None, length=k)
            # toks: (k, capacity); a routed family's: that and the
            # chosen experts (k, capacity, layers, top_k)
            return caches, tok, pos, samp, toks

        return self._plan(
            f"step{k}", jax.jit(_profile.named(_profile.PROGRAM_STEPK,
                                               stepk),
                                donate_argnums=(0,)),
            self._step_specs())

    def _build_spec_plan(self):
        """The speculative window plan — draft proposal scan, ONE
        exact target step, windowed verify, and in-graph acceptance,
        all one dispatch:

            (caches, dcaches, tok, pos, samp, pick_sorted) ->
            (caches', dcaches', tok', pos', samp',
             T (spec_tokens, capacity), accepted (capacity,))

        ``T[0]`` is the EXACT step's token (the same traced
        :meth:`_step_core` the non-speculative plan runs, so a full
        rejection falls back bit-identically); ``T[1:]`` are the
        window-verified target tokens for the draft's proposals, each
        selected with its absolute-index fold_in key.  ``accepted``
        in [1, spec_tokens] counts tokens valid to emit: proposal j is
        accepted while it equals the previous target token, the
        standard speculative prefix rule.  The draft scan runs
        ``spec_tokens - 1`` proposals plus one extra step so the LAST
        accepted token's draft K/V is written too (an all-accepted
        window leaves no cache gap).  Rolled-back state (tok', pos')
        re-derives from ``accepted``, so rejected positions are stale
        cache lines a later step overwrites before attending — the
        same write-then-attend invariant free slots rely on."""
        k = self.spec_tokens
        hyper, max_len = self._hyper, self.max_len
        dhyper = self._draft_hyper

        def spec(caches, dcaches, tok, pos, samp, pick_sorted, weights):
            params, dparams = weights

            def dbody(carry, _):
                dc, t, p = carry
                posc = jnp.minimum(p, max_len - 1)
                emb = _embed_token(dparams, t, posc)
                lg, dc = _decode_step(dparams, dhyper, dc, emb, posc)
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (dc, nxt, jnp.minimum(p + 1, max_len)), nxt
            # k iterations: k-1 proposals + the cache-gap filler (its
            # proposal is never verified)
            (dcaches, _, _), dprops = lax.scan(
                dbody, (dcaches, tok, pos), None, length=k)
            dprops = dprops[:k - 1]  # (k-1, capacity)
            # the exact fallback token — bit-identical to the
            # non-speculative step plan by shared trace
            caches, t0, *_ = self._step_core(caches, tok, pos, samp,
                                             pick_sorted, weights)
            # windowed verify of the proposals at pos+1 .. pos+k-1
            embs = [_embed_token(params, dprops[j],
                                 jnp.minimum(pos + 1 + j, max_len - 1))
                    for j in range(k - 1)]
            wlogits, caches = _decode_window(
                params, hyper, caches, jnp.stack(embs, axis=1),
                pos + 1)
            wtoks = [self._select(wlogits[:, j], samp, pick_sorted,
                                  offset=1 + j)
                     for j in range(k - 1)]
            T = jnp.concatenate([t0[None], jnp.stack(wtoks, axis=0)],
                                axis=0)  # (k, capacity)
            match = (dprops == T[:k - 1]).astype(jnp.int32)
            acc = 1 + jnp.cumprod(match, axis=0).sum(axis=0)
            newtok = jnp.take_along_axis(T, (acc - 1)[None, :],
                                         axis=0)[0]
            newpos = jnp.minimum(pos + acc, max_len)
            seed, stepc, temp, topk, topp = samp
            samp = (seed, stepc + acc, temp, topk, topp)
            return caches, dcaches, newtok, newpos, samp, T, acc

        caches, ispec, _, samp, flag = self._step_specs()
        return self._plan(
            f"spec{k}", jax.jit(_profile.named(_profile.PROGRAM_SPEC,
                                               spec),
                                donate_argnums=(0, 1)),
            (caches, self._draft_specs(), ispec, ispec, samp, flag))

    def _ensure_step_plans(self):
        """Build (or store-load) the decode-loop plans — the
        speculative window plan for a drafted engine, else the step
        plan + the fused-window ladder — called from warmup(), or
        lazily at the first dispatch of an unwarmed engine (one
        ``is None`` check per step thereafter)."""
        if self._step_fn is not None:
            return
        if self._draft_hyper is not None:
            self._spec_fn = self._build_spec_plan()
            # the built flag: a drafted engine's only step plan IS the
            # speculative window plan
            self._step_fn = self._spec_fn
            return
        for k in self._fuse_sizes:
            self._stepk_fns[k] = self._build_stepk_plan(k)
        self._step_fn = self._build_step_plan()  # set LAST: the flag

    def _slot_write(self, arrays, slot, tok0, length, seed0, temp0,
                    topk0, topp0):
        """Shared admission epilogue: write one slot's (tok, pos,
        sampling) state — step index starts at 1, the first token's
        index-0 key having just been consumed."""
        tok, pos, (seed, stepc, temp, topk, topp) = arrays
        tok = lax.dynamic_update_slice(tok, tok0[None], (slot,))
        pos = lax.dynamic_update_slice(
            pos, length[None].astype(pos.dtype), (slot,))
        seed = lax.dynamic_update_slice(seed, seed0[None], (slot,))
        stepc = lax.dynamic_update_slice(
            stepc, jnp.ones((1,), stepc.dtype), (slot,))
        temp = lax.dynamic_update_slice(temp, temp0[None], (slot,))
        topk = lax.dynamic_update_slice(topk, topk0[None], (slot,))
        topp = lax.dynamic_update_slice(topp, topp0[None], (slot,))
        return tok, pos, (seed, stepc, temp, topk, topp)

    def _sample_first(self, logits0, seed0, temp0, topk0, topp0):
        """First-token selection at absolute index 0 (the same
        :func:`_sample` + fold_in discipline every later index
        uses, behind the same branch: a greedy request's admission
        sorts nothing)."""
        return _pick_tokens(logits0, seed0, jnp.zeros((), jnp.int32),
                            temp0, topk0, topp0, temp0 > 0.0)

    def _build_admit_fn(self, s_b: int):
        """One prompt bucket's monolithic admission plan: batched
        prefill of the (1, s_b) padded prompt, first-token sampling,
        and the K/V insert into slot ``slot`` of the decode state —
        all one executable, so admitting is a single dispatch.  A
        drafted engine's plan also prefills the DRAFT's caches for the
        prompt (the draft must enter the window in lockstep)."""
        hyper, dhyper, fam = self._hyper, self._draft_hyper, self._fam

        def admit(caches, dcaches, tok, pos, samp, prompt, length,
                  slot, seed0, temp0, topk0, topp0, weights):
            params, dparams = weights
            x, pc = fam.prefill(params, hyper, prompt, s_b)
            last = lax.dynamic_index_in_dim(x[0], length - 1,
                                            keepdims=False)
            logits0 = fam.head(params, hyper, last[None, :])[0]
            tok0 = self._sample_first(logits0, seed0, temp0, topk0,
                                      topp0)
            new_caches = fam.insert(hyper, caches, pc, slot, length)
            new_dcaches = dcaches
            if dhyper is not None:
                _, dpc = _prefill(dparams, dhyper, prompt, s_b)
                new_dcaches = [
                    (kv_insert(ck, pk, slot), kv_insert(cv, pv, slot))
                    for (ck, cv), (pk, pv) in zip(dcaches, dpc)]
            tok, pos, samp = self._slot_write(
                (tok, pos, samp), slot, tok0, length, seed0, temp0,
                topk0, topp0)
            return new_caches, new_dcaches, tok, pos, samp, tok0

        # caches (target AND draft) donated for the same
        # in-place-update reason as the step plan; tok/pos/samp
        # excluded for the same pipeline-aliasing reason (an admission
        # can run while the previous step's token vector still awaits
        # its deferred fetch)
        return jax.jit(_profile.named(_profile.PROGRAM_ADMIT, admit),
                       donate_argnums=(0, 1))

    def _admit_fn_for(self, s_b: int):
        fn = self._admit_fns.get(s_b)
        if fn is None:
            caches, tok, pos, samp = self._state_specs()
            s0 = self._rep_sharding()
            pspec = jax.ShapeDtypeStruct((1, s_b), jnp.int32,
                                         sharding=s0)
            sspec = jax.ShapeDtypeStruct((), jnp.int32, sharding=s0)
            fn = self._admit_fns[s_b] = self._plan(
                f"admit{s_b}", self._build_admit_fn(s_b),
                (caches, self._draft_specs(), tok, pos, samp, pspec,
                 sspec, sspec) + self._scalar_specs())
        return fn

    # ---- prefix-KV pool plans -------------------------------------------
    def _prefix_bucket_for(self, n: int) -> int:
        """Largest prompt bucket <= n — the bucket-aligned prefix
        split point for a pool-eligible prompt."""
        p = self.prompt_buckets[0]
        for b in self.prompt_buckets:
            if b <= n:
                p = b
        return p

    def _build_pfxfill_fn(self, p_b: int):
        """The prefix-prefill plan: (1, p_b) prefix ids -> (per-layer
        (k, v) blocks, slab rows (1, p_b, heads * d_head), last hidden
        (d,)).
        Runs ONCE per distinct prefix content (the pool miss); its
        outputs are exactly what a pool hit memcpys, which is why hit
        and miss admissions are bit-identical."""
        hyper = self._hyper

        def fill(prefix, weights):
            x, pc = _prefill(weights[0], hyper, prefix, p_b)
            return pc, x[0, p_b - 1]

        return jax.jit(_profile.named(_profile.PROGRAM_FILL, fill))

    def _pfxfill_fn_for(self, p_b: int):
        fn = self._pfxfill_fns.get(p_b)
        if fn is None:
            s0 = self._rep_sharding()
            pspec = jax.ShapeDtypeStruct((1, p_b), jnp.int32,
                                         sharding=s0)
            fn = self._pfxfill_fns[p_b] = self._plan(
                f"pfxfill{p_b}", self._build_pfxfill_fn(p_b),
                (pspec,))
        return fn

    def _pfx_block_specs(self, p_b: int):
        s0 = self._rep_sharding()
        _, _, n_heads, d_head = self._slab_dims(self._hyper)
        pair = kv_slab_spec(1, p_b, n_heads, d_head, sharding=s0)
        hspec = jax.ShapeDtypeStruct((n_heads * d_head,), jnp.float32,
                                     sharding=s0)
        return [pair for _ in range(self._n_layers)], hspec

    def _build_pfxadmit_fn(self, p_b: int, s_b: int):
        """The pooled admission plan for (prefix bucket, prompt
        bucket): ``dynamic_update_slice`` the pooled prefix blocks
        into the slot (the memcpy), prefill only the TAIL (s_b - p_b
        padded positions, attending prefix + tail causally), sample
        the first token, and write the slot state — one executable per
        (p_b, s_b) pair actually used.  ``length == p_b`` (no tail)
        admissions reuse the pooled last-hidden for the first token's
        logits; the p_b == s_b variant compiles without any tail
        compute at all."""
        hyper = self._hyper
        tail_pad = s_b - p_b

        def padmit(caches, tok, pos, samp, pkv, h_pfx, tail, length,
                   slot, seed0, temp0, topk0, topp0, weights):
            params = weights[0]
            if tail_pad:
                xt, tc = _prefill_ext(params, hyper, tail, pkv, p_b)
            new_caches = []
            for i, (ck, cv) in enumerate(caches):
                pk, pv = pkv[i]
                ck, cv = kv_insert(ck, pk, slot), kv_insert(cv, pv, slot)
                if tail_pad:
                    tk, tv = tc[i]
                    ck = kv_insert(ck, tk, slot, p_b)
                    cv = kv_insert(cv, tv, slot, p_b)
                new_caches.append((ck, cv))
            if tail_pad:
                ti = jnp.clip(length - p_b - 1, 0, tail_pad - 1)
                lh = lax.dynamic_index_in_dim(xt[0], ti,
                                              keepdims=False)
                lh = jnp.where(length > p_b, lh, h_pfx)
            else:
                lh = h_pfx
            logits0 = _head_logits(params, lh[None, :])[0]
            tok0 = self._sample_first(logits0, seed0, temp0, topk0,
                                      topp0)
            tok, pos, samp = self._slot_write(
                (tok, pos, samp), slot, tok0, length, seed0, temp0,
                topk0, topp0)
            return new_caches, tok, pos, samp, tok0

        return jax.jit(_profile.named(_profile.PROGRAM_PADMIT, padmit),
                       donate_argnums=(0,))

    def _pfxadmit_fn_for(self, p_b: int, s_b: int):
        fn = self._pfxadmit_fns.get((p_b, s_b))
        if fn is None:
            caches, tok, pos, samp = self._state_specs()
            s0 = self._rep_sharding()
            blocks, hspec = self._pfx_block_specs(p_b)
            tspec = jax.ShapeDtypeStruct((1, s_b - p_b), jnp.int32,
                                         sharding=s0)
            sspec = jax.ShapeDtypeStruct((), jnp.int32, sharding=s0)
            fn = self._pfxadmit_fns[(p_b, s_b)] = self._plan(
                f"pfxadmit{p_b}_{s_b}",
                self._build_pfxadmit_fn(p_b, s_b),
                (caches, tok, pos, samp, blocks, hspec, tspec, sspec,
                 sspec) + self._scalar_specs())
        return fn

    def warmup(self) -> float:
        """AOT-compile every prompt bucket's admit plan plus the step
        plan (deploy pays the compiles, live streams never do).
        Returns wall seconds.  The warmed admissions land in slot 0 of
        the REAL state — harmless: the host free-list is untouched, so
        slot 0 is re-admitted (and its cache overwritten) before any
        live request reads it.  Must run BEFORE the first submit: the
        warms rebind the shared donated state on THIS thread, so a
        live dispatcher would race them into use-after-donate —
        _start_lock makes a concurrent first submit wait here rather
        than start one."""
        t0 = time.perf_counter()
        with self._start_cond:
            if self._started:
                raise RuntimeError(
                    "DecodeEngine.warmup() must run before the first "
                    "submit — the dispatcher owns the decode state "
                    "once it is serving")
            self._warming = True
        try:
            zero = jax.device_put(np.int32(0), self._rep)
            one = jax.device_put(np.int32(1), self._rep)
            fzero = jax.device_put(np.float32(0.0), self._rep)
            fone = jax.device_put(np.float32(1.0), self._rep)
            for b in self.prompt_buckets:
                prompt = jax.device_put(np.zeros((1, b), np.int32),
                                        self._rep)
                # tb covers the plan BUILD (the AOT compile — or the
                # store load that replaces it) plus one verifying
                # execution; compile_time_s is honest either way
                tb = time.perf_counter()
                fn = self._admit_fn_for(b)
                (self._caches, self._dcaches, self._tok, self._pos,
                 self._samp, tok0) = fn(
                    self._caches, self._dcaches, self._tok, self._pos,
                    self._samp, prompt, one, zero, zero, fzero, zero,
                    fone)
                jax.device_get(tok0)
                secs = time.perf_counter() - tb
                self._bucket_stats["compile_time_s"][b] = \
                    self._bucket_stats["compile_time_s"].get(b, 0.0) \
                    + secs
                self._bucket_stats["misses"][b] = \
                    self._bucket_stats["misses"].get(b, 0) + 1
                _slog.info("decode_warmup_bucket", bucket=b,
                           compile_ms=round(secs * 1e3, 3))
            if self._prefix_pool is not None:
                # every (prefix bucket, prompt bucket) pair a
                # pool-eligible prompt can land on: (b_i, b_i) for
                # exact-bucket prompts, (b_i, b_i+1) for in-between —
                # warmed here so the live loop never compiles one
                ladder = self.prompt_buckets
                for i, p_b in enumerate(ladder):
                    pfx = jax.device_put(np.zeros((1, p_b), np.int32),
                                         self._device)
                    pkv, h_last = self._pfxfill_fn_for(p_b)(pfx)
                    jax.device_get(h_last)
                    pairs = [(p_b, p_b)]
                    if i + 1 < len(ladder):
                        pairs.append((p_b, ladder[i + 1]))
                    plen = jax.device_put(np.int32(p_b), self._device)
                    for pb, sb in pairs:
                        tail = jax.device_put(
                            np.zeros((1, sb - pb), np.int32),
                            self._device)
                        fn = self._pfxadmit_fn_for(pb, sb)
                        (self._caches, self._tok, self._pos,
                         self._samp, tok0) = fn(
                            self._caches, self._tok, self._pos,
                            self._samp, pkv, h_last, tail, plen, zero,
                            zero, fzero, zero, fone)
                        jax.device_get(tok0)
            self._ensure_step_plans()
            greedy = self._pick_flags[False]
            if self._draft_hyper is not None:
                (self._caches, self._dcaches, self._tok, self._pos,
                 self._samp, toks, acc) = self._spec_fn(
                    self._caches, self._dcaches, self._tok, self._pos,
                    self._samp, greedy)
                jax.device_get(acc)
            else:
                self._run_step(self._step_fn, greedy)
                jax.device_get(self._tok)
                for fn in self._stepk_fns.values():
                    jax.device_get(self._run_step(fn, greedy)[0])
        finally:
            with self._start_cond:
                self._warming = False
                self._start_cond.notify_all()
        return time.perf_counter() - t0

    # ---- submission -----------------------------------------------------
    @property
    def closed(self) -> bool:
        return (self._closed or self._crashed
                or (self._started and not self._thread.is_alive()))

    def bucket_for(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt of {n} tokens exceeds the largest prompt bucket "
            f"({self.prompt_buckets[-1]})")

    @staticmethod
    def validate_sampling(temperature=0.0, top_k=None, top_p=None,
                          seed=0):
        """Sampling-parameter validation (raises ValueError) — shared
        by every envelope above the engine (the web sample's 400s, the
        fleet router, ``generate_ex``) so a bad request is rejected
        identically everywhere.  Returns the normalized
        (temperature, top_k, top_p, seed)."""
        t = float(temperature)
        if not np.isfinite(t) or t < 0.0:
            raise ValueError(
                f"temperature must be a finite value >= 0, got "
                f"{temperature!r}")
        if top_k is not None:
            top_k = int(top_k)
            if top_k < 1:
                raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None:
            top_p = float(top_p)
            if not (0.0 < top_p <= 1.0):
                raise ValueError(
                    f"top_p must lie in (0, 1], got {top_p}")
        seed = int(seed)
        if not (0 <= seed < 2 ** 31):
            raise ValueError(
                f"seed must lie in [0, 2**31), got {seed}")
        return t, top_k, top_p, seed

    def _validate(self, prompt_ids, max_new_tokens, temperature=0.0,
                  top_k=None, top_p=None, seed=0):
        """Shared request validation — raises ValueError, mutates
        nothing: (1-D prompt, length, bucket, max_new, sampling
        tuple).  ``generate`` pre-validates EVERY row through this
        before its first submit, so a bad late row cannot orphan
        earlier rows mid-decode."""
        prompt = np.asarray(prompt_ids)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(
                f"prompt_ids must be a non-empty 1-D id sequence, got "
                f"shape {prompt.shape}")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new}")
        L = int(prompt.shape[0])
        if L + max_new > self.max_len:
            raise ValueError(
                f"prompt ({L}) + max_new_tokens ({max_new}) exceeds "
                f"max_len ({self.max_len})")
        samp = self.validate_sampling(temperature, top_k, top_p, seed)
        return prompt, L, self.bucket_for(L), max_new, samp

    def submit(self, prompt_ids, max_new_tokens: int,
               eos_id: Optional[int] = None, span=None,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: int = 0) -> TokenStream:
        """Queue one prompt for continuous-batching decode; returns its
        :class:`TokenStream` immediately.  ``prompt_ids``: 1-D int ids
        (a (1, L) row is accepted too).  ``eos_id`` overrides the
        engine default; decoding stops at EOS (included in the stream)
        or after ``max_new_tokens``, whichever is first.
        ``temperature`` > 0 samples (optionally top-k/top-p truncated)
        from the per-request ``(seed, token index)`` fold_in stream —
        resubmitting the same (prompt, sampling params, seed) replays
        the same tokens regardless of engine occupancy."""
        prompt, L, bucket, max_new, samp = self._validate(
            prompt_ids, max_new_tokens, temperature, top_k, top_p,
            seed)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = prompt
        with self._id_lock:
            self._next_id += 1
            rid = self._next_id
        stream = TokenStream(rid)
        if span is not None:
            # opened on the caller's thread: covers queue time until
            # the dispatcher starts this request's prefill
            span.phase_start("decode_wait")
        req = _DecodeRequest(padded, L, bucket, max_new,
                             self.eos_id if eos_id is None else eos_id,
                             stream, span, temperature=samp[0],
                             top_k=samp[1], top_p=samp[2],
                             seed=samp[3])
        with self._submit_lock:
            if self.closed:
                raise DecodeEngineClosedError(
                    "DecodeEngine is closed — no dispatcher is "
                    "serving this queue")
            self._q.put(req)
            # waits out an in-flight warmup — the dispatcher only
            # begins once the warms are done
            self._ensure_started()
        if self._crashed or not self._thread.is_alive():
            # the dispatcher died between the closed check and the
            # enqueue — flush anything stranded (same crash-net race
            # the coalescer's submit covers)
            self._flush_queue(DecodeEngineClosedError(
                "DecodeEngine dispatcher died"))
        return stream

    def generate(self, prompts, max_new_tokens, eos_id=None,
                 timeout: Optional[float] = None, span=None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed=0) -> List[np.ndarray]:
        """Blocking convenience over :meth:`submit`: decode a batch of
        prompts (a (B, L) array, or a list of 1-D ragged rows) and
        return each row's generated continuation (1-D int32).
        ``max_new_tokens`` and ``seed`` may be per-row (a sequence) or
        shared; ``temperature``/``top_k``/``top_p`` are shared.
        ``span`` rides the request when there is exactly one row (a
        span is single-owner; batch rows would interleave phases)."""
        rows = ([np.asarray(prompts[i]) for i in range(len(prompts))]
                if isinstance(prompts, (list, tuple))
                else [r for r in np.asarray(prompts)])
        if np.ndim(max_new_tokens) == 0:
            max_news = [int(max_new_tokens)] * len(rows)
        else:
            max_news = [int(m) for m in max_new_tokens]
            if len(max_news) != len(rows):
                raise ValueError(
                    f"max_new_tokens has {len(max_news)} entries for "
                    f"{len(rows)} prompts")
        if np.ndim(seed) == 0:
            seeds = [int(seed)] * len(rows)
        else:
            seeds = [int(s) for s in seed]
            if len(seeds) != len(rows):
                raise ValueError(
                    f"seed has {len(seeds)} entries for "
                    f"{len(rows)} prompts")
        # all-or-nothing: validate EVERY row before the first submit,
        # so a bad late row can't leave earlier rows decoding into
        # abandoned streams (burning slots the caller gave up on)
        for r, m, s in zip(rows, max_news, seeds):
            self._validate(r, m, temperature, top_k, top_p, s)
        streams = [self.submit(r, m, eos_id=eos_id,
                               span=span if len(rows) == 1 else None,
                               temperature=temperature, top_k=top_k,
                               top_p=top_p, seed=s)
                   for (r, m, s) in zip(rows, max_news, seeds)]
        return [s.result(timeout=timeout) for s in streams]

    # ---- stats ----------------------------------------------------------
    def _phase(self, phase: str, **stats) -> _LoopPhase:
        """The dispatcher thread enters ``phase`` (one of
        ``LOOP_PHASES``): span and counter together."""
        return _LoopPhase(self, phase, stats)

    def stats(self) -> Dict[str, Any]:
        """Point-in-time decode counters (re-exported per model by
        ``InferenceModel.serving_stats`` and the Prometheus bridge)."""
        out = dict(self._counters)
        out.update(capacity=self.capacity,
                   slots_active=self._occupancy,
                   queued=self._q.qsize(),
                   prompt_buckets=self.prompt_buckets,
                   prefill_hits=dict(self._bucket_stats["hits"]),
                   prefill_misses=dict(self._bucket_stats["misses"]),
                   prefill_compile_time_s=dict(
                       self._bucket_stats["compile_time_s"]))
        pool = self._prefix_pool
        out["prefix_pool_size"] = pool.size if pool is not None else 0
        out["prefix_pool_entries"] = (len(pool.entries)
                                      if pool is not None else 0)
        out["spec_enabled"] = self._draft_hyper is not None
        if self._mesh_spec is not None:
            out["mesh_axes"] = dict(self._mesh_spec["axes"])
            out["mesh_devices"] = int(np.prod(
                list(self._mesh_spec["axes"].values())))
        proposed = out.get("spec_proposed", 0)
        out["spec_acceptance"] = (
            round(out.get("spec_accepted", 0) / proposed, 4)
            if proposed else None)
        return out

    # ---- dispatcher -----------------------------------------------------
    def _flush_queue(self, exc: BaseException):
        try:
            while True:
                r = self._q.get_nowait()
                if r is not _SHUTDOWN:
                    if r.span is not None:
                        r.span.phase_end()
                    r.stream._finish(exc)
        except queue.Empty:
            pass

    def close(self, timeout: float = 5.0):
        """Stop the dispatcher: active slots finish their streams
        first (graceful drain), queued-but-unadmitted requests are
        admitted and served ahead of the shutdown sentinel; anything
        racing the shutdown fails with DecodeEngineClosedError."""
        with self._submit_lock:
            already = self._closed
            self._closed = True
            if not already and self._thread.is_alive():
                self._q.put(_SHUTDOWN)
        if self._thread.is_alive():
            self._thread.join(timeout=timeout)
        if not self._thread.is_alive():
            self._flush_queue(DecodeEngineClosedError(
                "DecodeEngine closed"))

    def _samp_scalars(self, req: _DecodeRequest):
        """The request's sampling scalars as committed device values —
        explicit device_put like every other host->device hop in the
        loop (a bare python float into a jit is an implicit transfer
        of its own)."""
        return (jax.device_put(np.int32(req.seed), self._rep),
                jax.device_put(np.float32(req.temperature),
                               self._rep),
                jax.device_put(np.int32(req.top_k or 0), self._rep),
                jax.device_put(np.float32(1.0 if req.top_p is None
                                          else req.top_p),
                               self._rep))

    def _admit_monolithic(self, req: _DecodeRequest, slot: int) -> int:
        """The single-plan admission: one prefill+insert dispatch for
        the whole padded prompt (the v1 path — every engine without a
        prefix pool, and pool-ineligible short prompts)."""
        fresh = req.bucket not in self._admit_fns
        stat = ("misses" if (fresh
                             and req.bucket
                             not in self._bucket_stats["misses"])
                else "hits")
        self._bucket_stats[stat][req.bucket] = \
            self._bucket_stats[stat].get(req.bucket, 0) + 1
        # the timer starts BEFORE the plan build: on an unwarmed
        # engine the AOT compile (or store load) happens inside
        # _admit_fn_for, and compile_time_s must cover it
        t0 = time.perf_counter()
        fn = self._admit_fn_for(req.bucket)
        # every host->device hop is explicit (device_put), so the loop
        # stays clean under zoolint.sanitize() transfer guards
        prompt_dev = jax.device_put(req.prompt, self._rep)
        length_dev = jax.device_put(np.int32(req.length), self._rep)
        slot_dev = jax.device_put(np.int32(slot), self._rep)
        scalars = self._samp_scalars(req)
        _profile.note_transfer("h2d")
        (self._caches, self._dcaches, self._tok, self._pos,
         self._samp, tok0) = fn(
            self._caches, self._dcaches, self._tok, self._pos,
            self._samp, prompt_dev, length_dev, slot_dev, *scalars)
        with self._phase("admit_fetch"):
            tok0 = int(jax.device_get(tok0))
        _profile.note_transfer("d2h")
        if fresh:
            self._bucket_stats["compile_time_s"][req.bucket] = \
                self._bucket_stats["compile_time_s"].get(
                    req.bucket, 0.0) + (time.perf_counter() - t0)
        return tok0

    def _prefix_lookup(self, key: str) -> Optional[_PrefixEntry]:
        """Prefix-pool read — hot: once per pool-eligible admission;
        a miss is the signal to recompute (and re-pool) the block."""
        ent = self._prefix_pool.get(key)
        if ent is None:
            self._counters["prefix_misses"] += 1
        else:
            self._counters["prefix_hits"] += 1
        return ent

    def _admit_prefix(self, req: _DecodeRequest, slot: int) -> int:
        """Pool-eligible admission: split the prompt at its largest
        bucket boundary, serve the prefix block from the pool (or
        recompute + pool it), and run the (prefix, bucket) pair's
        memcpy+tail plan.  Hit or miss, the tail plan consumes
        bit-identical prefix blocks, so the streams cannot differ."""
        p_b = self._prefix_bucket_for(req.length)
        s_b = req.bucket
        # same fresh-compile accounting as the monolithic path: an
        # unwarmed engine's inline pfxfill/pfxadmit builds count as a
        # bucket MISS with their compile time recorded, never as a hit
        fresh = ((p_b, s_b) not in self._pfxadmit_fns
                 or p_b not in self._pfxfill_fns)
        stat = ("misses" if (fresh
                             and s_b
                             not in self._bucket_stats["misses"])
                else "hits")
        self._bucket_stats[stat][s_b] = \
            self._bucket_stats[stat].get(s_b, 0) + 1
        t0 = time.perf_counter()
        key = _PrefixPool.key(req.prompt[0, :p_b])
        ent = self._prefix_lookup(key)
        if ent is None:
            pfx_dev = jax.device_put(
                np.ascontiguousarray(req.prompt[:, :p_b]),
                self._device)
            _profile.note_transfer("h2d")
            pkv, h_last = self._pfxfill_fn_for(p_b)(pfx_dev)
            ent = _PrefixEntry(pkv, h_last, p_b)
            self._counters["prefix_evictions"] += \
                self._prefix_pool.put(key, ent)
        fn = self._pfxadmit_fn_for(p_b, s_b)
        tail = np.zeros((1, s_b - p_b), np.int32)
        tail[0, :req.length - p_b] = req.prompt[0, p_b:req.length]
        tail_dev = jax.device_put(tail, self._device)
        length_dev = jax.device_put(np.int32(req.length), self._device)
        slot_dev = jax.device_put(np.int32(slot), self._device)
        scalars = self._samp_scalars(req)
        _profile.note_transfer("h2d")
        (self._caches, self._tok, self._pos, self._samp, tok0) = fn(
            self._caches, self._tok, self._pos, self._samp, ent.kv,
            ent.h_last, tail_dev, length_dev, slot_dev, *scalars)
        with self._phase("admit_fetch"):
            tok0 = int(jax.device_get(tok0))
        _profile.note_transfer("d2h")
        if fresh:
            self._bucket_stats["compile_time_s"][s_b] = \
                self._bucket_stats["compile_time_s"].get(s_b, 0.0) \
                + (time.perf_counter() - t0)
        return tok0

    def _admit_slot(self, req: _DecodeRequest, slot: int):
        """Admit one queued request into ``slot``: run its admission
        plan (monolithic, or prefix-pooled when eligible), stream the
        first token, and activate the slot — or finish the request
        immediately when the first token already ends it (EOS /
        max_new == 1)."""
        waited = time.perf_counter() - req.t_submit
        self._counters["queue_wait_s"] += waited
        # the phase is how long an admission holds the loop: the host
        # preparation and the plan's dispatch, with the blocking fetch
        # of the first token as its child (admit_fetch)
        with self._phase("admit", bucket=req.bucket, length=req.length,
                         slot=slot, queue_wait_us=int(waited * 1e6)):
            self._admit(req, slot)

    def _admit(self, req: _DecodeRequest, slot: int):
        span = req.span
        if span is not None:
            span.phase_start("prefill")
        if (self._prefix_pool is not None
                and req.length >= self.prompt_buckets[0]):
            tok0 = self._admit_prefix(req, slot)
        else:
            tok0 = self._admit_monolithic(req, slot)
        self._counters["prefills"] += 1
        self._counters["admitted"] += 1
        self._counters["tokens"] += 1
        if req.temperature > 0.0:
            self._counters["sampled_tokens"] += 1
        req.produced = 1
        req.scheduled = 1
        req.stream._push(tok0)
        if span is not None:
            span.set_label("decode_bucket", req.bucket)
            span.set_label("decode_slot", slot)
        done = (req.produced >= req.max_new
                or (req.eos_id is not None and tok0 == req.eos_id))
        if done:
            if span is not None:
                span.phase_end()
            self._counters["evicted"] += 1
            req.stream._finish()
            self._free.append(slot)
            return
        if span is not None:
            # one phase for the whole shared-step participation —
            # per-step phases would be ring-buffer noise at 128 steps
            span.phase_start("decode_step")
        req.slot = slot
        self._slots[slot] = req
        self._occupancy += 1

    def _choose_fuse(self) -> int:
        """Window size for the next dispatch.  The invariant: a fused
        window must not CROSS a scheduling event, so admissions and
        evictions land on exactly the same step indices as pure
        per-step dispatching — fusion changes overhead, never the
        schedule.  The window is therefore the minimum
        remaining-to-schedule over active slots (an EOS-capable
        request counts as 1 — it can end on any step), clamped to the
        compiled plan ladder.

        One deliberate exception: with an EMPTY queue, the full
        ``step_fuse`` window is taken even past a request's end —
        nobody is waiting for its slot, its surplus tokens are
        truncated at fan-out, and the only cost is up to K-1 extra
        slot-steps of garbage against K-fold fewer dispatches on the
        drain tail.  (A request submitted mid-window waits at most
        ~K step-times for admission — the same order as the
        coalescer's gather grace.)

        ``scheduled`` (not ``produced``) drives the remaining check:
        the pipeline may hold one dispatched-unprocessed window, and
        planning from ``produced`` would double-schedule it."""
        if not self._fuse_sizes:
            return 1
        if self._q.empty():
            return self.step_fuse
        rem = self.step_fuse
        for req in self._slots:
            if req is None:
                continue
            r = (1 if req.eos_id is not None
                 else req.max_new - req.scheduled)
            if r < rem:
                rem = r
                if rem <= 1:
                    return 1
        for k in self._fuse_sizes:
            if k <= rem:
                return k
        return 1

    def _kv_positions(self, k: int) -> Tuple[int, int, int]:
        """What the next ``k`` steps of the live slots find in the
        slabs, by kind of slab (``_kv_kinds``: one layer's slab where
        all layers are alike, as TransformerLM's; each kind times its
        layers where a family has slabs of two lengths): the positions
        that are live (a slot's length, its new row included, step by
        step; in a windowed layer's ring at most its rows), the
        positions the step reads for them (the live ones rounded up to
        the kind's read block: the decode kernel's, or the whole slab
        where the kernel does not run), and the positions a full-length
        slab would have held there and the ring did not.  live / read
        says how much of what a step moves it uses; free slots are in
        none."""
        live = read = skipped = 0
        block = self._kv_block
        for req in self._slots:
            if req is not None:
                for n in range(req.length + req.scheduled,
                               req.length + req.scheduled + k):
                    n = min(n, self.max_len)
                    if block is not None:   # one kind, full-length
                        live += n
                        read += -(-n // block) * block
                        continue
                    for rows, block_r, layers in self._kv_kinds:
                        held = min(n, rows)
                        live += layers * held
                        read += layers * min(
                            -(-held // block_r) * block_r, rows)
                        skipped += layers * (n - held)
        return live, read, skipped

    def _any_sampled(self) -> bool:
        """Whether a slot the dispatcher holds live samples
        (``temperature > 0``): the ``pick_sorted`` the next dispatch
        hands its plan (:meth:`_select`).  From the requests in the
        slots, not from the device's per-slot temperatures, which keep a
        finished request's value until the slot is admitted into again.
        The dispatcher learns of an eviction one window late, so this is
        true for every step a sampled request takes, and a window
        longer."""
        return any(req is not None and req.temperature > 0.0
                   for req in self._slots)

    def _run_step(self, plan, flag):
        """Run a step plan over the persistent state and rebind it:
        ``(caches, tok, pos, samp, *rest) = plan(...)``.  Returns
        ``rest``, what the plan hands back beside the state: the single
        step nothing, or a routed family's chosen experts; a fused
        window its token matrix (with the chosen experts beside it)."""
        (self._caches, self._tok, self._pos, self._samp,
         *rest) = plan(self._caches, self._tok, self._pos, self._samp,
                       flag)
        return rest

    def _dispatch_step(self):
        """Dispatch the next decode window WITHOUT fetching (jax
        dispatch is asynchronous) and snapshot the slot->request map as
        of this dispatch — the fetch side fans tokens out against the
        snapshot, so an eviction or admission that happens while the
        device computes cannot mis-route a token.  Returns
        (token vector or (k, capacity) matrix, acceptance vector or
        None, snapshot, window)."""
        if self._step_fn is None:
            # unwarmed engine: build (or store-load) the step plans
            # inline, once — warmed engines pay one is-None check
            self._ensure_step_plans()
        if self._draft_hyper is not None:
            return self._dispatch_spec()
        k = self._choose_fuse()
        kv_live, kv_read, kv_skipped = self._kv_positions(k)
        pick_sorted = self._any_sampled()
        # a family without rings has nothing skipped to say on its span
        ring = ({} if self._kv_block is not None
                else {"kv_positions_window_skipped": kv_skipped})
        with self._phase("dispatch", k=k, live=self._occupancy,
                         kv_positions_live=kv_live,
                         kv_positions_read=kv_read,
                         pick_sorted=int(pick_sorted), **ring):
            flag = self._pick_flags[pick_sorted]
            if k > 1:
                [toks] = self._run_step(self._stepk_fns[k], flag)
                self._counters["fused_dispatches"] += 1
            else:
                routed = self._run_step(self._step_fn, flag)
                toks = (self._tok, *routed) if routed else self._tok
            self._counters["steps"] += k
            if pick_sorted:
                self._counters["steps_sorted"] += k
            self._counters["kv_positions_live"] += kv_live
            self._counters["kv_positions_read"] += kv_read
            self._counters["kv_positions_window_skipped"] += kv_skipped
            for req in self._slots:
                if req is not None:
                    req.scheduled += k
            return toks, None, list(self._slots), k

    def _dispatch_spec(self):
        """Dispatch one speculative window (draft scan + exact step +
        verify, ONE executable) — same snapshot discipline as
        :meth:`_dispatch_step`; the acceptance vector rides the
        pending tuple so the fetch side knows how many of each slot's
        ``spec_tokens`` candidates are valid."""
        k = self.spec_tokens
        # of the target's slabs, the window's one exact step goes
        # through the decode-attention op; the verify reads them whole
        kv_live, kv_read, _ = self._kv_positions(1)
        pick_sorted = self._any_sampled()
        with self._phase("dispatch", k=k, live=self._occupancy,
                         kv_positions_live=kv_live,
                         kv_positions_read=kv_read,
                         pick_sorted=int(pick_sorted)):
            (self._caches, self._dcaches, self._tok, self._pos,
             self._samp, toks, acc) = self._spec_fn(
                self._caches, self._dcaches, self._tok, self._pos,
                self._samp, self._pick_flags[pick_sorted])
            self._counters["steps"] += k
            if pick_sorted:
                self._counters["steps_sorted"] += k
            self._counters["kv_positions_live"] += kv_live
            self._counters["kv_positions_read"] += kv_read
            self._counters["spec_windows"] += 1
            for req in self._slots:
                if req is not None:
                    req.scheduled += k
            return toks, acc, list(self._slots), k

    def _count_routed(self, snapshot, chosen):
        """The ``moe_*`` counts of one fetched window, from the experts
        ``chosen (k, capacity, layers, top_k)`` by every slot at every
        step: over the steps a LIVE slot still had tokens to make (a
        free slot's, and a finished request's surplus steps, are
        garbage), the routed pairs, those whose expert is held here, and
        the held experts that got at least one token, step by step and
        layer by layer.  Returns the three increments."""
        first, count = self._hyper["experts_held"]
        k = chosen.shape[0]
        steps = np.array([0 if req is None or req.stream.done
                          else min(k, req.max_new - req.produced)
                          for req in snapshot])
        live = np.arange(k)[:, None] < steps[None, :]   # (k, capacity)
        local = chosen - first
        held = (local >= 0) & (local < count) & live[:, :, None, None]
        hit = (local[..., None] == np.arange(count)) & held[..., None]
        return (int(live.sum()) * chosen.shape[2] * chosen.shape[3],
                int(held.sum()), int(hit.any(axis=(1, 3)).sum()))

    def _push_window(self, snapshot, toks, counts, chosen=None):
        """Fan one fetched window out to the slots live at dispatch
        time, evicting finished requests: ``toks`` is (k, capacity),
        ``counts[slot]`` how many of the k rows are valid for that
        slot.  A request that finished in an EARLIER window's
        processing (the pipeline dispatches window n+1 before window n
        is processed, so its snapshot can still name it) is skipped —
        its stream is closed and the slot's extra computed tokens are
        garbage by construction, as are any tokens past a request's
        max_new/EOS inside a window."""
        c = self._counters
        tokens0, evicted0 = c["tokens"], c["evicted"]
        with self._phase("fanout") as ann:
            routed = {}
            if chosen is not None:
                routed = dict(zip(
                    ("moe_assignments", "moe_assignments_held",
                     "moe_experts_hit"),
                    self._count_routed(snapshot, chosen)))
                for name, n in routed.items():
                    c[name] += n
                routed["steps"] = len(toks)
            for slot, req in enumerate(snapshot):
                if req is None or req.stream.done:
                    continue
                sampled = req.temperature > 0.0
                for j in range(counts[slot]):
                    tok = int(toks[j, slot])
                    req.produced += 1
                    c["tokens"] += 1
                    if sampled:
                        c["sampled_tokens"] += 1
                    req.stream._push(tok)
                    if (req.produced >= req.max_new
                            or (req.eos_id is not None
                                and tok == req.eos_id)):
                        if req.span is not None:
                            req.span.phase_end()
                        c["evicted"] += 1
                        self._occupancy -= 1
                        req.stream._finish()
                        self._slots[slot] = None
                        self._free.append(slot)
                        break
            ann.set_metadata(tokens=c["tokens"] - tokens0,
                             evicted=c["evicted"] - evicted0, **routed)

    def _process_step(self, pending):
        """Fetch a dispatched window ((capacity,) single step,
        (K, capacity) fused) and fan it out against its snapshot."""
        tok_dev, acc_dev, snapshot, k = pending
        if acc_dev is not None:
            return self._process_spec(pending)
        with self._phase("fetch"):      # the host waits for the device
            toks = jax.device_get(tok_dev)
        _profile.note_transfer("d2h")
        chosen = None
        if self._fam.routed:    # the chosen experts rode the same fetch
            toks, chosen = toks
            chosen = chosen.reshape((k, self.capacity) + chosen.shape[-2:])
        if k == 1:
            toks = toks.reshape(1, -1)
        self._push_window(snapshot, toks, [k] * self.capacity, chosen)

    def _process_spec(self, pending):
        """Fetch a speculative window's (spec_tokens, capacity)
        candidate matrix + acceptance vector and fan out each slot's
        ACCEPTED tokens (at least the exact fallback token, at most
        the whole window) — the verify loop's host half, hot once per
        window."""
        tok_dev, acc_dev, snapshot, k = pending
        with self._phase("fetch"):      # the host waits for the device
            toks = jax.device_get(tok_dev)
            acc = jax.device_get(acc_dev)
        _profile.note_transfer("d2h")
        counts = [0] * self.capacity
        for slot, req in enumerate(snapshot):
            if req is None or req.stream.done:
                continue
            counts[slot] = int(acc[slot])
            # acceptance accounting covers live slots only — free
            # slots compute garbage windows that must not dilute the
            # reported acceptance rate
            self._counters["spec_proposed"] += k - 1
            self._counters["spec_accepted"] += int(acc[slot]) - 1
        self._push_window(snapshot, toks, counts)

    def _decode_loop(self):
        try:
            self._loop_inner()
        except BaseException as e:  # crash net: never strand a caller
            # _crashed (this is its ONLY writer; the closed property
            # folds it in) flips BEFORE the lock barrier: a submit
            # already inside its critical section finishes the enqueue
            # and its own post-put check flushes, one entering after
            # sees closed and raises.  The acquire is a BARRIER, not a
            # guard — bounded because a submitter blocked on a full
            # queue holds the lock until our flush below frees a slot,
            # so we must not wait on it forever.
            self._crashed = True
            got = self._submit_lock.acquire(timeout=1.0)
            if got:
                self._submit_lock.release()
            self._flush_queue(e)
            for slot, req in enumerate(self._slots):
                if req is not None:
                    if req.span is not None:
                        req.span.phase_end()
                    req.stream._finish(e)
                    self._slots[slot] = None
            self._occupancy = 0
            raise

    def _loop_inner(self):
        # one-deep step pipeline: step k+1 is DISPATCHED before step
        # k's tokens are fetched, so the host side (token fan-out,
        # eviction, stream wake-ups, the next admission) overlaps the
        # device compute instead of serializing with it — the
        # serving-side analog of the coalescer's one-deep dispatch
        # pipeline.  Cost: an eviction is observed one step late, so a
        # freed slot re-admits one step later (bounded occupancy
        # slack, never a correctness issue — see _process_step).
        pending = None
        shutdown = False
        while True:
            # 1. admit queued requests into free slots — between
            # steps, which is what makes the batching iteration-level
            while self._free and not shutdown:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is _SHUTDOWN:
                    shutdown = True
                    break
                self._admit_slot(nxt, self._free.popleft())
            # 2. dispatch the next step, then fan out the previous one
            nxt_pending = (self._dispatch_step() if self._occupancy
                           else None)
            if pending is not None:
                self._process_step(pending)
            pending = nxt_pending
            # 3. idle: wait for work (or drain out on shutdown)
            if pending is None and not self._occupancy:
                if shutdown:
                    return
                try:
                    with self._phase("idle"):
                        nxt = self._q.get(timeout=0.05)
                except queue.Empty:
                    continue
                if nxt is _SHUTDOWN:
                    shutdown = True
                    continue
                self._admit_slot(nxt, self._free.popleft())
