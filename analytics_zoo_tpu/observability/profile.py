"""XLA profiling hooks: compiles, transfers, and live buffers as
metrics + span events instead of sanitizer aborts.

zoolint's ``sanitize()`` turns an unexpected compile or implicit
transfer into a hard failure — right for CI, wrong for production,
where the question is "how often and where".  This module subscribes
the SAME jax monitoring stream (``backend_compile`` duration events
fire exactly once per real XLA compile; cache hits fire nothing, so
counts are exact) but records instead of raising:

* every compile increments ``zoo_xla_compiles_total`` / adds to
  ``zoo_xla_compile_seconds_total`` AND lands as a ``backend_compile``
  event on the current request span (when one is active via
  ``trace.activate`` — e.g. an unwarmed shape compiling on the request
  path shows up IN that request's trace);
* other jax duration events count under
  ``zoo_xla_events_total{event=...}`` (bounded cardinality: jax's own
  event vocabulary);
* the serving dispatch path reports its explicit uploads through
  :func:`note_transfer` (``zoo_transfers_total{direction=...}``) — one
  flag-check when no hooks are installed;
* ``zoo_live_buffers`` is a scrape-time gauge over
  ``jax.live_arrays()`` — a leak shows as monotonic growth.

Install once per process (the web service does), plug
``handle.families`` into a :class:`~.metrics.MetricsRegistry`::

    handle = profile.install()
    registry.register_collector(handle.families)
    ...
    handle.close()

The same module owns the names a DEVICE profile goes by: :func:`annotate`
writes the program's host spans (``SPANS``) into the profiler's own
trace, on the device's clock; ``KERNELS``, ``SCOPES`` and ``PROGRAMS``
are the names the attention kernels, the ``jax.named_scope`` regions
and the jitted programs carry there.  Tests and the benchmark's readers
import them from here.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from . import trace
from .metrics import Family

_COMPILE_EVENT_SUBSTR = "backend_compile"

# ---- names in a device profile ------------------------------------------
#: every host span of the program starts with this
SPAN_PREFIX = "zoo/"
#: the host spans, each on the thread that does the work: the fit loop
#: (``train/``), the prefetch worker (``input/``), the decode dispatcher
#: (``decode/``)
SPANS = tuple(SPAN_PREFIX + n for n in (
    "train/step", "train/data_wait", "train/step_dispatch",
    "train/ckpt_save", "train/loss_fetch",
    "input/produce", "input/h2d",
    "decode/admit", "decode/admit_fetch", "decode/dispatch",
    "decode/fetch", "decode/fanout", "decode/idle"))
#: ``name=`` of the ``pallas_call``s of ops/attention.py: the three flash
#: kernels of the train step and the prefill, and the length-bounded
#: decode-attention kernel of the decode step
KERNEL_FLASH_FWD = "zoo_flash_fwd"
KERNEL_FLASH_BWD_DQ = "zoo_flash_bwd_dq"
KERNEL_FLASH_BWD_DKV = "zoo_flash_bwd_dkv"
KERNEL_DECODE_ATTN = "zoo_decode_attn"
#: the decode step's attention over a bfloat16 slab whose cached heads
#: each serve a group of query heads (the ``cohere2_moe`` family)
KERNEL_DECODE_ATTN_GQA = "zoo_decode_attn_gqa"
#: the decode step's state-space update (ops/ssm.py): every slot's
#: float32 state read and written in place
KERNEL_SSM_DECODE = "zoo_ssm_decode"
KERNELS = (KERNEL_FLASH_FWD, KERNEL_FLASH_BWD_DQ, KERNEL_FLASH_BWD_DKV,
           KERNEL_DECODE_ATTN, KERNEL_DECODE_ATTN_GQA, KERNEL_SSM_DECODE)
#: regions inside the jitted programs: ``jax.named_scope``s, which are
#: HLO metadata (part of the persistent compilation cache's key since
#: ``common.context.enable_compile_cache`` puts it there), and
#: ``zoo_sample``, a jit of its own inside the decode plans, whose name
#: is part of the program
SCOPE_LOSS = "zoo_loss"
SCOPE_OPTIMIZER_UPDATE = "zoo_optimizer_update"
SCOPE_GRAD_ACCUM = "zoo_grad_accum"
#: a decode step's attention layer: its norm, projections and the
#: attention over the slab
SCOPE_DECODE_ATTENTION = "zoo_decode_attention"
SCOPE_SAMPLE = "zoo_sample"
#: the parts of a decoder's forward, in step and admit plans alike: the
#: token (and position) table lookups; norms and the residual adds beside
#: them; q/k/v (with their rotary turn) and the output projection; the
#: causal attention over a prompt (on the chip ``zoo_flash_fwd``); a
#: dense MLP; a Mamba mixer's in and out projections (its gated norm
#: with the latter); the head (the final norm inside it is
#: ``zoo_norm``); what lays a prompt's state and first token into a slot
SCOPE_EMBED = "zoo_embed"
SCOPE_NORM = "zoo_norm"
SCOPE_ATTN_PROJ = "zoo_attn_proj"
SCOPE_ATTN_CORE = "zoo_attn_core"
SCOPE_MLP = "zoo_mlp"
SCOPE_SSM_PROJ = "zoo_ssm_proj"
SCOPE_HEAD = "zoo_head"
SCOPE_INSERT = "zoo_insert"
#: the top-k expert sublayer (ops/moe.py), in step and admit plans: the
#: whole of it, and inside it the router, the held experts' grouped
#: products and the shared experts
SCOPE_MOE = "zoo_moe"
SCOPE_MOE_ROUTER = "zoo_moe_router"
SCOPE_MOE_EXPERTS = "zoo_moe_experts"
SCOPE_MOE_SHARED = "zoo_moe_shared"
#: a Mamba-2 mixer (ops/ssm.py), in step and admit plans: the whole of it
#: (in_proj to out_proj), and inside it the convolution and the scan (a
#: prompt's chunked scan, or a step's state update)
SCOPE_SSM = "zoo_ssm"
SCOPE_SSM_CONV = "zoo_ssm_conv"
SCOPE_SSM_SCAN = "zoo_ssm_scan"
#: the innermost names that partition an admit plan: every operation of
#: ``jit_admit`` lies under one of them, and the innermost one it lies
#: under is its part (``zoo_moe`` and ``zoo_ssm`` hold parts; a norm
#: inside the head is ``zoo_norm``)
ADMIT_PARTS = (SCOPE_EMBED, SCOPE_NORM, SCOPE_ATTN_PROJ, SCOPE_ATTN_CORE,
               SCOPE_MLP, SCOPE_MOE_ROUTER, SCOPE_MOE_EXPERTS,
               SCOPE_MOE_SHARED, SCOPE_SSM_PROJ, SCOPE_SSM_CONV,
               SCOPE_SSM_SCAN, SCOPE_HEAD, SCOPE_INSERT, SCOPE_SAMPLE)
SCOPES = (SCOPE_LOSS, SCOPE_OPTIMIZER_UPDATE, SCOPE_GRAD_ACCUM,
          SCOPE_DECODE_ATTENTION, SCOPE_MOE, SCOPE_SSM) + ADMIT_PARTS
#: XLA module names of the jitted programs: the trainer's step, the
#: decode engine's admit / prefix-admit / single step / fused window /
#: speculative window / prefix-fill plans
PROGRAM_TRAIN_STEP = "jit_train_step"
PROGRAM_ADMIT = "jit_admit"
PROGRAM_PADMIT = "jit_padmit"
PROGRAM_STEP = "jit_step"
PROGRAM_STEPK = "jit_stepk"
PROGRAM_SPEC = "jit_spec"
PROGRAM_FILL = "jit_fill"
PROGRAMS = (PROGRAM_TRAIN_STEP, PROGRAM_ADMIT, PROGRAM_PADMIT,
            PROGRAM_STEP, PROGRAM_STEPK, PROGRAM_SPEC, PROGRAM_FILL)

_annotations = None     # (TraceAnnotation, StepTraceAnnotation), lazily


def annotate(name: str, **stats):
    """A host span ``zoo/<name>`` on the profiler's clock: a
    ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` where a
    ``step_num`` is given) to enter around the work, on the thread that
    does it.  ``stats`` are the counts taken at that boundary; they come
    back as the event's stats, and ``set_metadata(**more)`` on the
    returned object adds those known only at the end.  Inert unless a
    profiler session is running (well under a microsecond), so there is
    no switch: whoever captures a profile gets the program's spans."""
    global _annotations
    if _annotations is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        _annotations = (TraceAnnotation, StepTraceAnnotation)
    return _annotations["step_num" in stats](SPAN_PREFIX + name, **stats)


def named(name: str, fn):
    """``fn`` renamed so that ``jax.jit(fn)`` is called ``name`` in a
    device profile: one of ``PROGRAMS`` (``jit_<fn>``, the XLA module of
    an outermost jit) or, for a jit inside a program, the bare name its
    operations then carry as ``jit(<name>)``.  Pinned here and not left
    to whatever the function happens to be called."""
    fn.__name__ = fn.__qualname__ = name.removeprefix("jit_")
    return fn

_lock = threading.Lock()
_installed: "Optional[XlaProfile]" = None


class XlaProfile:
    """Counters fed by jax's monitoring stream (see module doc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_seconds = 0.0
        self._events: Dict[str, int] = {}
        self._transfers: Dict[str, int] = {}
        self._closed = False

    # ---- feed side ----
    def _on_duration_event(self, key: str, duration: float, **kw):
        if self._closed:
            return
        if _COMPILE_EVENT_SUBSTR in key:
            with self._lock:
                self.compiles += 1
                self.compile_seconds += duration
            span = trace.current_span()
            if span is not None:
                span.event("backend_compile",
                           seconds=round(duration, 6), key=key)
        else:
            with self._lock:
                self._events[key] = self._events.get(key, 0) + 1

    def _note_transfer(self, direction: str):
        with self._lock:
            self._transfers[direction] = \
                self._transfers.get(direction, 0) + 1

    # ---- read side ----
    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"compiles": self.compiles,
                    "compile_seconds": round(self.compile_seconds, 6),
                    "events": dict(self._events),
                    "transfers": dict(self._transfers)}

    def families(self) -> List[Family]:
        """Prometheus collector (plug into MetricsRegistry)."""
        with self._lock:
            compiles = self.compiles
            seconds = self.compile_seconds
            events = dict(self._events)
            transfers = dict(self._transfers)
        fams = [
            Family("counter", "zoo_xla_compiles_total",
                   "XLA backend compiles observed since install",
                   [({}, compiles)]),
            Family("counter", "zoo_xla_compile_seconds_total",
                   "cumulative XLA compile wall seconds",
                   [({}, seconds)]),
        ]
        if events:
            fams.append(Family(
                "counter", "zoo_xla_events_total",
                "other jax monitoring duration events, by key",
                [({"event": k}, v) for k, v in sorted(events.items())]))
        if transfers:
            fams.append(Family(
                "counter", "zoo_transfers_total",
                "explicit host<->device transfers on the serving "
                "dispatch path, by direction",
                [({"direction": d}, v)
                 for d, v in sorted(transfers.items())]))
        fams.append(Family(
            "gauge", "zoo_live_buffers",
            "live jax device buffers (scrape-time)",
            [({}, _live_buffer_count())]))
        return fams

    def close(self):
        """Unhook from jax monitoring (idempotent)."""
        global _installed
        self._closed = True
        with _lock:
            if _installed is self:
                _installed = None
        try:
            from jax._src import monitoring as _monitoring
            unhook = getattr(
                _monitoring,
                "_unregister_event_duration_listener_by_callback", None)
            if unhook is not None:
                unhook(self._on_duration_event)
        except Exception:
            pass  # _closed already made the listener inert


def _live_buffer_count() -> float:
    try:
        import jax
        return float(len(jax.live_arrays()))
    except Exception:
        return float("nan")


def install() -> XlaProfile:
    """Subscribe an :class:`XlaProfile` to jax's monitoring stream and
    make it the process target for :func:`note_transfer`.  Returns the
    existing handle when one is already installed (one stream, one
    consumer)."""
    global _installed
    with _lock:
        if _installed is not None:
            return _installed
        handle = XlaProfile()
        from jax._src import monitoring as _monitoring
        _monitoring.register_event_duration_secs_listener(
            handle._on_duration_event)
        _installed = handle
        return handle


def installed() -> "Optional[XlaProfile]":
    return _installed


def note_transfer(direction: str = "h2d"):
    """Count one explicit transfer (called by the serving dispatch
    path around its ``device_put``).  A single flag-check when no
    profile is installed."""
    handle = _installed
    if handle is not None:
        handle._note_transfer(direction)
