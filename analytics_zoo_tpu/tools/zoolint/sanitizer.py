"""zoolint.sanitize(): runtime sanitizer for pinned hot loops.

The static rules catch patterns; this catches FACTS — a context manager
that asserts "this block performed zero unexpected XLA compiles and no
implicit host<->device transfers":

* **compiles** — counted via jax's monitoring events
  (``/jax/core/compile/backend_compile_duration`` fires exactly once per
  real XLA compile; cache hits fire nothing).  More than ``max_compiles``
  raises :class:`RecompileDetected` at block exit, listing the events.
* **transfers** — jax's transfer guards set to ``disallow`` for all
  three directions via ``jax.config.update`` (the process-wide default,
  NOT the thread-local ``jax.transfer_guard`` context) so worker threads
  — the coalescer dispatcher — are covered too.  An implicit transfer
  raises an ``XlaRuntimeError`` mentioning "Disallowed ... transfer" at
  the offending call.  Explicit ``jax.device_put`` / ``jax.device_get``
  always pass: the point is that data movement must be *visible*.

Backend caveat: on the CPU backend device->host is zero-copy — there is
no transfer to guard — so d2h violations are only observable on real
accelerators.  Host->device IS enforced on CPU (jit arguments arriving
as numpy count), which is why the serving dispatch path uploads via
explicit ``device_put`` (see BucketedExecutableCache._dispatch).

Usage::

    with zoolint.sanitize(max_compiles=0) as rep:
        for x in pinned_hot_loop:
            model.predict(x)
    assert rep.compiles == 0    # redundant — exit would have raised

Tests get it as the ``zoolint_sanitize`` fixture; the serving, replica
and decode hot-loop tests run their warmed loops under it.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple


class SanitizeError(RuntimeError):
    """Base for sanitizer verdicts."""


class RecompileDetected(SanitizeError):
    """The sanitized block compiled more than its budget allows."""


class InvariantLeakDetected(SanitizeError):
    """A gauge invariant moved across the sanitized block: an
    in-flight/slot/ticket counter (or the live thread count) did not
    return to its entry value over a quiesced serve window — the
    runtime signature of the ZL701/ZL702 leak class (a seat taken on
    an exception path and never given back shows up here as a counter
    permanently up by one)."""


class SanitizeReport:
    """Live view into the sanitized block (yielded by sanitize())."""

    def __init__(self, label: str):
        self.label = label
        self._lock = threading.Lock()
        self._events: List[Tuple[str, float]] = []

    def _record(self, key: str, duration: float):
        with self._lock:
            if len(self._events) < 1000:  # cap pathological loops
                self._events.append((key, duration))

    @property
    def compiles(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def events(self) -> List[Tuple[str, float]]:
        with self._lock:
            return list(self._events)


_GUARD_CONFIGS = ("jax_transfer_guard_host_to_device",
                  "jax_transfer_guard_device_to_device",
                  "jax_transfer_guard_device_to_host")
_COMPILE_EVENT_SUBSTR = "backend_compile"


@contextlib.contextmanager
def sanitize(max_compiles: int = 0,
             transfer_guard: Optional[str] = "disallow",
             label: str = "zoolint.sanitize",
             invariants: Optional[Callable[[], Dict[str, Any]]] = None,
             invariant_threads: bool = True):
    """Assert the block stays compile- and transfer-clean (module doc).

    ``max_compiles``: XLA compiles the block may perform (0 for a warmed
    hot loop).  ``transfer_guard``: guard level for all three directions
    ("disallow" / "log" / None to leave transfers unguarded).  Yields a
    :class:`SanitizeReport`; raises :class:`RecompileDetected` on exit
    when the budget is exceeded.  Transfer violations raise inside jax
    at the offending call (XlaRuntimeError, "Disallowed ... transfer").

    **Invariant-snapshot mode** (``invariants=``): pass a zero-arg
    callable returning gauge values — in-flight counts, queue seats,
    slot occupancy, admission tickets — and the block asserts every
    one of them (plus, with ``invariant_threads``, the live
    ``threading.active_count()``) returns to its entry value by block
    exit, raising :class:`InvariantLeakDetected` otherwise.  The block
    must be QUIESCED at both ends (warmed before entry, drained before
    exit — a sequential closed-loop serve window is, by construction);
    a monotonic stat counter does not belong in the snapshot, only
    gauges that a leak-free window brings back to rest.  This is the
    runtime twin of the ZL701/ZL702 static rules: the lint proves no
    exception path CAN leak a seat, the snapshot proves this run
    DIDN'T.  Checked only on clean exit (an exception unwinding out of
    the block is its own report), after the compile budget.

    Guards are process-global while the block runs — don't nest, and
    don't run unrelated jax work concurrently with a sanitized block.
    """
    import jax
    from jax._src import monitoring as _monitoring

    report = SanitizeReport(label)
    pre_inv: Optional[Dict[str, Any]] = None
    if invariants is not None:
        pre_inv = dict(invariants())
        if invariant_threads:
            pre_inv["live_threads"] = threading.active_count()
    active = [True]  # unhook even if jax keeps the listener registered

    def _listener(key: str, duration: float, **kw):
        if active[0] and _COMPILE_EVENT_SUBSTR in key:
            report._record(key, duration)

    _monitoring.register_event_duration_secs_listener(_listener)
    prev = {name: getattr(jax.config, name) for name in _GUARD_CONFIGS}
    if transfer_guard is not None:
        for name in _GUARD_CONFIGS:
            jax.config.update(name, transfer_guard)
    try:
        yield report
    finally:
        active[0] = False
        if transfer_guard is not None:
            for name, value in prev.items():
                jax.config.update(name, value)
        unhook = getattr(_monitoring,
                         "_unregister_event_duration_listener_by_callback",
                         None)
        if unhook is not None:
            try:
                unhook(_listener)
            except Exception:
                pass  # the active flag already made it inert
    if report.compiles > max_compiles:
        lines = "\n  ".join(f"{k} ({d * 1e3:.1f} ms)"
                            for k, d in report.events[:10])
        raise RecompileDetected(
            f"{label}: {report.compiles} XLA compile(s) inside a block "
            f"budgeted for {max_compiles} — a shape/dtype escaped the "
            f"warmed bucket ladder, or a jit wrapper was rebuilt:\n  "
            f"{lines}")
    if pre_inv is not None:
        post_inv = dict(invariants())
        if invariant_threads:
            post_inv["live_threads"] = threading.active_count()
        leaks = {k: (pre_inv.get(k), post_inv.get(k))
                 for k in sorted(set(pre_inv) | set(post_inv))
                 if pre_inv.get(k) != post_inv.get(k)}
        if leaks:
            detail = ", ".join(f"{k}: {a!r} -> {b!r}"
                               for k, (a, b) in leaks.items())
            raise InvariantLeakDetected(
                f"{label}: {len(leaks)} invariant(s) moved across a "
                f"quiesced serve window ({detail}) — an in-flight/"
                "slot/ticket counter (or a thread) leaked; an "
                "exception path somewhere took a seat it never gave "
                "back (the ZL701/ZL702 bug class, live)")
