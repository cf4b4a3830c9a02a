"""Async host↔device prefetch: overlap batch k+1's host work with batch
k's device compute.

``jax.device_put`` is dispatch-asynchronous, but everything BEFORE it —
decode, shuffle-gather, ``np.stack``, tail padding — runs on the host and
serializes with the step loop unless it is moved off-thread.  An earlier
round's notes (withdrawn) read overlap efficiency 0.65 for the
synchronous put-then-step pattern: the host→device transfer plus batch
materialization is the end-to-end wall.  ``prefetch`` runs the source
iterator AND the transform (decode + ``device_put``) on a background
thread with a bounded buffer, so while the device computes batch *k* the
host is already materializing and shipping batch *k+1* (double-buffered
at the default ``depth=2``).

Used by the Trainer's fit/predict loops and by ``InferenceModel``'s
batch streaming; safe anywhere an iterator of batches feeds a compute
loop.  Ordering is preserved exactly; source exceptions re-raise at the
consumer at the position they occurred; abandoning the iterator
(``close()`` / GC / ``break``) stops the worker promptly.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

from ..observability.profile import annotate

_END = object()
_ERR = object()


def _put(q: "queue.Queue", stop: threading.Event, item) -> bool:
    """Bounded put that stays responsive to close(); returns False when
    the consumer is gone."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.05)
            return True
        except queue.Full:
            continue
    return False


def _worker(source, transform, q, stop):
    # two host spans a batch on the profiler's clock (inert without a
    # profiler session): the source's next(), and the transform (the
    # trainer's microbatch split + upload)
    try:
        source = iter(source)
        while True:
            with annotate("input/produce"):
                item = next(source, _END)
            if item is _END:
                break
            if stop.is_set():
                return
            if transform is not None:
                with annotate("input/h2d"):
                    item = transform(item)
            if not _put(q, stop, (None, item)):
                return
        _put(q, stop, (_END, None))
    except BaseException as e:  # re-raised at the consumer
        _put(q, stop, (_ERR, e))


class PrefetchIterator:
    """Iterator pulling items through a background worker thread.

    ``transform`` (host decode + ``jax.device_put``) runs ON THE WORKER,
    so at most ``depth`` transformed items are in flight ahead of the
    consumer — bounded memory, double-buffered overlap at depth 2.
    """

    def __init__(self, iterable: Iterable, transform: Optional[Callable] = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        # the worker closes over (source, transform, queue, stop) but NOT
        # self — a running thread referencing a bound method would keep
        # this iterator alive forever, so an abandoned iterator could
        # never be collected and its __del__/close never fire
        self._thread = threading.Thread(
            target=_worker, args=(iterable, transform, self._q, self._stop),
            name="zoo-prefetch", daemon=True)
        self._started = False
        self._done = False

    # ---- consumer side ----
    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if not self._started:
            self._started = True
            self._thread.start()
        kind, val = self._q.get()
        if kind is _END:
            self._done = True
            raise StopIteration
        if kind is _ERR:
            self._done = True
            self._stop.set()
            raise val
        return val

    def close(self):
        """Stop the worker and drop buffered items (idempotent)."""
        self._done = True
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def prefetch(iterable: Iterable, transform: Optional[Callable] = None,
             depth: int = 2) -> PrefetchIterator:
    """Prefetch ``iterable`` through a background thread.

    ``transform(item)`` — typically decode + ``jax.device_put`` — runs on
    the worker; ``depth`` bounds how many transformed items wait ahead of
    the consumer (2 = classic double buffering)."""
    return PrefetchIterator(iterable, transform=transform, depth=depth)
