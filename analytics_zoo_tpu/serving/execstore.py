"""Persistent executable store: a content-addressed on-disk compile
cache for zero-compile cold start and instant fleet deploy.

Every fresh process pays the full bucket-ladder + decode-plan XLA
compile (~380 ms per executable on a CPU host, PR 5) before it can
serve — a restarted worker or a newly provisioned replica is cold for
seconds.  PR 5 already proved the serialized-executable round trip
loads in ~3-10 ms with only the device assignment rewritten; this
module persists those bytes so the SECOND process (and every process
after it, on every machine sharing the store) warms from disk in
milliseconds instead of compiling:

* **Content-addressed.**  An entry's key is a SHA-256 fingerprint over
  everything that could change the compiled artifact: the lowered HLO
  module (which captures the model graph, the padded bucket / batch
  signature, and — for plans that close over weights — the weight
  values themselves), a digest of the weights when they are runtime
  ARGUMENTS (the replica forward), the jax + jaxlib version strings,
  the backend platform and device kind, ``XLA_FLAGS``, and any
  caller-supplied extras (the decode engine adds its
  ``(capacity, max_len, bucket)`` tuple).  A change to ANY ingredient
  lands on a different key — "stale" entries are simply never found.
* **Read-through / write-behind.**  The compile sites
  (:meth:`~..pipeline.inference.serving.ReplicaSet.ensure_compiled`
  and the decode engine's plan builder) consult the store at
  warmup/compile-miss time only; a hit rehydrates the executable, a
  miss compiles exactly as before and then persists the result.  The
  per-dispatch hot path never touches the store — lookups happen only
  where a compile would otherwise happen (tests pin this).
* **Corruption-safe, never wrong.**  Writes go to a temp file and are
  published with an atomic rename; every entry carries a SHA-256
  checksum of its payload verified on read.  A truncated, bit-flipped,
  or unpicklable entry is counted ``invalid``, deleted, and the caller
  silently falls back to a fresh compile — the store can cause a
  recompile, never a wrong executable.
* **Observable.**  ``zoo_execstore_{hit,miss,write,invalid,evicted}_total``
  counter families plus ``zoo_execstore_entries`` /
  ``zoo_execstore_bytes`` gauges (:meth:`ExecStore.families`), an
  ``execstore_load`` event on the active request span when a hit
  happens under one, and structured log lines for every store verdict.

Enabling the store::

    export ZOO_EXECSTORE_DIR=/var/cache/zoo-exec   # fleet recipe
    # or, programmatically:
    from analytics_zoo_tpu.serving import execstore
    execstore.configure("/var/cache/zoo-exec", byte_budget=2 << 30)

With the store enabled, ``ModelRegistry.deploy()`` and
``DecodeEngine.warmup()`` in a process whose store is warm record
ZERO ``backend_compile`` events (test_execstore holds this across
two real processes).  Without configuration the store is
entirely inert — no files, no lookups, identical serving behavior.

Hygiene: the store is size-capped LRU.  Reads bump an entry's mtime;
``gc()`` (also ``python -m analytics_zoo_tpu.serving.execstore gc``)
evicts oldest-mtime entries over the byte budget — but never an entry
this process itself wrote or loaded (a deploy's own executables must
not vanish under it).  ``stat`` prints the store table.

Entry format: one JSON header line (fingerprint, meta, payload
checksum) followed by the raw payload bytes — ``stat`` and
``entries()`` read headers alone.  Trust model: payloads are
deserialized executables (decode-plan payloads are pickles), so the
store directory must be trusted exactly like the model files
themselves — point it at an operator-owned path, not a world-writable
one.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .. import envcontract
from ..observability import trace as _trace
from ..observability.log import get_logger as _get_logger
from ..observability.metrics import Family

_slog = _get_logger("zoo.serving.execstore")

ENV_DIR = "ZOO_EXECSTORE_DIR"
ENV_BUDGET = "ZOO_EXECSTORE_BYTES"
_SUFFIX = ".zexe"

_COUNTER_KEYS = ("hit", "miss", "write", "invalid", "evicted")


def _runtime_parts(device=None) -> Tuple:
    """The environment half of every fingerprint: anything here
    changing means an on-disk executable may no longer load (or may
    load but compute differently), so it must land on a different
    key.  Split out as a function so tests can monkeypatch a version
    bump without reinstalling jax."""
    import jax
    import jaxlib
    if device is None:
        device = jax.local_devices()[0]
    return ("jax", jax.__version__, "jaxlib", jaxlib.__version__,
            "platform", getattr(device.client, "platform", "?"),
            "device_kind", getattr(device, "device_kind", "?"),
            "xla_flags", os.environ.get("XLA_FLAGS", ""))


def hlo_digest(lowered) -> str:
    """SHA-256 of a ``jax.jit(...).lower(...)`` result's HLO module
    TEXT — the graph/shape/dtype half of a fingerprint.  Lowering is
    a trace + HLO emission: it fires no ``backend_compile`` event, so
    hashing it keeps the store-hit path compile-free.  The text form
    deliberately, not the serialized proto: the proto embeds
    process-unique computation ids (two identical lowerings hash
    differently even in ONE process), while the text is stable for
    identical source.  Source locations in the module metadata rotate
    the key on a code edit — a benign recompile, never a stale hit.
    Large constants may be elided from the text, which is why every
    caller ALSO folds a :func:`params_digest` of the weights into its
    fingerprint."""
    text = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    return hashlib.sha256(text.encode()).hexdigest()


def params_digest(tree) -> str:
    """SHA-256 over a param tree's leaf CONTENTS (+ shapes/dtypes).
    Needed when the weights are runtime arguments of the executable
    (the replica forward): the compiled code is then weight-agnostic,
    but the store key must still rotate on a weight change so a
    redeploy with new weights can never be answered by an entry
    recorded against old ones.  Explicit ``device_get`` — runs at
    deploy time, transfer-guard visible."""
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(jax.device_get(leaf))
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def serialize_compiled(compiled) -> bytes:
    """A jax-level ``Compiled`` (from ``lower().compile()``) as store
    payload bytes: the executable's PJRT serialization plus the
    in/out pytree defs it needs to be callable again."""
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = _se.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree), protocol=4)


def rehydrate(payload: bytes, devices):
    """Store payload bytes back into a callable jax-level ``Compiled``
    on ``devices`` (the devices it was compiled for, in assignment
    order) — a LOAD, not a compile: no ``backend_compile`` event
    fires, and calling the result is bit-identical to calling the
    freshly compiled original (same binary).  Raises on any malformed
    payload (callers fall back to compiling)."""
    from jax.experimental import serialize_executable as _se
    ser, in_tree, out_tree = pickle.loads(payload)
    devices = list(devices)
    return _se.deserialize_and_load(ser, in_tree, out_tree,
                                    backend=devices[0].client,
                                    execution_devices=devices)


class StoreEntry:
    """One verified store read: the payload bytes + writer metadata."""

    __slots__ = ("fingerprint", "payload", "meta")

    def __init__(self, fingerprint: str, payload: bytes,
                 meta: Dict[str, Any]):
        self.fingerprint = fingerprint
        self.payload = payload
        self.meta = meta


class ExecStore:
    """The on-disk store (module docstring).  Thread-safe: counter and
    protected-set mutations are lock-guarded; file publishes are
    atomic renames, so concurrent processes sharing one directory see
    whole entries or nothing."""

    def __init__(self, root: str, byte_budget: Optional[int] = None):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.byte_budget = (None if byte_budget is None
                            else int(byte_budget))
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {k: 0 for k in _COUNTER_KEYS}
        # entries this process wrote OR loaded: its own deploy depends
        # on them, so gc() must never evict them out from under it
        self._protected: set = set()

    # ---- keys ----
    def fingerprint(self, *parts, device=None) -> str:
        """Content address over ``parts`` + the runtime environment
        (jax/jaxlib versions, platform, device kind, XLA_FLAGS)."""
        h = hashlib.sha256()
        for part in _runtime_parts(device) + parts:
            h.update(repr(part).encode())
            h.update(b"\x00")
        return h.hexdigest()

    def _path(self, fp: str) -> str:
        return os.path.join(self.root, fp + _SUFFIX)

    def _count(self, key: str, n: int = 1):
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    # ---- read-through ----
    def lookup(self, fp: str) -> Optional[StoreEntry]:
        """One store read: the verified entry for ``fp``, or None on a
        miss.  A present-but-corrupt entry (truncated, bit-flipped,
        unpicklable, checksum mismatch) counts ``invalid``, is
        deleted, and reads as a miss — the caller compiles.  A hit
        bumps the entry's mtime (the LRU clock), protects it from
        this process's gc, records an ``execstore_load`` event on the
        active request span, and logs a structured line."""
        path = self._path(fp)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            self._count("miss")
            _slog.info("execstore_miss", key=fp[:12])
            return None
        try:
            # entry = one JSON header line + raw payload bytes (see
            # put()); json.dumps escapes newlines, so the first \n is
            # always the split point
            nl = raw.index(b"\n")
            obj = json.loads(raw[:nl])
            payload = raw[nl + 1:]
            meta = obj["meta"]
            if hashlib.sha256(payload).hexdigest() != obj["sha256"]:
                raise ValueError("payload checksum mismatch")
        except Exception as e:  # noqa: BLE001 — any decode failure is
            # the same verdict: invalid, delete, recompile
            self.note_invalid(fp, e)
            return None
        try:
            os.utime(path)  # LRU touch; best-effort
        except OSError:
            pass
        with self._lock:
            self._protected.add(fp)
        self._count("hit")
        ms = round((time.perf_counter() - t0) * 1e3, 3)
        span = _trace.current_span()
        if span is not None:
            span.event("execstore_load", key=fp[:12], ms=ms,
                       bytes=len(payload))
        _slog.info("execstore_hit", key=fp[:12], bytes=len(payload),
                   read_ms=ms)
        return StoreEntry(fp, payload, meta)

    def note_invalid(self, fp: str, error: BaseException):
        """Record (and remove) a corrupt/undecodable entry so the
        recompile's write-behind replaces it cleanly.  Also the hook
        rehydration callers use when the PAYLOAD decodes but the
        executable inside it will not load."""
        self._count("invalid")
        try:
            os.remove(self._path(fp))
        except OSError:
            pass
        _slog.error("execstore_invalid", key=fp[:12],
                    error=f"{type(error).__name__}: {error}")

    # ---- write-behind ----
    def put(self, fp: str, payload: bytes,
            meta: Optional[Dict[str, Any]] = None) -> bool:
        """Persist one entry: a small JSON header line (fingerprint,
        meta, payload checksum) followed by the raw payload bytes —
        ``stat``/``entries()`` read the header alone, never the
        payload — written to a temp file and published by atomic
        rename (a reader never sees a torn entry).  Returns False
        (and logs) instead of raising on I/O or meta-encoding failure
        — the store must never fail a deploy that just compiled
        successfully.  A configured byte budget triggers an inline gc
        after the write (compile-time path, never per-dispatch)."""
        meta = dict(meta or {})
        meta.setdefault("created_at", time.time())
        path = self._path(fp)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            blob = json.dumps(
                {"fingerprint": fp, "meta": meta,
                 "sha256": hashlib.sha256(payload).hexdigest()}
            ).encode("utf-8") + b"\n" + payload
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except (OSError, TypeError, ValueError) as e:
            try:
                os.remove(tmp)
            except OSError:
                pass
            _slog.error("execstore_write_failed", key=fp[:12],
                        error=f"{type(e).__name__}: {e}")
            return False
        with self._lock:
            self._protected.add(fp)
        self._count("write")
        _slog.info("execstore_write", key=fp[:12], bytes=len(blob),
                   kind=meta.get("kind", "?"))
        if self.byte_budget is not None:
            self.gc()
        return True

    # ---- hygiene ----
    def _scan(self) -> List[Tuple[float, int, str]]:
        """(mtime, size, fingerprint) for every entry on disk."""
        out = []
        try:
            with os.scandir(self.root) as it:
                for de in it:
                    if not de.name.endswith(_SUFFIX):
                        continue
                    try:
                        st = de.stat()
                    except OSError:
                        continue
                    out.append((st.st_mtime, st.st_size,
                                de.name[:-len(_SUFFIX)]))
        except OSError:
            pass
        return out

    def gc(self, byte_budget: Optional[int] = None) -> Dict[str, Any]:
        """Size-capped LRU eviction: drop oldest-mtime entries until
        the store fits ``byte_budget`` (default: the configured
        budget; no-op when neither is set).  Entries this process
        wrote or loaded are NEVER evicted — a running server's own
        deploy must survive its own gc; they still count toward the
        total, so a budget smaller than the live working set simply
        keeps the protected set and nothing else."""
        budget = self.byte_budget if byte_budget is None else int(byte_budget)
        entries = self._scan()
        total = sum(size for _, size, _ in entries)
        evicted = 0
        freed = 0
        if budget is not None:
            with self._lock:
                protected = set(self._protected)
            for mtime, size, fp in sorted(entries):
                if total <= budget:
                    break
                if fp in protected:
                    continue
                try:
                    os.remove(self._path(fp))
                except OSError:
                    continue
                evicted += 1
                freed += size
                total -= size
        if evicted:
            self._count("evicted", evicted)
            _slog.info("execstore_gc", evicted=evicted,
                       freed_bytes=freed, kept_bytes=total)
        return {"evicted": evicted, "freed_bytes": freed,
                "entries": len(entries) - evicted, "bytes": total}

    # ---- observability ----
    def stats(self) -> Dict[str, Any]:
        entries = self._scan()
        with self._lock:
            counters = dict(self._counters)
            protected = len(self._protected)
        return {"root": self.root, "entries": len(entries),
                "bytes": sum(size for _, size, _ in entries),
                "byte_budget": self.byte_budget,
                "protected": protected, **counters}

    def families(self) -> List[Family]:
        """Prometheus collector: plug into a MetricsRegistry."""
        s = self.stats()
        fams = [Family("counter", f"zoo_execstore_{k}_total",
                       _FAMILY_HELP[k], [({}, s[k])])
                for k in _COUNTER_KEYS]
        fams.append(Family("gauge", "zoo_execstore_entries",
                           "executables currently persisted in the "
                           "store", [({}, s["entries"])]))
        fams.append(Family("gauge", "zoo_execstore_bytes",
                           "total bytes on disk in the store",
                           [({}, s["bytes"])]))
        return fams

    def entries(self) -> List[Dict[str, Any]]:
        """Per-entry table for the ``stat`` CLI (newest first).  Reads
        each entry's JSON header LINE only — never the payload, so
        listing a budget-sized store moves kilobytes, not
        gigabytes."""
        out = []
        for mtime, size, fp in sorted(self._scan(), reverse=True):
            try:
                with open(self._path(fp), "rb") as f:
                    head = f.readline(1 << 16)
                meta = json.loads(head).get("meta", {})
                kind = meta.get("kind", "?")
                model = meta.get("model", "-")
                mesh = _mesh_label(meta.get("mesh"))
            except Exception:  # noqa: BLE001 — stat must never crash
                kind, model, mesh = "unreadable", "-", "-"
            out.append({"fingerprint": fp, "bytes": size,
                        "mtime": mtime, "kind": kind, "model": model,
                        "mesh": mesh})
        return out

    def by_mesh(self) -> Dict[str, Dict[str, int]]:
        """Entries/bytes aggregated by the writer's ``mesh`` meta tag
        (``axes`` x ``strategy``; ``-`` for single-device entries) —
        the sharded-serving operator's view of how much of the store
        each mesh layout occupies."""
        agg: Dict[str, Dict[str, int]] = {}
        for e in self.entries():
            row = agg.setdefault(e["mesh"], {"entries": 0, "bytes": 0})
            row["entries"] += 1
            row["bytes"] += e["bytes"]
        return agg

    def by_model(self) -> Dict[str, Dict[str, int]]:
        """Entries/bytes aggregated by the writer's ``model`` meta tag
        (the registry name the deploy served; ``-`` for untagged
        entries) — what a density fleet's operator reads to see which
        models the shared store keeps on disk."""
        agg: Dict[str, Dict[str, int]] = {}
        for e in self.entries():
            row = agg.setdefault(e["model"], {"entries": 0, "bytes": 0})
            row["entries"] += 1
            row["bytes"] += e["bytes"]
        return agg


def _mesh_label(mesh) -> str:
    """Collapse a header ``mesh`` meta dict to a stable short label
    for aggregation: ``tensor=2/tp`` (axes sorted by name).  ``-``
    for entries written by single-device sets."""
    if not isinstance(mesh, dict):
        return "-"
    axes = mesh.get("axes")
    parts = ",".join(f"{k}={v}" for k, v in sorted(axes.items())) \
        if isinstance(axes, dict) and axes else "?"
    return f"{parts}/{mesh.get('strategy', '?')}"


_FAMILY_HELP = {
    "hit": "executable store lookups answered from disk",
    "miss": "executable store lookups that fell through to a compile",
    "write": "executables persisted to the store",
    "invalid": "corrupt/undecodable store entries detected (each one "
               "fell back to a fresh compile)",
    "evicted": "entries removed by LRU gc",
}


# ---- process-wide configuration --------------------------------------
_cur_lock = threading.Lock()
_current: Optional[ExecStore] = None
_env_checked = False


def configure(root: str, byte_budget: Optional[int] = None) -> ExecStore:
    """Enable the store for this process (every compile site consults
    it from now on).  Returns the store."""
    global _current, _env_checked
    with _cur_lock:
        _current = ExecStore(root, byte_budget=byte_budget)
        _env_checked = True
        return _current


def disable():
    """Turn the store off for this process (files stay on disk)."""
    global _current, _env_checked
    with _cur_lock:
        _current = None
        _env_checked = True


def current() -> Optional[ExecStore]:
    """The process store, or None when disabled.  First call honors
    ``ZOO_EXECSTORE_DIR`` (+ optional ``ZOO_EXECSTORE_BYTES``) so a
    fleet worker enables the store with one environment variable and
    zero code."""
    global _current, _env_checked
    if _current is None and not _env_checked:
        with _cur_lock:
            if _current is None and not _env_checked:
                _env_checked = True
                root = envcontract.env_str(ENV_DIR)
                if root:
                    budget = envcontract.env_str(ENV_BUDGET)
                    _current = ExecStore(
                        root,
                        byte_budget=int(budget) if budget else None)
    return _current


# ---- CLI --------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    """``python -m analytics_zoo_tpu.serving.execstore gc|stat``."""
    import argparse
    # --root is accepted on BOTH sides of the subcommand (`--root X
    # stat` and `stat --root X`): SUPPRESS on the shared parent keeps
    # an absent sub-level flag from clobbering a top-level one
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--root", default=argparse.SUPPRESS,
                        help=f"store directory (default: ${ENV_DIR})")
    parser = argparse.ArgumentParser(
        prog="python -m analytics_zoo_tpu.serving.execstore",
        description="inspect / garbage-collect the persistent "
                    "executable store")
    parser.add_argument("--root", default=None,
                        help=f"store directory (default: ${ENV_DIR})")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_stat = sub.add_parser("stat", parents=[common],
                            help="print store contents and counters")
    p_stat.add_argument("--by-model", action="store_true",
                        help="aggregate entries/bytes per model tag "
                             "(the registry name each deploy wrote)")
    p_stat.add_argument("--by-mesh", action="store_true",
                        help="aggregate entries/bytes per mesh layout "
                             "(axes x strategy; '-' = single-device)")
    p_gc = sub.add_parser("gc", parents=[common],
                          help="LRU-evict down to a byte budget")
    p_gc.add_argument("--budget", type=int, default=None,
                      help=f"byte budget (default: ${ENV_BUDGET})")
    args = parser.parse_args(argv)
    root = args.root or envcontract.env_str(ENV_DIR)
    if not root:
        parser.error(f"no store: pass --root or set ${ENV_DIR}")
    store = ExecStore(root)
    if args.cmd == "stat":
        s = store.stats()
        print(f"execstore {s['root']}: {s['entries']} entries, "
              f"{s['bytes']:,} bytes"
              + (f" (budget {s['byte_budget']:,})"
                 if s["byte_budget"] else ""))
        if getattr(args, "by_model", False) \
                or getattr(args, "by_mesh", False):
            # largest first: the density question is "what is eating
            # the store", answered top-down
            table = store.by_mesh() if getattr(args, "by_mesh", False) \
                else store.by_model()
            agg = sorted(table.items(), key=lambda kv: -kv[1]["bytes"])
            for tag, row in agg:
                print(f"  {tag:<24} {row['entries']:>5} entries  "
                      f"{row['bytes']:>12,} B")
            return 0
        for e in store.entries():
            age = time.time() - e["mtime"]
            print(f"  {e['fingerprint'][:16]}  {e['bytes']:>10,} B  "
                  f"{age:>8.0f}s old  {e['kind']}  {e['model']}  "
                  f"{e['mesh']}")
        return 0
    budget = args.budget
    if budget is None:
        env_budget = envcontract.env_str(ENV_BUDGET)
        if env_budget is None:
            parser.error(f"gc needs --budget or ${ENV_BUDGET}")
        budget = int(env_budget)
    res = store.gc(byte_budget=budget)
    print(f"execstore gc: evicted {res['evicted']} entries "
          f"({res['freed_bytes']:,} B freed), {res['entries']} kept "
          f"({res['bytes']:,} B)")
    return 0


if __name__ == "__main__":  # pragma: no cover — tested via main()
    import sys
    try:
        sys.exit(main())
    except BrokenPipeError:
        # stat | head closed the pipe — a normal way to read a long
        # table, not an error worth a traceback
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
