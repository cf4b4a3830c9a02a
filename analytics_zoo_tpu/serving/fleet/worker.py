"""Fleet serving worker: one process of the worker plane.

``python -m analytics_zoo_tpu.serving.fleet.worker --share DIR
--port-file PATH [--fake] [--registry-json '{...}']``

A worker is the existing single-process data plane — a
:class:`~..registry.ModelRegistry` with its bucketed executables,
coalescer, admission control and decode engines — behind a localhost
socket speaking :mod:`.protocol` frames.  It owns NO fleet state: what
it serves is whatever the share directory's committed artifacts say
(``activate`` ops name versions), so a crashed worker's replacement
rebuilds the serving set from disk + execstore, in milliseconds when
the store is warm.

Supervision contract (the PR 10 machinery, reused):

* ``ZOO_HEARTBEAT_FILE`` — touched from the accept loop (throttled),
  so a wedged front door reads stale and the watchdog SIGKILLs;
* ``ZOO_FLIGHTREC_DIR`` — per-process black box installed from env;
  spans/logs/metric snapshots land under ``rank{r}.i{inc}/`` where
  rank is ``ZOO_TPU_PROCESS_ID`` and the incarnation is
  ``ZOO_RESTART_COUNT`` (both exported by the fleet supervisor);
* ``ZOO_EXECSTORE_DIR`` — the shared store; a warm activate records
  zero ``backend_compile`` events (reported per activate, which is
  how the fleet drill gates it cross-process);
* ``ZOO_PAGER_RESIDENT`` — when set (an int), the worker's registry
  runs a weight pager with that resident budget: each worker pages
  independently over the SHARED execstore, so a density fleet keeps
  one on-disk copy of every executable while each worker holds only
  its own traffic's working set on device.  ``--registry-json
  '{"pager": {...}}'`` configures the full knob set and wins over
  the env;
* ``ZOO_FLEET_WIRE=json`` — pin this worker's NEGOTIATED reply wire
  to the v1 JSON encoding (it still decodes binary requests); the
  router's per-connection ``hello`` discovers this and keeps that
  connection on JSON — the fleet-wide escape hatch for the v2 binary
  wire, and how mixed-version fleets interoperate;
* ``ZOO_FLEET_MAX_FRAME`` — frame-size cap in bytes (default 256
  MiB); an oversize REPLY degrades to a structured error envelope
  carrying ``attempted_bytes`` instead of a dropped connection;
* the port file is written ATOMICALLY once the socket is listening —
  its presence is the router's readiness signal, and a restarted
  incarnation's fresh port lands the same way.

``--fake`` serves the same protocol with zero jax WORK — stub
builders only, no backend touched, no compile ever (the package root
still imports jax; that is import cost, not compute) — so the tier-1
supervisor/router tests run the whole fan-out/retry machinery in
seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ... import envcontract
from ...observability import flightrec, tracefleet
from ...observability import trace as trace_mod
from ...observability.log import get_logger
from ...observability.metrics import MetricsRegistry
from .. import execstore
from ..metrics import registry_collector
from ..registry import ModelRegistry
from . import artifact, protocol

_slog = get_logger("zoo.serving.fleet.worker")

_HB_MIN_INTERVAL_S = 0.5
_ACCEPT_TIMEOUT_S = 0.25


class ServingWorker:
    """The worker process body (module docstring)."""

    def __init__(self, share_dir: str, registry_kwargs: Optional[dict] = None,
                 fake: bool = False):
        self.share_dir = share_dir
        self.fake = fake
        # identity from the flightrec helpers — the SAME parse that
        # names this process's recorder directory and log stamps
        self.rank = flightrec._env_rank()
        self.incarnation = flightrec._env_incarnation()
        # every worker traces: finished registry spans land in the
        # flight recorder (the configure() finish hook), tail-sampled
        # exemplars in the tracer's bounded store, and a traced
        # request's reply piggybacks its span summary back to the
        # router (reply_trace in _handle).  setdefault: registry_json
        # is parsed JSON and can never carry a live tracer, but a
        # caller constructing in-process may
        self.tracer = trace_mod.Tracer(
            capacity=512, **trace_mod.tail_config_from_env())
        reg_kwargs = dict(registry_kwargs or {})
        reg_kwargs.setdefault("tracer", self.tracer)
        self.registry = ModelRegistry(**reg_kwargs)
        self.metrics = MetricsRegistry()
        self.metrics.register_collector(registry_collector(self.registry))
        self.metrics.register_collector(self.tracer.families)
        store = None if fake else execstore.current()
        if store is not None:
            self.metrics.register_collector(store.families)
        rec = flightrec.current()
        if rec is not None:
            rec.add_collector(self.metrics.collect)
        self._hb_path = envcontract.env_str("ZOO_HEARTBEAT_FILE")
        self._hb_last = 0.0
        self._compile_events: List[str] = []
        self._compile_hooked = False
        # v2 wire ceiling this worker will NEGOTIATE down to:
        # ZOO_FLEET_WIRE=json pins the fleet to the v1 JSON wire (the
        # negotiation-fallback test hook, and the escape hatch if a
        # binary-wire bug ever ships) — the worker still DECODES
        # either encoding regardless
        self.wire_max = (protocol.WIRE_JSON
                         if envcontract.env_str("ZOO_FLEET_WIRE") == "json"
                         else protocol.WIRE_BINARY)
        # load piggyback: serve-op in-flight count plus a throttled
        # residency snapshot, attached to every reply (and ping) so
        # the router's affinity view refreshes for free on the data
        # path instead of needing a polling control op
        self._inflight = 0
        self._load_lock = threading.Lock()
        self._res_cache: tuple = (0.0, None)
        self._stop = threading.Event()
        self._listener: Optional[socket.socket] = None
        self._conn_threads: List[threading.Thread] = []
        # control ops dispatch through a table: the serve loop is a
        # zoolint hot entry, and the control plane (activate → deploy
        # → warmup) legitimately BLOCKS on compiles — the table keeps
        # cold control ops off the hot path, in the call graph the
        # analyzer sees exactly as in the code's intent
        self._control = {"activate": self._activate,
                         "promote": self._promote,
                         "undeploy": self._undeploy,
                         "ping": self._ping,
                         "metrics": self._metrics,
                         "shutdown": self._shutdown}

    # ---- supervision plumbing ----
    def _beat(self) -> None:
        if not self._hb_path:
            return
        now = time.monotonic()
        if now - self._hb_last < _HB_MIN_INTERVAL_S:
            return
        self._hb_last = now
        try:
            with open(self._hb_path, "a"):
                os.utime(self._hb_path, None)
        except OSError:
            pass  # an unwritable heartbeat must not kill serving

    def _hook_compiles(self) -> None:
        """Count ``backend_compile`` events so every activate reply can
        report exactly what XLA work it did — the cross-process
        zero-compile gate reads these numbers."""
        if self._compile_hooked or self.fake:
            return
        self._compile_hooked = True
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(
            lambda key, dur, **kw: (
                self._compile_events.append(key)
                if "backend_compile" in key else None))

    # ---- socket plumbing ----
    def bind(self) -> int:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind(("127.0.0.1", 0))
        s.listen(64)
        s.settimeout(_ACCEPT_TIMEOUT_S)
        self._listener = s
        return s.getsockname()[1]

    def serve_forever(self) -> None:
        """Accept loop (main thread): one thread per connection, a
        heartbeat touch per pass — the liveness signal the watchdog
        judges this process by."""
        assert self._listener is not None, "bind() first"
        while not self._stop.is_set():
            self._beat()
            try:
                conn, addr = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                break  # listener closed under us during shutdown
            conn.settimeout(None)
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            t.start()
            self._conn_threads.append(t)
            # reap finished handlers so a long-lived worker's thread
            # list stays bounded
            self._conn_threads = [x for x in self._conn_threads
                                  if x.is_alive()]
        try:
            self._listener.close()
        except OSError:
            pass
        self.registry.shutdown()

    def _load_snapshot(self) -> Dict[str, Any]:
        """The per-reply load piggyback: in-flight serve ops plus the
        residency list, the latter recomputed at most every ~50ms (a
        dict walk, but not per-request at fleet QPS)."""
        now = time.monotonic()
        with self._load_lock:
            out = self._inflight
            ts, res = self._res_cache
            if res is not None and now - ts <= 0.05:
                return {"o": out, "r": res}
        res = self.registry.resident_models()
        with self._load_lock:
            self._res_cache = (now, res)
            out = self._inflight
        return {"o": out, "r": res}

    def _serve_conn(self, conn: socket.socket) -> None:
        """One connection's request/reply loop (a zoolint hot entry:
        this is the per-request path).  Frame errors and hangups end
        the connection; op errors travel back as structured error
        envelopes — the connection survives a shed request.

        The reply encoding is per-connection state: JSON until the
        peer negotiates the binary wire with a ``hello`` (whose REPLY
        is still JSON — the peer does not know the verdict yet);
        requests decode as whatever they arrived as, no negotiation
        needed (the payload's first byte discriminates)."""
        wire = protocol.WIRE_JSON
        try:
            while not self._stop.is_set():
                got = protocol.recv_envelope(conn)
                if got is None:
                    return  # clean hangup
                req, _, _ = got
                rid = req.get("id")
                op = req.get("op")
                if op == "hello":
                    agreed = min(int(req.get("wire", 1)), self.wire_max)
                    protocol.send_frame(conn, {
                        "id": rid, "ok": True,
                        "result": {"wire": agreed, "rank": self.rank}})
                    wire = agreed
                    continue
                resp = self._execute(req, rid)
                resp["load"] = self._load_snapshot()
                binary = (wire == protocol.WIRE_BINARY
                          and op in ("predict", "generate"))
                try:
                    protocol.send_envelope(conn, resp, binary=binary)
                except (TypeError, ValueError,
                        protocol.FrameError) as e:
                    # an unserializable or oversized RESULT must
                    # degrade to an error reply, not a dead connection
                    # the router reads as a worker crash (and retries
                    # into, killing a sibling with the same reply).
                    # Safe to send a second frame: both failures fire
                    # BEFORE any bytes hit the socket — a mid-send
                    # OSError stays fatal for exactly that reason.
                    err = {"error": type(e).__name__,
                           "message": f"unserializable response: {e}"}
                    attempted = getattr(e, "attempted_bytes", None)
                    if attempted is not None:
                        err["attempted_bytes"] = attempted
                        err["max_frame_bytes"] = \
                            protocol.max_frame_bytes()
                    protocol.send_frame(conn, {
                        "id": rid, "ok": False,
                        "load": self._load_snapshot(), "error": err})
                if op == "shutdown":
                    self._stop.set()
                    return
        except (protocol.FrameError, OSError):
            pass  # dropped peer: the router already treats it as dead
        finally:
            try:
                conn.close()
            except OSError:
                pass

    # ---- ops ----
    def _execute(self, req: Dict[str, Any],
                 rid: Any) -> Dict[str, Any]:
        """One op, balanced: the in-flight count rides every exit
        explicitly (the PR 6 seat-leak discipline, zoolint ZL702 —
        which is also why this lives OUTSIDE _serve_conn's transport
        try: a nested protected region would hide the balance from
        the exception-path CFG).  In-flight covers every op uniformly
        (control ops are rare and brief) with deliberately LOCK-FREE
        bare updates — the piggyback is a load HINT, the router's own
        outstanding count is the scheduling truth."""
        try:
            self._inflight += 1
            result = self._handle(req)
        except BaseException as e:  # noqa: BLE001 — every op failure
            # becomes a structured envelope; the router re-raises the
            # concrete class
            self._inflight -= 1
            return {"id": rid, "ok": False,
                    "error": protocol.encode_error(e)}
        else:
            self._inflight -= 1
            return {"id": rid, "ok": True, **result}

    def _handle(self, req: Dict[str, Any]) -> Dict[str, Any]:
        op = req.get("op")
        if op == "predict":
            x = protocol.decode_value(req["inputs"])
            out, info = self.registry.predict_ex(
                req["model"], x,
                deadline_ms=req.get("deadline_ms"),
                trace_id=req.get("trace_id"),
                priority_class=req.get("priority_class"))
            # results stay RAW arrays: send_envelope owns the encoding
            # (binary hoists them out-of-band; JSON b64s them) — a
            # pre-encoded __nd__ dict would ride the binary wire as
            # base64 TEXT and throw the savings away
            return self._serve_result(out, info, req.get("trace_id"))
        if op == "generate":
            prompts = protocol.decode_value(req["prompt_ids"])
            # sampling params cross the wire as json scalars; the same
            # (prompt, sampling, seed) through any worker of this
            # artifact replays the single-process registry's tokens
            # bit-exactly (the engine's fold_in RNG is process-free)
            out, info = self.registry.generate_ex(
                req["model"], prompts, req["max_new_tokens"],
                deadline_ms=req.get("deadline_ms"),
                trace_id=req.get("trace_id"),
                priority_class=req.get("priority_class"),
                eos_id=req.get("eos_id"),
                temperature=req.get("temperature", 0.0),
                top_k=req.get("top_k"), top_p=req.get("top_p"),
                seed=req.get("seed", 0))
            return self._serve_result(out, info, req.get("trace_id"))
        fn = self._control.get(op)
        if fn is None:
            raise ValueError(f"unknown op {op!r}")
        return fn(req)

    def _serve_result(self, out, info, trace_id) -> Dict[str, Any]:
        """Package a serve-op result, piggybacking the worker-side
        span summary when the request carried a ``trace_id`` — the
        trace twin of the ``load`` residency piggyback, so the router
        stitches the worker timeline under its ``worker_call`` with
        no extra round trip.  Untraced requests pay one None check."""
        resp: Dict[str, Any] = {"result": out, "info": info}
        if trace_id is not None:
            t = tracefleet.reply_trace(self.tracer, trace_id,
                                       rank=self.rank,
                                       inc=self.incarnation)
            if t is not None:
                resp["trace"] = t
        return resp

    def _promote(self, req: Dict[str, Any]) -> Dict[str, Any]:
        return {"result": {"version": self.registry.promote(
            req["model"])}}

    def _undeploy(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """Retire one model: drain + close in the registry, which also
        detaches it from the pager and drops its spans — the worker's
        next scrape carries none of its series (the registry snapshot
        is the collector), so a cycling density fleet's exposition
        stays bounded by what is DEPLOYED, not by what ever was."""
        drained = self.registry.undeploy(
            req["model"],
            drain_timeout=float(req.get("drain_timeout", 10.0)))
        return {"result": {"model": req["model"], "drained": drained,
                           "rank": self.rank}}

    def _ping(self, req: Dict[str, Any]) -> Dict[str, Any]:
        return {"result": {"pid": os.getpid(), "rank": self.rank,
                           "incarnation": self.incarnation,
                           "models": self.registry.models()}}

    def _metrics(self, req: Dict[str, Any]) -> Dict[str, Any]:
        return {"result": {"text": self.metrics.render_prometheus()}}

    def _shutdown(self, req: Dict[str, Any]) -> Dict[str, Any]:
        return {"result": {"stopping": True}}

    def _activate(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """Warm-before-swap activation of one committed artifact
        version: build from the share, warm to completion (execstore
        read-through — zero compiles when the store already holds this
        fingerprint), then the registry's atomic pointer swap.  The
        old version keeps serving until the swap, so a rolling upgrade
        never shows this worker cold."""
        self._hook_compiles()
        model, version = req["model"], int(req["version"])
        spec, params = artifact.load(self.share_dir, model, version)
        kwargs = artifact.build_deploy_kwargs(spec, params)
        if req.get("canary_fraction") is not None:
            kwargs["canary_fraction"] = req["canary_fraction"]
        store = None if self.fake else execstore.current()
        s0 = store.stats() if store is not None else {}
        c0, t0 = len(self._compile_events), time.perf_counter()
        v = self.registry.deploy(model, version=version, **kwargs)
        warm_ms = round((time.perf_counter() - t0) * 1e3, 3)
        compiles = len(self._compile_events) - c0
        # the store hit/miss DELTA is the authoritative warm/cold
        # verdict for this activation: a decode-capable deployment
        # always fires a few trivial fill "compiles" allocating its
        # slot-array state (seen at PR 8 — state allocation, not
        # plan compilation), so misses==0 is the cross-process
        # zero-PLAN-compile claim; the raw compile count stays exact
        # for pure predict-plane deploys
        s1 = store.stats() if store is not None else {}
        hits = s1.get("hit", 0) - s0.get("hit", 0)
        misses = s1.get("miss", 0) - s0.get("miss", 0)
        _slog.info("fleet_activate", model=model, version=v,
                   compiles=compiles, warm_ms=warm_ms, rank=self.rank,
                   store_hits=hits, store_misses=misses)
        return {"result": {"version": v, "compiles": compiles,
                           "store_hits": hits, "store_misses": misses,
                           "warm_ms": warm_ms, "rank": self.rank}}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m analytics_zoo_tpu.serving.fleet.worker",
        description="fleet serving worker (module docstring)")
    ap.add_argument("--share", required=True,
                    help="shared fleet directory (artifacts live under "
                         "deploys/, the execstore wherever "
                         "ZOO_EXECSTORE_DIR points)")
    ap.add_argument("--port-file", required=True,
                    help="written atomically with the bound port once "
                         "the worker is listening (readiness signal)")
    ap.add_argument("--registry-json", default=None,
                    help="ModelRegistry kwargs as JSON")
    ap.add_argument("--fake", action="store_true",
                    help="serve stub builders only, never import jax "
                         "(test mode)")
    args = ap.parse_args(argv)

    flightrec.install_from_env()
    reg_kwargs = json.loads(args.registry_json) if args.registry_json \
        else {}
    pager_env = envcontract.env_str("ZOO_PAGER_RESIDENT")
    if pager_env and "pager" not in reg_kwargs:
        try:
            reg_kwargs["pager"] = {"max_resident": int(pager_env)}
        except ValueError:
            _slog.error("fleet_worker_bad_pager_env", value=pager_env)
    worker = ServingWorker(args.share, registry_kwargs=reg_kwargs,
                           fake=args.fake)
    if not args.fake:
        # touch jax early so import cost lands before readiness, and
        # the compile listener sees every event from the first activate
        worker._hook_compiles()
    port = worker.bind()
    flightrec.atomic_write(args.port_file, str(port))
    _slog.info("fleet_worker_up", rank=worker.rank,
               incarnation=worker.incarnation, port=port,
               fake=worker.fake, pid=os.getpid())
    try:
        worker.serve_forever()
    finally:
        flightrec.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
