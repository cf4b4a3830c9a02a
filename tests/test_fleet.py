"""Fleet serving: frame-protocol codec (torn-write/short-read/CRC
behavior, error envelope fidelity, array bit-exactness), the committed
deploy artifact, and the supervisor/router machinery driven through
REAL worker processes in ``--fake`` mode (no jax): deploy fan-out
ordering, least-outstanding routing, retry-on-dead-worker,
crash-restart with version replay, priority-class pass-through, and
the rank-merged fleet scrape.  Fake mode does zero jax work (stub
data plane — no backend, no compiles), so these stay fast; the
jax-real end is the two tests that start workers with ``fake=False``:
cross-process generate determinism, and (``slow``) the warm fan-out
of real deploys answered as one process answers."""

import json
import os
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

from analytics_zoo_tpu.serving import (ColdStartTimeout,
                                       DeadlineExceeded, DeployError,
                                       ModelNotFound, Overloaded,
                                       ServingError)
from analytics_zoo_tpu.serving.fleet import (FleetRouter,
                                             WorkerUnavailable,
                                             artifact, protocol)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STUB = "analytics_zoo_tpu.serving.fleet.builders:stub"


# ------------------------------------------------------------ protocol
def _pair():
    a, b = socket.socketpair()
    a.settimeout(5)
    b.settimeout(5)
    return a, b


def test_frame_roundtrip_and_arrays():
    a, b = _pair()
    try:
        ints = np.arange(12, dtype=np.int16).reshape(3, 4)
        x = ints.astype(np.float32)
        x[0, 0] = np.nan  # bit-exact means NaN payload bits too
        obj = {"op": "predict", "id": 7, "nested": [1, "s", None],
               "inputs": protocol.encode_value(x),
               "many": protocol.encode_value([ints, {"k": x}])}
        protocol.send_frame(a, obj)
        got = protocol.recv_frame(b)
        assert got["op"] == "predict" and got["id"] == 7
        y = protocol.decode_value(got["inputs"])
        assert y.dtype == np.float32 and y.shape == (3, 4)
        assert np.array_equal(y, x, equal_nan=True)
        many = protocol.decode_value(got["many"])
        assert many[0].dtype == np.int16
        assert np.array_equal(many[1]["k"], x, equal_nan=True)
    finally:
        a.close()
        b.close()


def test_clean_eof_between_frames_is_none():
    a, b = _pair()
    protocol.send_frame(a, {"id": 1})
    a.close()
    try:
        assert protocol.recv_frame(b) == {"id": 1}
        assert protocol.recv_frame(b) is None  # hangup, not an error
    finally:
        b.close()


def test_torn_frame_raises():
    """EOF mid-payload (a worker SIGKILLed mid-sendall's buffered
    bytes) is a FrameError, never a short JSON parsed as truth."""
    a, b = _pair()
    payload = json.dumps({"id": 2, "big": "x" * 64}).encode()
    frame = struct.pack("<II", len(payload),
                        zlib.crc32(payload) & 0xffffffff) + payload
    a.sendall(frame[:len(frame) - 10])  # torn: 10 bytes never arrive
    a.close()
    try:
        with pytest.raises(protocol.FrameError, match="short read"):
            protocol.recv_frame(b)
    finally:
        b.close()


def test_torn_header_raises():
    a, b = _pair()
    a.sendall(b"\x05\x00")  # 2 of 8 header bytes
    a.close()
    try:
        with pytest.raises(protocol.FrameError, match="short read"):
            protocol.recv_frame(b)
    finally:
        b.close()


def test_crc_mismatch_and_oversize_raise():
    a, b = _pair()
    payload = b'{"id": 3}'
    a.sendall(struct.pack("<II", len(payload), 12345) + payload)
    try:
        with pytest.raises(protocol.FrameError, match="CRC"):
            protocol.recv_frame(b)
    finally:
        a.close()
        b.close()
    a, b = _pair()
    a.sendall(struct.pack("<II", protocol.MAX_FRAME_BYTES + 1, 0))
    try:
        with pytest.raises(protocol.FrameError, match="exceeds"):
            protocol.recv_frame(b)
    finally:
        a.close()
        b.close()


# --------------------------------------------------- binary wire (v2)
def test_binary_payload_roundtrip_zero_copy():
    """The v2 binary payload: nested envelopes with arrays hoisted
    out-of-band round-trip bit-exactly (NaN and -0.0 payload bits
    included), and the decode side is ZERO-copy — every array comes
    back as a read-only view over the received buffer."""
    ints = np.arange(10, dtype=np.int16).reshape(2, 5)
    x = ints.astype(np.float32)
    x[0, 0] = np.nan
    x[1, 1] = -0.0
    obj = {"op": "predict", "id": 9, "inputs": x,
           "nested": {"deep": [ints, {"k": x}], "s": "txt", "n": None},
           "empty": np.zeros((0, 3), dtype=np.float64)}
    payload = protocol.encode_binary(obj)
    assert payload.startswith(protocol.BIN_MAGIC)
    back = protocol.decode_binary(payload)
    assert back["op"] == "predict" and back["id"] == 9
    y = back["inputs"]
    assert y.dtype == np.float32 and y.shape == (2, 5)
    assert y.tobytes() == x.tobytes()  # NaN/-0.0 bits survive
    assert back["nested"]["deep"][0].dtype == np.int16
    assert np.array_equal(back["nested"]["deep"][0], ints)
    assert back["nested"]["s"] == "txt" and back["nested"]["n"] is None
    assert back["empty"].shape == (0, 3)
    # zero-copy: views over the payload buffer, not owned copies
    assert y.base is not None and not y.flags.writeable
    # and the whole point: binary beats the b64 JSON encoding on size
    as_json = json.dumps(protocol.encode_value(obj),
                         separators=(",", ":")).encode()
    assert len(payload) < len(as_json)


def test_binary_envelope_over_socket_first_byte_discriminates():
    """recv_envelope reads EITHER encoding on the same connection with
    no negotiation (0xff can never begin a JSON text) and reports the
    frame's encoding + wire bytes — the byte-accounting feed."""
    a, b = _pair()
    try:
        x = np.arange(24, dtype=np.float64).reshape(4, 6)
        n_tx = protocol.send_envelope(a, {"id": 1, "inputs": x},
                                      binary=True)
        env, n_rx, enc = protocol.recv_envelope(b)
        assert enc == "binary" and n_rx == n_tx
        assert np.array_equal(env["inputs"], x)
        # same socket, JSON frame next — arrays still materialize
        n_tx = protocol.send_envelope(a, {"id": 2, "inputs": x},
                                      binary=False)
        env, n_rx, enc = protocol.recv_envelope(b)
        assert enc == "json" and n_rx == n_tx
        assert np.array_equal(env["inputs"], x)
    finally:
        a.close()
        b.close()


def test_binary_torn_mid_buffer_and_crc_raise():
    """A worker SIGKILLed mid-sendall of a binary frame leaves a torn
    frame; a flipped bit in the raw buffer region is a CRC conviction
    — both are FrameError, never a short array parsed as truth."""
    payload = protocol.encode_binary(
        {"id": 4, "x": np.arange(1024, dtype=np.float64)})
    frame = struct.pack("<II", len(payload),
                        zlib.crc32(payload) & 0xffffffff) + payload
    a, b = _pair()
    a.sendall(frame[:len(frame) - 100])  # torn inside the buffer
    a.close()
    try:
        with pytest.raises(protocol.FrameError, match="short read"):
            protocol.recv_envelope(b)
    finally:
        b.close()
    a, b = _pair()
    a.sendall(frame[:-1] + bytes([frame[-1] ^ 0xFF]))
    try:
        with pytest.raises(protocol.FrameError, match="CRC"):
            protocol.recv_envelope(b)
    finally:
        a.close()
        b.close()


def test_binary_garbage_header_is_frame_error():
    bad = protocol.BIN_MAGIC + struct.pack("<I", 999999) + b"{}"
    with pytest.raises(protocol.FrameError, match="binary"):
        protocol.decode_binary(bad)


def test_env_frame_cap_and_attempted_bytes(monkeypatch):
    """ZOO_FLEET_MAX_FRAME caps both directions; the oversize-SEND
    flavor carries attempted_bytes and fires before any bytes hit the
    socket, so the connection survives (the worker's degrade-to-error
    path depends on exactly this)."""
    monkeypatch.setenv("ZOO_FLEET_MAX_FRAME", "64")
    assert protocol.max_frame_bytes() == 64
    a, b = _pair()
    try:
        with pytest.raises(protocol.FrameError) as ei:
            protocol.send_envelope(
                a, {"id": 1, "x": np.zeros(64)}, binary=True)
        assert ei.value.attempted_bytes is not None
        assert ei.value.attempted_bytes > 64
        with pytest.raises(protocol.FrameError) as ei:
            protocol.send_frame(a, {"id": 1, "pad": "y" * 64})
        assert ei.value.attempted_bytes is not None
        # no bytes ever hit the socket: it still carries frames once
        # the cap allows them
        monkeypatch.setenv("ZOO_FLEET_MAX_FRAME", "1048576")
        protocol.send_envelope(a, {"id": 2}, binary=False)
        assert protocol.recv_envelope(b)[0] == {"id": 2}
    finally:
        a.close()
        b.close()
    # receive side: an oversized length prefix is convicted BEFORE
    # allocating the claimed payload
    monkeypatch.setenv("ZOO_FLEET_MAX_FRAME", "64")
    a, b = _pair()
    a.sendall(struct.pack("<II", 100, 0))
    try:
        with pytest.raises(protocol.FrameError, match="exceeds"):
            protocol.recv_frame(b)
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("exc,code,detail", [
    (Overloaded("queue full", evicted=True, queue_depth=64),
     "Overloaded", ("evicted", True)),
    (DeadlineExceeded("hopeless", shed=True, predicted_ms=12.5),
     "DeadlineExceeded", ("shed", True)),
    (ModelNotFound("no such model", model="nope"),
     "ModelNotFound", ("model", "nope")),
    (DeployError("warmup blew up", model="m", version=3),
     "DeployError", ("version", 3)),
    # a worker's cold-start SLO miss crosses as the concrete 503 —
    # and as a ServingError it is NEVER retried on a sibling, so one
    # slow fault cannot fan out into every worker faulting the model
    (ColdStartTimeout("cold past deadline", model="m",
                      waited_ms=52.1),
     "ColdStartTimeout", ("waited_ms", 52.1)),
])
def test_error_envelope_fidelity(exc, code, detail):
    """A serving error crossing the wire reconstructs the CONCRETE
    class with message, details, and http_status intact."""
    back = protocol.decode_error(protocol.encode_error(exc))
    assert type(back) is type(exc)
    assert back.code == code
    assert back.message == exc.message
    k, v = detail
    assert back.details[k] == v
    assert back.http_status == exc.http_status


def test_unknown_error_code_degrades_to_serving_error():
    back = protocol.decode_error(
        protocol.encode_error(ValueError("bad rows")))
    assert isinstance(back, ServingError)
    assert back.details["error"] == "ValueError"
    assert "bad rows" in back.message


# ------------------------------------------------------------ artifact
def test_artifact_commit_point_is_the_spec(tmp_path):
    share = str(tmp_path)
    w = {"w0": np.arange(4, dtype=np.float32)}
    d = artifact.publish(share, "m", 1, w, {"builder": STUB})
    assert artifact.versions(share, "m") == {1: d}
    # an in-flight publish (weights landed, spec not yet) is invisible
    os.makedirs(os.path.join(artifact.deploys_root(share), "m", "v2"))
    assert artifact.versions(share, "m") == {1: d}
    spec, params = artifact.load(share, "m", 1)
    assert spec["builder"] == STUB and spec["version"] == 1
    assert np.array_equal(params["w0"], w["w0"])
    with pytest.raises(ValueError, match="invalid model name"):
        artifact.publish(share, "../evil", 1, None, {"builder": STUB})


# ------------------------------------------------- fake-worker fleet
@pytest.fixture
def make_fleet(tmp_path):
    routers = []

    def make(n_workers=2, registry_kwargs=None, env=None, **kw):
        kw.setdefault("max_restarts", 2)
        kw.setdefault("restart_backoff", 0.2)
        worker_env = {"PYTHONPATH": REPO}
        worker_env.update(env or {})
        r = FleetRouter(str(tmp_path / "share"), n_workers=n_workers,
                        fake=True, registry_kwargs=registry_kwargs,
                        env=worker_env, **kw)
        r.start(timeout=60)
        routers.append(r)
        return r

    yield make
    for r in routers:
        r.close()


def _wait(cond, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def test_deploy_predict_roundtrip_and_fanout_ordering(make_fleet):
    """Deploy fans out ONE worker at a time in rank order (rolling by
    construction: activation k+1 starts only after k completed), and
    the served result is bit-exact for the version the info names."""
    r = make_fleet(n_workers=2)
    rep = r.deploy("m", None, STUB, builder_args={"scale": 2.0})
    acts = rep["activations"]
    assert [a["rank"] for a in acts] == [0, 1]
    assert all("error" not in a for a in acts)
    assert acts[0]["t_end"] <= acts[1]["t_start"]  # non-overlapping
    x = np.arange(6, dtype=np.float64).reshape(2, 3)
    out, info = r.predict_ex("m", x)
    assert info["model"] == "m" and info["version"] == 1
    assert np.array_equal(out, x * 2.0)
    # second version: both workers swap, traffic follows
    r.deploy("m", None, STUB, builder_args={"scale": 3.0})
    out, info = r.predict_ex("m", x)
    assert info["version"] == 2 and np.array_equal(out, x * 3.0)


def test_undeploy_retires_fleet_series_and_serving(make_fleet):
    """Router undeploy fans out to every worker AND retires the
    model's fleet-level series: the per-(model, version) fan-out
    gauge and the active map are dropped (a density fleet cycling
    many models must not grow the scrape one dead series per deploy
    forever), workers stop serving it, and the surviving model is
    untouched."""
    r = make_fleet(n_workers=2)
    r.deploy("gone", None, STUB, builder_args={"scale": 2.0})
    r.deploy("kept", None, STUB, builder_args={"scale": 3.0})
    x = np.ones((1, 4))
    assert np.array_equal(r.predict_ex("gone", x)[0], x * 2.0)
    fams = {f.name: f for f in r.families()}
    fanout = fams["zoo_fleet_deploy_fanout_seconds"]
    assert {s[0]["model"] for s in fanout.samples} == {"gone", "kept"}
    rep = r.undeploy("gone")
    assert [a["rank"] for a in rep["activations"]] == [0, 1]
    assert all(a["model"] == "gone" for a in rep["activations"])
    with pytest.raises(ModelNotFound):
        r.predict_ex("gone", x)
    # fleet series retired; the survivor keeps serving and scraping
    fams = {f.name: f for f in r.families()}
    fanout = fams["zoo_fleet_deploy_fanout_seconds"]
    assert {s[0]["model"] for s in fanout.samples} == {"kept"}
    assert np.array_equal(r.predict_ex("kept", x)[0], x * 3.0)
    # the worker-side scrape dropped the model too (the registry
    # snapshot is the collector — nothing lingers after undeploy)
    from analytics_zoo_tpu.observability.metrics import \
        parse_prometheus_text
    parsed = parse_prometheus_text(r.metrics_text())
    models = {dict(k[1]).get("model") for k in parsed["samples"]}
    assert "gone" not in models and "kept" in models


def test_router_retries_once_on_worker_death_mid_request(make_fleet):
    """The deterministic mid-request death (stub die_after kills the
    PROCESS before replying): the router must complete every request
    on the sibling, count the retry, and the supervisor must restart
    + replay the dead worker."""
    r = make_fleet(n_workers=2)
    r.deploy("m", None, STUB,
             builder_args={"scale": 1.0, "die_after": 3,
                           "die_rank": 1})
    x = np.ones((1, 4))
    for _ in range(10):
        out, _ = r.predict_ex("m", x)
        assert np.array_equal(out, x)  # zero failed requests
    assert r.retries_total == 1
    # the corpse was harvested and the replacement replayed the
    # current version set before rejoining the rotation
    assert _wait(lambda: r.supervisor.postmortems
                 and r.states().get("live") == 2)
    assert r.ping(1)["incarnation"] == 1
    assert r.ping(1)["models"] == {"m": 1}
    assert r.replays[1] == [
        {"model": "m", "version": 1, "compiles": 0,
         "store_hits": 0, "store_misses": 0,
         "warm_ms": r.replays[1][0]["warm_ms"], "rank": 1}]
    pm_path = r.supervisor.postmortems[0]
    with open(pm_path) as f:
        pm = json.load(f)
    assert pm["failed_rank"] == 1 and pm["reason"] == "exit"
    assert pm["ranks"]["1"]["rc"] == 17


def test_transient_timeout_unroutes_then_revives(make_fleet):
    """A request tripping the call timeout on a HEALTHY worker (slow
    model, not a death) unroutes it only transiently: the detached
    revival probe pings it back into rotation — no restart, no
    postmortem, same incarnation."""
    r = make_fleet(n_workers=2, call_timeout_s=0.3)
    r.deploy("fast", None, STUB)
    r.deploy("slow", None, STUB, builder_args={"delay_s": 0.8})
    with pytest.raises(ConnectionError):
        r.predict_ex("slow", np.ones((1, 2)))
    # the picked worker was unrouted by the timeout, but it never
    # died — the revival probe must restore it
    assert _wait(lambda: all(h.routable for h in r.handles),
                 timeout=10)
    out, _ = r.predict_ex("fast", np.ones((1, 2)))
    assert np.array_equal(out, np.ones((1, 2)))
    assert r.supervisor.postmortems == []
    assert [r.ping(rk)["incarnation"] for rk in (0, 1)] == [0, 0]


def test_all_workers_dead_raises_worker_unavailable(make_fleet):
    r = make_fleet(n_workers=1, max_restarts=0)
    r.deploy("m", None, STUB)
    r.supervisor.kill(0)
    assert _wait(lambda: r.states().get("dead") == 1)
    with pytest.raises(WorkerUnavailable) as ei:
        r.predict_ex("m", np.ones((1, 2)))
    assert ei.value.http_status == 503
    assert ei.value.details["states"]["dead"] == 1


def test_priority_class_and_structured_errors_cross_process(make_fleet):
    """The admission envelope survives the hop: a priority class tags
    the worker-side controller's counters, and a predictive deadline
    shed comes back as DeadlineExceeded(shed=True) — details intact."""
    r = make_fleet(
        n_workers=1,
        registry_kwargs={"priority_classes": {"gold": [10, 0.9]},
                         "max_queue": 8, "max_concurrency": 1})
    r.deploy("m", None, STUB, builder_args={"delay_s": 0.05})
    x = np.ones((1, 2))
    out, _ = r.predict_ex("m", x, priority_class="gold")
    assert np.array_equal(out, x)
    # the 50ms EWMA is seeded: a 1ms deadline is predictively hopeless
    with pytest.raises(DeadlineExceeded) as ei:
        r.predict_ex("m", x, deadline_ms=1.0, priority_class="gold")
    assert ei.value.details.get("shed") is True
    # the class rode admission on the WORKER: its counters prove it
    from analytics_zoo_tpu.observability.metrics import \
        parse_prometheus_text
    s = parse_prometheus_text(r.metrics_text())["samples"]
    assert s[("zoo_class_admitted_total",
              (("class", "gold"), ("model", "m"),
               ("rank", "0")))] == 1.0
    assert s[("zoo_shed_total",
              (("class", "gold"), ("model", "m"),
               ("rank", "0")))] == 1.0


def test_fleet_scrape_merges_ranks_and_fleet_families(make_fleet):
    """Router /metrics = every worker's exposition rank-labeled and
    merged (counters gain a rank-less fleet total) + the router's own
    zoo_fleet_* families."""
    from analytics_zoo_tpu.observability.metrics import \
        parse_prometheus_text
    r = make_fleet(n_workers=2)
    r.deploy("m", None, STUB)
    x = np.ones((1, 2))
    for _ in range(4):
        r.predict("m", x)
    parsed = parse_prometheus_text(r.metrics_text())
    s = parsed["samples"]
    assert parsed["types"]["zoo_fleet_workers"] == "gauge"
    assert s[("zoo_fleet_workers", (("state", "live"),))] == 2
    assert s[("zoo_fleet_workers", (("state", "dead"),))] == 0
    assert parsed["types"]["zoo_fleet_router_retries_total"] \
        == "counter"
    assert s[("zoo_fleet_router_retries_total", ())] == 0
    assert s[("zoo_fleet_deploy_fanout_seconds",
              (("model", "m"), ("version", "1")))] >= 0
    # per-rank requests + the rank-less fleet total summing them
    per_rank = [s.get(("zoo_model_requests_total",
                       (("model", "m"), ("rank", str(rk)),
                        ("version", "1")))) for rk in (0, 1)]
    total = s[("zoo_model_requests_total",
               (("model", "m"), ("version", "1")))]
    assert sum(v for v in per_rank if v is not None) == total == 4.0


def test_distributed_trace_stitches_across_processes(make_fleet):
    """A traced fleet request piggybacks the worker span on the reply
    (router span gains children + fleet_gap_ms), the exemplar family
    rides the router scrape rank-labeled, and the offline stitcher
    reassembles the same request from the supervisor's flight dir."""
    from analytics_zoo_tpu.observability import tracefleet
    from analytics_zoo_tpu.observability.trace import Tracer
    r = make_fleet(n_workers=2)
    r.tracer = Tracer(capacity=64, tail_quantile=0.5, tail_cap=8)
    r.deploy("m", None, STUB, builder_args={"scale": 2.0})
    x = np.ones((1, 2))
    infos = [r.predict_ex("m", x)[1] for _ in range(4)]
    assert all("request_id" in info for info in infos)
    assert any("fleet_gap_ms" in info for info in infos)
    tid = infos[-1]["request_id"]
    sd = r.tracer.find(tid)
    ch = sd["children"]
    assert len(ch) == 1 and ch[0]["tid"] == tid
    assert ch[0]["rank"] in (0, 1) and ch[0]["phases"]
    # the worker leg landed in that rank's flight recorder too: the
    # offline join reproduces the inline picture from disk alone
    flight = r.supervisor.flight_dir()
    assert _wait(lambda: tracefleet.harvest_legs(flight, trace_id=tid))
    st = tracefleet.stitch(sd, tracefleet.harvest_legs(flight,
                                                       trace_id=tid))
    assert st["stitched_legs"] == 1 and st["monotonic"]
    assert not st["partial"]
    assert st["attributed_fraction"] > 0.5
    # exemplars scrape through the router, stamped rank="router"
    text = r.metrics_text()
    assert 'zoo_trace_spans_total{rank="router"}' in text
    assert "zoo_trace_exemplar_ms" in text


def test_restarted_router_never_reuses_versions(tmp_path):
    """Auto-versioning is seeded from the COMMITTED artifacts on
    disk: a second router lifetime over the same share continues the
    version sequence instead of overwriting v1 (committed artifacts
    are immutable — long-running workers replay from them)."""
    share = str(tmp_path / "share")
    env = {"PYTHONPATH": REPO}
    r1 = FleetRouter(share, n_workers=1, fake=True, env=env)
    try:
        r1.start(timeout=60)
        assert r1.deploy("m", None, STUB)["version"] == 1
    finally:
        r1.close()
    r2 = FleetRouter(share, n_workers=1, fake=True, env=env)
    try:
        r2.start(timeout=60)
        assert r2.deploy("m", None, STUB)["version"] == 2
        assert sorted(artifact.versions(share, "m")) == [1, 2]
        out, info = r2.predict_ex("m", np.ones((1, 2)))
        assert info["version"] == 2
    finally:
        r2.close()


def test_least_outstanding_spreads_and_ping(make_fleet):
    """Sequential requests against idle workers rotate (ties rotate
    round-robin), so both workers serve; ping reports identity."""
    r = make_fleet(n_workers=2)
    r.deploy("m", None, STUB)
    x = np.ones((2, 2))
    for _ in range(8):
        r.predict("m", x)
    served = [r.ping(rk)["models"] for rk in (0, 1)]
    assert served == [{"m": 1}, {"m": 1}]
    from analytics_zoo_tpu.observability.metrics import \
        parse_prometheus_text
    s = parse_prometheus_text(r.metrics_text())["samples"]
    counts = [s.get(("zoo_model_requests_total",
                     (("model", "m"), ("rank", str(rk)),
                      ("version", "1")))) for rk in (0, 1)]
    assert all(c and c >= 3 for c in counts), counts


# ----------------------------------------------- fleet v2 (fake mode)
def test_binary_wire_shrinks_bytes_and_stays_bit_exact(make_fleet):
    """The negotiated binary wire vs the JSON wire, A/B on one fleet:
    identical results bit-for-bit, measurably fewer bytes on both
    directions (b64 alone is +33%), counted per (direction, encoding)
    — and the worker's load piggyback populates the router's residency
    view on the data path."""
    r = make_fleet(n_workers=1)
    r.deploy("m", None, STUB, builder_args={"scale": 3.0})
    x = np.arange(64 * 64, dtype=np.float64).reshape(64, 64) / 7.0
    wb0 = r.wire_bytes
    out_bin, _ = r.predict_ex("m", x)
    wb1 = r.wire_bytes
    bin_tx = wb1.get(("tx", "binary"), 0) - wb0.get(("tx", "binary"), 0)
    bin_rx = wb1.get(("rx", "binary"), 0) - wb0.get(("rx", "binary"), 0)
    assert bin_tx > 0 and bin_rx > 0
    # the reply's piggyback refreshed residency lock-free
    assert "m" in r.handles[0].resident
    r.set_wire("json")
    out_json, _ = r.predict_ex("m", x)
    wb2 = r.wire_bytes
    json_tx = wb2.get(("tx", "json"), 0) - wb1.get(("tx", "json"), 0)
    json_rx = wb2.get(("rx", "json"), 0) - wb1.get(("rx", "json"), 0)
    assert np.array_equal(out_bin, x * 3.0)
    assert np.asarray(out_bin).tobytes() == np.asarray(out_json).tobytes()
    # same request, same reply: the binary frames are >20% smaller
    assert json_tx > bin_tx * 1.2, (json_tx, bin_tx)
    assert json_rx > bin_rx * 1.2, (json_rx, bin_rx)


def test_wire_negotiation_falls_back_to_json_pinned_worker(make_fleet):
    """ZOO_FLEET_WIRE=json pins the worker's negotiated ceiling to v1:
    the router's hello lands on the pinned worker, the connection
    stays on JSON, traffic still serves bit-exactly, and every frame
    is accounted under encoding=json — mixed fleets interoperate."""
    r = make_fleet(n_workers=1, env={"ZOO_FLEET_WIRE": "json"})
    r.deploy("m", None, STUB, builder_args={"scale": 2.0})
    x = np.arange(32, dtype=np.float64).reshape(4, 8)
    out, _ = r.predict_ex("m", x)
    assert np.array_equal(out, x * 2.0)
    wb = r.wire_bytes
    assert wb[("tx", "json")] > 0 and wb[("rx", "json")] > 0
    assert not any(enc == "binary" for _, enc in wb)


def test_affinity_scoring_prefers_resident_worker(make_fleet):
    """Residency-weighted scheduling: a worker holding the model wins
    until it is ``affinity_penalty`` requests deeper than a sibling
    (soft pin — load can override), outcomes counted hit/miss/cold
    and exposed as zoo_fleet_affinity_total."""
    r = make_fleet(n_workers=2)  # default affinity_penalty=4
    h0, h1 = r.handles
    h1.resident = frozenset({"m"})
    # the resident worker wins while its load gap stays under the
    # penalty: 4 consecutive picks, no releases, all hits
    for _ in range(4):
        assert r._pick(model="m") is h1
    # at outstanding=4 the non-resident sibling ties (0 + penalty)
    # and the rotation sends the overflow there: a counted miss
    assert r._pick(model="m") is h0
    # nobody holds this one: somebody must fault it — cold
    r._pick(model="other")
    assert r.affinity_counts == {"hit": 4, "miss": 1, "cold": 1}
    # the retry re-pick is count=False: one request, one outcome
    r._pick(model="m", count=False)
    assert r.affinity_counts == {"hit": 4, "miss": 1, "cold": 1}
    fams = {f.name: f for f in r.families()}
    aff = {s[0]["outcome"]: s[1]
           for s in fams["zoo_fleet_affinity_total"].samples}
    assert aff == {"hit": 4, "miss": 1, "cold": 1}
    assert "zoo_fleet_wire_bytes_total" in fams


def test_router_coalesces_concurrent_predicts(make_fleet):
    """Cross-process coalescing: concurrent compatible predicts merge
    into ONE wire request (leader concatenates, serves, splits), each
    caller gets its own rows bit-exactly, and the merged ride is
    visible in info["coalesced"]."""
    r = make_fleet(n_workers=1, coalesce_ms=40.0)
    r.deploy("m", None, STUB, builder_args={"scale": 2.0})
    xs = [np.full((2, 4), float(i)) for i in range(3)]
    outs = [None] * 3
    infos = [None] * 3
    errs = []

    def call(i):
        try:
            outs[i], infos[i] = r.predict_ex("m", xs[i])
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
        time.sleep(0.005)  # land inside the leader's window
    for t in threads:
        t.join()
    assert errs == []
    for i in range(3):
        assert np.array_equal(outs[i], xs[i] * 2.0), i
    # at least the riders saw the merged batch
    merged = [inf.get("coalesced") for inf in infos
              if inf.get("coalesced")]
    assert merged and max(merged) >= 4  # >= leader rows + one rider


def test_elastic_scale_down_drains_then_scale_up_revives(make_fleet):
    """The elastic pool round trip under live traffic: scale-down
    latches + drains the victims (zero dropped requests, zero
    postmortems — deliberate retirement, not an incident), scale-up
    revives the retired slots as fresh incarnations that replay the
    version set warm before turning routable."""
    r = make_fleet(n_workers=3)
    r.deploy("m", None, STUB,
             builder_args={"scale": 2.0, "delay_s": 0.05})
    x = np.ones((1, 4))
    oks, errs = [], []

    def hammer():
        for _ in range(10):
            try:
                out, _ = r.predict_ex("m", x)
                oks.append(bool(np.array_equal(out, x * 2.0)))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)  # traffic in flight when the shrink lands
    rep = r.set_pool_size(1)
    for t in threads:
        t.join()
    assert errs == [] and all(oks) and len(oks) == 40
    assert rep["retired"] == [2, 1] and rep["forced"] == []
    assert r.pool_size() == 1
    assert r.states()["retired"] == 2
    assert r.supervisor.postmortems == []
    # grow back: retired slots revive first, warm from replay
    rep2 = r.set_pool_size(3)
    assert sorted(rep2["grew"]) == [1, 2]
    assert _wait(lambda: r.states().get("live") == 3)
    for rk in (1, 2):
        info = r.ping(rk)
        assert info["incarnation"] == 1  # a revival, not a restart
        assert info["models"] == {"m": 1}
        assert [rec["model"] for rec in r.replays[rk]] == ["m"]
    out, _ = r.predict_ex("m", x)
    assert np.array_equal(out, x * 2.0)


def test_autoscaler_drives_pool_through_load_signals(make_fleet):
    """fleet_autoscaler wires PR 6's Autoscaler to the router: the
    queue-depth signal crosses via load_signals() and apply_scale
    resizes the pool through set_pool_size — ticked synthetically."""
    from analytics_zoo_tpu.serving.fleet import fleet_autoscaler
    r = make_fleet(n_workers=2)
    r.deploy("m", None, STUB)
    r.set_pool_size(1)
    sc = fleet_autoscaler(
        r, min_replicas=1, max_replicas=2, up_queue_depth=2,
        down_queue_depth=0, hold_ticks=1, cooldown_s=0.0,
        interval_s=0.01)
    assert r.pool_size() == 1
    # synthetic pressure: park router-side outstanding above the bar
    with r._lock:
        r.handles[0].outstanding += 3
    sc.tick()
    assert r.pool_size() == 2
    with r._lock:
        r.handles[0].outstanding -= 3
    out, _ = r.predict_ex("m", np.ones((1, 2)))
    assert np.array_equal(out, np.ones((1, 2)))


def test_oversize_reply_degrades_to_structured_error(make_fleet):
    """A reply past ZOO_FLEET_MAX_FRAME degrades worker-side to a
    structured error envelope carrying the attempted size — the
    router's caller gets a ServingError with details, NOT a dead
    connection read as a worker crash (which would retry the same
    oversize reply into a sibling)."""
    r = make_fleet(n_workers=1, env={"ZOO_FLEET_MAX_FRAME": "8192"})
    # expand=64 inflates the REPLY 64x past the cap while the request
    # stays tiny; "ok" proves the connection survives the degrade
    r.deploy("big", None, STUB, builder_args={"expand": 64})
    r.deploy("ok", None, STUB, builder_args={"scale": 2.0})
    x = np.ones((4, 16), dtype=np.float64)
    with pytest.raises(ServingError) as ei:
        r.predict_ex("big", x)
    d = ei.value.details
    assert d["error"] == "FrameError"
    assert d["attempted_bytes"] > 8192
    assert d["max_frame_bytes"] == 8192
    out, _ = r.predict_ex("ok", x)
    assert np.array_equal(out, x * 2.0)
    assert r.retries_total == 0
    assert r.supervisor.postmortems == []


# ------------------------------------- cross-process determinism (v2)
def test_cross_process_generate_determinism(tmp_path):
    """The decode engine v2 determinism contract re-gated across the
    wire: the same (prompt, seed, sampling params) through a REAL
    fleet worker process (jax, decode engine, framed protocol) and
    through a single-process registry built from the SAME artifact
    spec yields bit-identical tokens — greedy and sampled.  The
    engine's fold_in RNG has no process-dependent input, and the
    sampling envelope crosses the wire as plain json scalars, so this
    is the whole stack's replayability in one assertion."""
    from analytics_zoo_tpu.serving import ModelRegistry
    from analytics_zoo_tpu.serving.fleet import builders

    lm_args = {"vocab_size": 32, "seq_len": 48, "n_layers": 1,
               "d_model": 16, "n_heads": 2, "capacity": 2,
               "prompt_buckets": [8, 16], "prefix_pool": 2}
    # 10 tokens: pool-ELIGIBLE (8-token prefix + tail), so the pooled
    # admission path itself is what replays across processes
    prompt = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3]]
    cases = [
        dict(max_new_tokens=6),
        dict(max_new_tokens=6, temperature=0.9, top_k=8, top_p=0.9,
             seed=77),
        dict(max_new_tokens=5, temperature=1.3, seed=12345),
    ]

    # in-process reference: the builder's own deploy kwargs, exactly
    # what the worker's activate runs from the artifact spec
    reg = ModelRegistry()
    try:
        reg.deploy("lm", **builders.lm(lm_args, None))
        ref = [[np.asarray(t).tolist() for t in
                reg.generate("lm", np.asarray(prompt, np.int32), **c)]
               for c in cases]
    finally:
        reg.shutdown()

    r = FleetRouter(str(tmp_path / "share"), n_workers=1, fake=False,
                    env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
                    max_restarts=1)
    try:
        r.start(timeout=120)
        rep = r.deploy("lm", None,
                       "analytics_zoo_tpu.serving.fleet.builders:lm",
                       builder_args=lm_args)
        assert all("error" not in a for a in rep["activations"]), rep
        for c, expect in zip(cases, ref):
            out, info = r.generate_ex(
                "lm", np.asarray(prompt, np.int32), **c)
            got = [np.asarray(t).tolist() for t in out]
            assert got == expect, (c, got, expect)
            # replay across the wire too
            out2, _ = r.generate_ex(
                "lm", np.asarray(prompt, np.int32), **c)
            assert [np.asarray(t).tolist() for t in out2] == got
    finally:
        r.close()


# ------------------------------------------- real workers, one store
def test_real_workers_deploy_warm_and_answer_as_one_process_does(
        tmp_path):
    """Two REAL worker processes (jax, registry, the share's execstore)
    behind the router.  A deploy fans out in rank order: the first
    activation of new weights compiles (it fills the store, and shows
    that a zero below means something), every later worker warms with
    0 compiles; weights the store has seen deploy with 0 everywhere;
    a killed worker comes back replaying the current version with 0.
    After each deploy every worker, asked directly, answers bit-equal
    to a registry in this process built by the same builder."""
    from analytics_zoo_tpu.serving import ModelRegistry
    from analytics_zoo_tpu.serving.fleet import builders

    n_layers, d = 6, 16
    registry_kwargs = {"max_batch_size": 8}

    def weights(seed):
        rng = np.random.default_rng(seed)
        return {f"w{i}": rng.normal(size=(d, d)).astype(np.float32) * 0.1
                for i in range(n_layers)}

    first, second = weights(7), weights(11)
    x = np.random.default_rng(3).normal(size=(3, d)).astype(np.float32)
    with ModelRegistry(**registry_kwargs) as reg:    # no store here
        want = []
        for name, w in (("first", first), ("second", second)):
            reg.deploy(name, warmup_shapes=(d,),
                       **builders.mlp({"n_layers": n_layers}, w))
            want.append(np.asarray(reg.predict(name, x)).copy())

    r = FleetRouter(str(tmp_path / "share"), n_workers=2, fake=False,
                    registry_kwargs=registry_kwargs,
                    env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
                    max_restarts=1, restart_backoff=0.2)

    def fan_out(params):
        rep = r.deploy("mlp", params,
                       "analytics_zoo_tpu.serving.fleet.builders:mlp",
                       builder_args={"n_layers": n_layers},
                       warmup_shapes=[d])
        acts = rep["activations"]
        assert [a["rank"] for a in acts] == [0, 1], acts
        assert all("error" not in a for a in acts), acts
        return rep["version"], [a["compiles"] for a in acts]

    def every_worker_answers(version, expect):
        for h in r.handles:
            resp = r._call(h, {"op": "predict", "model": "mlp",
                               "inputs": x})
            assert resp["info"]["version"] == version
            assert np.array_equal(
                protocol.decode_value(resp["result"]), expect), h.rank

    try:
        r.start(timeout=300)
        version, compiles = fan_out(first)
        assert version == 1 and compiles[0] > 0 and compiles[1] == 0
        every_worker_answers(1, want[0])
        version, compiles = fan_out(second)
        assert version == 2 and compiles[0] > 0 and compiles[1] == 0
        every_worker_answers(2, want[1])
        version, compiles = fan_out(first)
        assert version == 3 and compiles == [0, 0]
        every_worker_answers(3, want[0])
        r.supervisor.kill(1)
        assert _wait(lambda: r.supervisor.postmortems
                     and r.states().get("live") == 2
                     and r.replays.get(1), timeout=120)
        assert [(rep["model"], rep["version"], rep["compiles"])
                for rep in r.replays[1]] == [("mlp", 3, 0)]
        every_worker_answers(3, want[0])
    finally:
        r.close()


def test_fleet_parent_imports_initialise_no_backend():
    """A chip belongs to one process: the router/supervisor parent (and
    the launcher) only move bytes and must never touch a jax backend,
    or they would hold the chip their one worker needs.  Importing the
    package — serving plane, fleet and launcher included — must leave
    jax's backend table empty."""
    import subprocess
    import sys
    code = (
        "import analytics_zoo_tpu, analytics_zoo_tpu.serving, "
        "analytics_zoo_tpu.launcher\n"
        "from analytics_zoo_tpu.serving.fleet import router, supervisor\n"
        "from analytics_zoo_tpu.pipeline.inference import "
        "InferenceModel, DecodeEngine\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('NO_BACKEND')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env={**os.environ, "PYTHONPATH": REPO},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "NO_BACKEND" in proc.stdout, \
        proc.stderr[-2000:]
