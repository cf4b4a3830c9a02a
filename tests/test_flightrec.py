"""Cross-process observability: flight recorder (crash-safe framed
records, atomic snapshots, harvest/postmortem), pod-level aggregation
(rank label merge, counter-sum vs gauge-last-write, pod totals), the
training step profiler, structured-log rank stamping, and the
zoo_process_info default family.
"""

import json
import os
import struct
import time
import zlib

import numpy as np
import pytest

from analytics_zoo_tpu.observability import aggregate, flightrec
from analytics_zoo_tpu.observability.metrics import (
    MetricsRegistry, parse_prometheus_text, process_info_family,
    render_prometheus)
from analytics_zoo_tpu.observability.trace import TRAIN_PHASES, Span


@pytest.fixture
def isolated_recorder():
    """Process-global recorder state must not leak across tests."""
    flightrec._reset_for_tests()
    yield
    flightrec._reset_for_tests()


# ------------------------------------------------------ flight recorder
def test_recorder_round_trip_and_torn_tail(tmp_path,
                                           isolated_recorder):
    rec = flightrec.FlightRecorder(str(tmp_path), rank=1, incarnation=2)
    for s in range(1, 5):
        rec.record_step(s)
    rec.record_log({"level": "info", "msg": "hello"})
    rec.record_span({"trace_id": "t1", "name": "train_step"})
    rec.close()
    d = os.path.join(str(tmp_path), "rank1.i2")
    seg = os.path.join(d, "events.seg")
    records = flightrec.read_records(seg)
    assert [r["step"] for r in records if r["t"] == "hb"] == [1, 2, 3, 4]
    assert any(r["t"] == "log" for r in records)
    # a SIGKILL mid-write leaves a torn frame: reader must stop cleanly
    with open(seg, "ab") as f:
        f.write(struct.pack("<II", 500, 42) + b"torn")
    assert flightrec.read_records(seg) == records
    # a CRC-corrupt record (disk-level partial write) is also a stop
    payload = json.dumps({"t": "hb", "step": 99}).encode()
    with open(seg, "ab") as f:
        f.write(struct.pack("<II", len(payload), 0xdeadbeef) + payload)
    assert flightrec.read_records(seg) == records
    # meta.json landed atomically at open
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    assert meta["rank"] == 1 and meta["incarnation"] == 2
    assert meta["pid"] == os.getpid()


def test_recorder_segment_rotation_bounds_disk(tmp_path,
                                               isolated_recorder):
    rec = flightrec.FlightRecorder(str(tmp_path), rank=0, incarnation=0,
                                   max_segment_bytes=2048)
    for s in range(1, 501):
        rec.record_step(s)
    rec.close()
    d = os.path.join(str(tmp_path), "rank0.i0")
    sizes = [os.path.getsize(os.path.join(d, n))
             for n in ("events.seg", "events.seg.old")
             if os.path.exists(os.path.join(d, n))]
    # two bounded segments, however many records were appended
    assert len(sizes) == 2 and all(sz <= 4096 for sz in sizes)
    # the TAIL survives rotation: last step recorded is readable
    h = flightrec.harvest(str(tmp_path))
    assert h[0]["last_step"] == 500


def test_harvest_picks_newest_incarnation_and_postmortem_merges(
        tmp_path, isolated_recorder):
    old = flightrec.FlightRecorder(str(tmp_path), rank=1, incarnation=0)
    old.record_step(7)
    old.close()
    new = flightrec.FlightRecorder(str(tmp_path), rank=1, incarnation=1)
    new.record_step(3)
    new.close()
    h = flightrec.harvest(str(tmp_path))
    assert h[1]["incarnation"] == 1 and h[1]["last_step"] == 3
    assert h[1]["incarnations"] == [0, 1]
    pm = flightrec.write_postmortem(
        str(tmp_path), str(tmp_path / "pm.json"), reason="watchdog",
        failed_rank=1, incarnation=1,
        supervisor={0: {"rc": -15, "heartbeat_age_s": 1.5},
                    1: {"rc": None, "heartbeat_age_s": 31.0}})
    assert pm["failed_rank"] == 1 and pm["reason"] == "watchdog"
    assert pm["ranks"]["1"]["last_step"] == 3
    assert pm["ranks"]["1"]["heartbeat_age_s"] == 31.0
    # rank 0 never recorded anything: supervisor evidence still lands
    assert pm["ranks"]["0"]["rc"] == -15
    with open(tmp_path / "pm.json") as f:
        assert json.load(f) == json.loads(json.dumps(pm))


def test_recorder_hooks_capture_spans_and_logs(tmp_path,
                                               isolated_recorder):
    from analytics_zoo_tpu.observability.log import get_logger
    from analytics_zoo_tpu.observability.trace import Tracer
    rec = flightrec.configure(str(tmp_path), rank=0, incarnation=0)
    assert flightrec.configure(str(tmp_path)) is rec  # idempotent
    tracer = Tracer()
    with tracer.request("req", model="m") as span:
        with span.phase("execute"):
            pass
    # a record below the handler threshold still reaches the black box
    get_logger("zoo.test.flightrec").debug("quiet line", k=1)
    flightrec.shutdown()
    h = flightrec.harvest(str(tmp_path))
    assert any(s.get("name") == "req" for s in h[0]["spans"])
    assert any(r.get("msg") == "quiet line" for r in h[0]["logs"])
    # shutdown unhooked: new spans no longer try to record
    with tracer.request("after"):
        pass


def test_snapshot_atomic_and_throttled(tmp_path, isolated_recorder):
    rec = flightrec.FlightRecorder(str(tmp_path), rank=0, incarnation=0,
                                   snapshot_interval_s=60.0)
    assert rec.snapshot_metrics(force=True)
    assert not rec.snapshot_metrics()  # throttled
    prom = os.path.join(str(tmp_path), "rank0.i0", "metrics.prom")
    parsed = parse_prometheus_text(open(prom).read())
    # the default collector: the process-info join key
    assert any(k[0] == "zoo_process_info"
               for k in parsed["samples"])
    assert not os.path.exists(prom + ".tmp")
    rec.close()


# ---------------------------------------------------------- aggregation
def _write_snap(base, rank, inc, text):
    d = os.path.join(base, f"rank{rank}.i{inc}")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "metrics.prom"), "w") as f:
        f.write(text)


def test_aggregate_multi_rank_round_trip(tmp_path):
    """The satellite round-trip pin: aggregated multi-rank families
    re-render and re-parse — label merge, same-named family merge
    across snapshots, counter summation vs gauge last-write."""
    base = str(tmp_path)
    _write_snap(base, 0, 0,
                "# HELP zoo_train_steps_total steps\n"
                "# TYPE zoo_train_steps_total counter\n"
                "zoo_train_steps_total 12\n"
                "# TYPE zoo_queue_depth gauge\n"
                "zoo_queue_depth 5\n"
                "# TYPE zoo_lat_seconds summary\n"
                'zoo_lat_seconds{quantile="0.5"} 0.01\n'
                "zoo_lat_seconds_sum 0.4\n"
                "zoo_lat_seconds_count 40\n")
    # rank 1 restarted once: two incarnations of the same counter must
    # SUM (each incarnation restarts from 0) while the gauge takes the
    # newest incarnation's value
    _write_snap(base, 1, 0,
                "# TYPE zoo_train_steps_total counter\n"
                "zoo_train_steps_total 4\n"
                "# TYPE zoo_queue_depth gauge\n"
                "zoo_queue_depth 9\n")
    _write_snap(base, 1, 1,
                "# TYPE zoo_train_steps_total counter\n"
                "zoo_train_steps_total 8\n"
                "# TYPE zoo_queue_depth gauge\n"
                "zoo_queue_depth 2\n")
    text = aggregate.aggregate_dir(base)
    parsed = parse_prometheus_text(text)  # parses clean
    s = parsed["samples"]
    assert s[("zoo_train_steps_total", (("rank", "0"),))] == 12
    assert s[("zoo_train_steps_total", (("rank", "1"),))] == 12
    assert s[("zoo_train_steps_total", ())] == 24  # pod total
    assert s[("zoo_queue_depth", (("rank", "1"),))] == 2  # last write
    assert s[("zoo_lat_seconds",
              (("quantile", "0.5"), ("rank", "0")))] == 0.01
    assert s[("zoo_lat_seconds_count", (("rank", "0"),))] == 40
    assert parsed["types"]["zoo_train_steps_total"] == "counter"
    assert parsed["types"]["zoo_lat_seconds"] == "summary"
    # one # TYPE block per family even though every rank declared it
    assert text.count("# TYPE zoo_train_steps_total counter") == 1
    # and the whole aggregate re-renders losslessly through the
    # library path too
    re_text = render_prometheus(
        aggregate.aggregate_files(aggregate.iter_snapshots(base)))
    assert parse_prometheus_text(re_text)["samples"] == s


def test_aggregate_typeless_snapshot_keeps_counter_semantics(tmp_path):
    """A snapshot that lost its # TYPE line (hand-dropped flat files)
    must not demote an established counter to last-write or drop it
    from the pod total — the sum decision uses the RESOLVED family
    type."""
    base = str(tmp_path)
    _write_snap(base, 0, 0, "# TYPE zoo_train_steps_total counter\n"
                            "zoo_train_steps_total 5\n")
    with open(os.path.join(base, "rank0.prom"), "w") as f:
        f.write("zoo_train_steps_total 7\n")  # no TYPE line
    s = parse_prometheus_text(aggregate.aggregate_dir(base))["samples"]
    assert s[("zoo_train_steps_total", (("rank", "0"),))] == 12
    assert s[("zoo_train_steps_total", ())] == 12


def test_aggregate_type_conflict_raises(tmp_path):
    base = str(tmp_path)
    _write_snap(base, 0, 0, "# TYPE zoo_x counter\nzoo_x 1\n")
    _write_snap(base, 1, 0, "# TYPE zoo_x gauge\nzoo_x 2\n")
    with pytest.raises(ValueError, match="both"):
        aggregate.aggregate_dir(base)


def test_aggregate_preserves_existing_rank_label(tmp_path):
    base = str(tmp_path)
    _write_snap(base, 0, 0,
                "# TYPE zoo_y_total counter\n"
                'zoo_y_total{rank="7"} 3\n')
    s = parse_prometheus_text(aggregate.aggregate_dir(base))["samples"]
    # the snapshot's own rank label wins; no bogus pod total is built
    assert s == {("zoo_y_total", (("rank", "7"),)): 3.0}


def test_step_view_names_stragglers(tmp_path):
    base = str(tmp_path)
    _write_snap(base, 0, 0, "# TYPE zoo_train_steps_total counter\n"
                            "zoo_train_steps_total 20\n")
    _write_snap(base, 1, 0, "# TYPE zoo_train_steps_total counter\n"
                            "zoo_train_steps_total 14\n")
    view = aggregate.step_view(base)
    assert view["ranks"][1]["lag"] == 6 and view["stragglers"] == [1]
    # rate between two observations
    view2 = aggregate.step_view(base, prev={0: 10.0, 1: 10.0},
                                interval_s=2.0)
    assert view2["ranks"][0]["steps_per_s"] == 5.0


def test_aggregate_cli_scrape_and_view(tmp_path, capsys):
    base = str(tmp_path)
    _write_snap(base, 0, 0, "# TYPE zoo_train_steps_total counter\n"
                            "zoo_train_steps_total 6\n")
    assert aggregate.main([base]) == 0
    out = capsys.readouterr().out
    assert parse_prometheus_text(out)["samples"][
        ("zoo_train_steps_total", (("rank", "0"),))] == 6
    out_path = str(tmp_path / "pod.prom")
    assert aggregate.main([base, "--out", out_path]) == 0
    assert os.path.exists(out_path)
    assert aggregate.main([base, "--view", "--json"]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["ranks"]["0"]["steps"] == 6


# --------------------------------------------------------- process info
def test_process_info_family_default_and_env(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_PROCESS_ID", "3")
    monkeypatch.setenv("ZOO_RESTART_COUNT", "2")
    fam = process_info_family()
    labels = fam.samples[0][0]
    assert labels["rank"] == "3" and labels["incarnation"] == "2"
    assert labels["pid"] == str(os.getpid())
    assert "jax" in labels and "start_unix" in labels
    reg = MetricsRegistry()
    s = parse_prometheus_text(reg.render_prometheus())["samples"]
    key = next(k for k in s if k[0] == "zoo_process_info")
    assert s[key] == 1.0
    # opt-out stays available for aggregation-side registries
    assert "zoo_process_info" not in \
        MetricsRegistry(process_info=False).render_prometheus()


# ------------------------------------------------------- log stamping
def test_structured_log_stamps_rank_and_incarnation(monkeypatch):
    import logging
    from analytics_zoo_tpu.observability import log as log_mod
    monkeypatch.setenv("ZOO_TPU_PROCESS_ID", "1")
    monkeypatch.setenv("ZOO_RESTART_COUNT", "4")
    log_mod.refresh_identity()
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(json.loads(record.getMessage()))

    logger = logging.getLogger("zoo.test.stamp")
    handler = Capture()
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        log_mod.get_logger("zoo.test.stamp").info("line", extra_k=7)
    finally:
        logger.removeHandler(handler)
        monkeypatch.delenv("ZOO_TPU_PROCESS_ID")
        monkeypatch.delenv("ZOO_RESTART_COUNT")
        log_mod.refresh_identity()
    (rec,) = records
    assert rec["rank"] == 1 and rec["incarnation"] == 4
    assert rec["extra_k"] == 7 and rec["msg"] == "line"


def test_structured_log_unstamped_without_contract(monkeypatch):
    import logging
    from analytics_zoo_tpu.observability import log as log_mod
    monkeypatch.delenv("ZOO_TPU_PROCESS_ID", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    monkeypatch.delenv("ZOO_RESTART_COUNT", raising=False)
    log_mod.refresh_identity()
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(json.loads(record.getMessage()))

    logger = logging.getLogger("zoo.test.nostamp")
    handler = Capture()
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        log_mod.get_logger("zoo.test.nostamp").info("line")
    finally:
        logger.removeHandler(handler)
        log_mod.refresh_identity()
    assert "rank" not in records[0] and "incarnation" not in records[0]


# ------------------------------------------------------ step profiler
def test_step_profiler_phases_and_timeline(tmp_path):
    from analytics_zoo_tpu.train.stepprof import StepProfiler
    tl = str(tmp_path / "timeline.jsonl")
    prof = StepProfiler(timeline_path=tl)
    for step in (1, 2):
        prof.last_wait_s = 0.002
        span = prof.begin_step(step, h2d_s=0.001)
        with span.phase("step_dispatch"):
            time.sleep(0.001)
        if step == 2:
            with span.phase("ckpt_save"):
                pass
        prof.finish_step(span, step)
    assert prof.steps == 2
    snap = prof.snapshot()
    assert set(snap["phases"]) >= {"data_wait", "h2d", "step_dispatch"}
    assert snap["phases"]["ckpt_save"]["count"] == 1
    text = render_prometheus(prof.families())
    s = parse_prometheus_text(text)["samples"]
    assert s[("zoo_train_step_seconds_count",
              (("phase", "step_dispatch"),))] == 2
    assert prof.write_timeline() == tl
    lines = [json.loads(ln) for ln in open(tl)]
    assert [e["step"] for e in lines] == [1, 2]
    assert all(f"{p}_ms" in lines[0] for p in TRAIN_PHASES)


def test_trainer_step_profiler_end_to_end(tmp_path):
    """fit with the profiler on: every phase populated, losses
    bit-identical to an unprofiled fit (observability must never
    change the math), timeline artifact published."""
    import optax
    from analytics_zoo_tpu.data.dataset import Dataset
    from analytics_zoo_tpu.pipeline.api.keras import (Sequential,
                                                      objectives)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.train import triggers
    from analytics_zoo_tpu.train.trainer import Trainer

    def make():
        m = Sequential()
        m.add(Dense(8, activation="relu", input_shape=(4,)))
        m.add(Dense(3))
        return Trainer(m.to_graph(),
                       objectives.get("sparse_categorical_crossentropy"),
                       optax.sgd(0.1), seed=0)

    rng = np.random.default_rng(1)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.integers(0, 3, 32).astype(np.int32)
    ds = Dataset.from_ndarray(x, y)
    plain = make()
    h_plain = plain.fit(ds, batch_size=8, shuffle=False,
                        end_trigger=triggers.MaxEpoch(2))
    traced = make()
    tl = str(tmp_path / "steps.jsonl")
    prof = traced.enable_step_profiler(timeline_path=tl)
    flightrec._reset_for_tests()
    flightrec.configure(str(tmp_path / "fr"), rank=0, incarnation=0)
    try:
        h_traced = traced.fit(ds, batch_size=8, shuffle=False,
                              end_trigger=triggers.MaxEpoch(2))
    finally:
        flightrec.shutdown()
    assert h_plain["loss"] == h_traced["loss"]  # bit-identical
    assert prof.steps == 8
    for phase in ("data_wait", "h2d", "step_dispatch"):
        assert prof.windows[phase].count == 8, phase
    entries = [json.loads(ln) for ln in open(tl)]
    assert len(entries) == 8
    assert sum(e.get("compiles", 0) for e in entries) >= 1
    # the flight recorder got per-step liveness markers AND the
    # batched rich step entries (flushed at fit end), plus the
    # profiler families in its final snapshot
    h = flightrec.harvest(str(tmp_path / "fr"))
    assert h[0]["last_step"] == 8
    assert [e["step"] for e in h[0]["steps"]] == list(range(1, 9))
    assert "step_dispatch_ms" in h[0]["steps"][0]
    s = parse_prometheus_text(open(h[0]["metrics_path"]).read())["samples"]
    assert s[("zoo_train_steps_total", ())] >= 8.0
    assert any(k[0] == "zoo_train_step_seconds" for k in s)