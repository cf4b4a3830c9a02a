"""Supervising launcher (fault-tolerant local fan-out): crash-restart
with the ZOO_RESUME contract, pod-wide fast-fail reaping at
--max-restarts 0, heartbeat watchdog SIGKILL+relaunch, and the
coordinator port-race retry.

These drive the REAL supervisor loop (`launcher._run_supervised`)
through `python -m analytics_zoo_tpu.launcher`, but with trivial
non-jax worker scripts so they stay fast enough for tier-1 — the full
jax.distributed drill (kill, resume, bit-equal parameters) lives in
test_launcher.py (slow).
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a fake pod worker: no jax, just the supervision contract.  Modes:
#   crash   — rank 1 exits 3 on the first incarnation
#   partial — rank 1 exits 2; rank 0 "blocks in a collective" (sleeps)
#   hang    — rank 1 heartbeats once then stops (watchdog fodder)
#   bind    — rank 0 prints a bind error + exits 1 until the flag file
WORKER = textwrap.dedent("""
    import os, sys, time
    rank = int(os.environ.get("ZOO_TPU_PROCESS_ID", "0"))
    mode, flag = sys.argv[1], sys.argv[2]
    hb = os.environ.get("ZOO_HEARTBEAT_FILE")
    resume = os.environ.get("ZOO_RESUME")

    def beat():
        if hb:
            with open(hb, "a"):
                os.utime(hb, None)

    if mode == "crash" and rank == 1 and not resume:
        sys.exit(3)
    if mode == "crashrec":
        # a worker WITH a flight recorder: append real framed records
        # (stdlib only — this pins the on-disk framing cross-
        # implementation) + an atomic metric snapshot, then rank 1
        # dies mid-write leaving a torn tail frame
        import struct, zlib, json as _json
        base = os.environ["ZOO_FLIGHTREC_DIR"]
        inc = os.environ.get("ZOO_RESTART_COUNT", "0")
        d = os.path.join(base, f"rank{rank}.i{inc}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "events.seg"), "ab") as f:
            for step in range(1, 7):
                p = _json.dumps({"t": "hb", "ts": time.time(),
                                 "step": step}).encode()
                f.write(struct.pack("<II", len(p),
                                    zlib.crc32(p) & 0xffffffff) + p)
            f.write(struct.pack("<II", 64, 1234) + b"half")  # torn
        with open(os.path.join(d, "metrics.prom"), "w") as f:
            f.write("# TYPE zoo_train_steps_total counter\\n")
            f.write("zoo_train_steps_total 6\\n")
        if rank == 1 and not resume:
            time.sleep(0.5)  # let rank 0 land its snapshot first
            sys.exit(5)
    if mode == "hang" and rank == 1 and not resume:
        beat()
        time.sleep(300)
    if mode == "bind" and rank == 0 and not os.path.exists(flag):
        open(flag, "w").close()
        print("RuntimeError: Failed to bind: Address already in use",
              file=sys.stderr)
        sys.exit(1)
    if mode == "partial" and rank == 1:
        sys.exit(2)
    if mode == "partial" and rank == 0:
        time.sleep(300)
    for _ in range(4):
        beat()
        time.sleep(0.05)
    print(f"DONE rank={rank} resume={resume or 0} "
          f"restart_count={os.environ.get('ZOO_RESTART_COUNT', 0)}",
          flush=True)
""")


def _launch(tmp_path, mode, extra_args=(), timeout=120):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    summary = tmp_path / "summary.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    for k in list(env):
        if k.startswith(("ZOO_TPU_", "ZOO_RESUME", "ZOO_FAULT_",
                         "JAX_COORDINATOR", "JAX_NUM_PROCESSES",
                         "JAX_PROCESS_ID")):
            env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, "-m", "analytics_zoo_tpu.launcher",
         "--num-processes", "2", "--restart-backoff", "0.1",
         "--summary-json", str(summary)] + list(extra_args)
        + [str(script), mode, str(tmp_path / "flag")],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=timeout)
    summ = json.loads(summary.read_text()) if summary.exists() else None
    return proc, summ


def _cleanup_kept(summ):
    """Reap the supervision run_dir the launcher preserves once a
    postmortem was written (tests read it first, then clean up)."""
    import shutil
    for p in (summ or {}).get("postmortems", []):
        shutil.rmtree(os.path.dirname(p), ignore_errors=True)


def test_crash_restarts_with_resume_env(tmp_path):
    """A worker exiting nonzero tears the pod down and relaunches it
    with ZOO_RESUME=1 within the --max-restarts budget."""
    proc, summ = _launch(tmp_path, "crash", ["--max-restarts", "1"])
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert summ["restarts"] == 1 and summ["reasons"] == ["exit"]
    # the relaunched incarnation saw the resume contract
    assert "DONE rank=0 resume=1 restart_count=1" in proc.stdout
    assert "DONE rank=1 resume=1" in proc.stdout
    assert summ["metrics"]["restarts"] == {"exit": 1}
    _cleanup_kept(summ)


def test_partial_death_fast_fails_with_no_restarts(tmp_path):
    """--max-restarts 0: one dead worker must NOT leave the survivor
    blocked until its own timeout — the supervisor always reaps the
    pod, and the failing worker's rc propagates."""
    start = time.time()
    proc, summ = _launch(tmp_path, "partial")
    wall = time.time() - start
    assert proc.returncode == 2, proc.stdout[-2000:]
    # the survivor "blocks" for 300s; reaping must beat that by far
    assert wall < 60, f"supervisor waited on the blocked survivor ({wall:.0f}s)"
    assert summ["restarts"] == 0 and summ["rc"] == 2


def test_watchdog_kills_and_restarts_hung_worker(tmp_path):
    """A stale heartbeat past --watchdog-sec is a hang: SIGKILL the
    worker, reap the pod, relaunch with resume."""
    proc, summ = _launch(tmp_path, "hang",
                         ["--max-restarts", "1", "--watchdog-sec", "2"])
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert summ["reasons"] == ["watchdog"], summ
    assert "DONE rank=1 resume=1" in proc.stdout
    assert summ["metrics"]["restarts"] == {"watchdog": 1}
    _cleanup_kept(summ)


def test_restart_budget_exhaustion_fails(tmp_path):
    """A pod that keeps crashing past the budget surfaces the failure
    rc instead of looping forever (the crash mode only crashes the
    FIRST incarnation, so --max-restarts 0 must fail) — and the
    incident still gets its postmortem: supervisor-side evidence
    (failed rank, exit rc, heartbeat age) must be present even though
    these fake workers never wrote a flight-recorder record."""
    proc, summ = _launch(tmp_path, "crash")
    assert proc.returncode == 3
    assert summ == {"rc": 3, "restarts": 0, "port_retries": 0,
                    "reasons": [], "postmortems": summ["postmortems"],
                    "metrics": summ["metrics"]}
    assert len(summ["postmortems"]) == 1
    with open(summ["postmortems"][0]) as f:
        pm = json.load(f)
    assert pm["reason"] == "exit" and pm["failed_rank"] == 1
    assert pm["ranks"]["1"]["rc"] == 3
    # rank 1 exited before ever heartbeating; rank 0 finished clean
    assert pm["ranks"]["1"]["heartbeat_age_s"] is None
    assert pm["ranks"]["0"]["heartbeat_age_s"] is not None
    # the run_dir is preserved alongside for humans
    latest = os.path.join(os.path.dirname(summ["postmortems"][0]),
                          "pod_postmortem.json")
    assert os.path.exists(latest)
    import shutil
    shutil.rmtree(os.path.dirname(summ["postmortems"][0]),
                  ignore_errors=True)


def test_coordinator_bind_race_retried_with_fresh_port(tmp_path):
    """The documented _free_port race (launcher.py): worker 0 failing
    to bind the probed port at startup is retried on a fresh port,
    WITHOUT consuming the crash-restart budget and WITHOUT setting
    ZOO_RESUME (nothing trained yet)."""
    proc, summ = _launch(tmp_path, "bind")  # max-restarts defaults to 0
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert summ["port_retries"] == 1 and summ["restarts"] == 0
    assert summ["reasons"] == ["port"]
    assert "DONE rank=0 resume=0" in proc.stdout


def test_postmortem_harvests_flight_recorders(tmp_path):
    """The reaped pod's postmortem answers "why did rank 1 die":
    harvested flight-recorder heartbeats name the last completed step
    (the torn tail frame the kill left is dropped, never misread), the
    supervisor contributes the exit rc and heartbeat age, and the
    aggregated pod scrape lands beside it with per-rank step counters
    summing to the pod total."""
    import shutil
    from analytics_zoo_tpu.observability.metrics import \
        parse_prometheus_text
    proc, summ = _launch(tmp_path, "crashrec", ["--max-restarts", "1"])
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert len(summ["postmortems"]) == 1
    run_dir = os.path.dirname(summ["postmortems"][0])
    try:
        with open(summ["postmortems"][0]) as f:
            pm = json.load(f)
        assert pm["reason"] == "exit" and pm["failed_rank"] == 1
        assert pm["incarnation"] == 0
        r1 = pm["ranks"]["1"]
        assert r1["rc"] == 5
        assert r1["last_step"] == 6
        assert [h["step"] for h in r1["heartbeats"]][-3:] == [4, 5, 6]
        # the sibling pod-level scrape: rank-labeled series + pod total
        with open(os.path.join(run_dir, "pod_metrics.prom")) as f:
            s = parse_prometheus_text(f.read())["samples"]
        assert s[("zoo_train_steps_total", (("rank", "0"),))] == 6
        assert s[("zoo_train_steps_total", (("rank", "1"),))] == 6
        assert s[("zoo_train_steps_total", ())] == 12
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def test_watchdog_postmortem_names_stale_heartbeat(tmp_path):
    """The watchdog incident's postmortem carries the hung worker's
    heartbeat age — at least the watchdog window, since that is what
    convicted it."""
    import shutil
    proc, summ = _launch(tmp_path, "hang",
                         ["--max-restarts", "1", "--watchdog-sec", "2"])
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert len(summ["postmortems"]) == 1
    run_dir = os.path.dirname(summ["postmortems"][0])
    try:
        with open(summ["postmortems"][0]) as f:
            pm = json.load(f)
        assert pm["reason"] == "watchdog" and pm["failed_rank"] == 1
        assert pm["ranks"]["1"]["heartbeat_age_s"] >= 2.0
        # rank 0 exited clean long before: its aging heartbeat file
        # must NOT read as a second hung worker
        assert pm["stale_ranks"] == [1]
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def test_train_metric_families_render_and_parse():
    """zoo_train_restarts_total / zoo_ckpt_* families round-trip the
    Prometheus exposition parser (docs/observability.md rows)."""
    from analytics_zoo_tpu.observability.metrics import (
        parse_prometheus_text, render_prometheus)
    from analytics_zoo_tpu.train import metrics as tm
    state = tm.snapshot()
    try:
        tm.reset()
        tm.record_restart("exit")
        tm.record_restart("watchdog")
        tm.record_ckpt_save("sharded")
        tm.record_ckpt_commit()
        tm.record_ckpt_restore("ok")
        tm.record_ckpt_restore("corrupt_discarded")
        text = render_prometheus(tm.train_families())
        parsed = parse_prometheus_text(text)
        assert parsed["types"]["zoo_train_restarts_total"] == "counter"
        s = parsed["samples"]
        assert s[("zoo_train_restarts_total", (("reason", "exit"),))] == 1
        assert s[("zoo_train_restarts_total",
                  (("reason", "watchdog"),))] == 1
        assert s[("zoo_ckpt_saves_total", (("format", "sharded"),))] == 1
        assert s[("zoo_ckpt_restores_total", (("outcome", "ok"),))] == 1
        assert s[("zoo_ckpt_restores_total",
                  (("outcome", "corrupt_discarded"),))] == 1
        assert s[("zoo_ckpt_commits_total", ())] == 1
    finally:
        tm.reset()
        for r, v in state["restarts"].items():
            for _ in range(v):
                tm.record_restart(r)
        for f, v in state["ckpt_saves"].items():
            for _ in range(v):
                tm.record_ckpt_save(f)
        for o, v in state["ckpt_restores"].items():
            for _ in range(v):
                tm.record_ckpt_restore(o)
        for _ in range(state["ckpt_commits"]):
            tm.record_ckpt_commit()
