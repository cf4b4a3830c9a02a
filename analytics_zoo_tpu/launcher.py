"""``zoo-tpu-submit`` — the launcher entry point.

Parity surface: the reference ships shell launchers that prepare the
environment and submit the user's program to the cluster
(reference: scripts/spark-submit-with-zoo.sh:15-41, jupyter-with-zoo.sh).
The TPU-native analog prepares the ``jax.distributed`` env contract
(ZOO_TPU_COORDINATOR / NUM_PROCESSES / PROCESS_ID, consumed by
``init_nncontext`` → parallel/distributed.py) and runs the user script.

Three modes:

* single process (default)            — just run the script;
* pod process  (--process-id given)   — export the cluster env for THIS
  process of a multi-host pod, then run the script (invoke once per host,
  e.g. from your pod manifest);
* local fan-out (--num-processes N, no --process-id) — spawn N local
  worker processes forming a real jax.distributed cluster on this
  machine (CPU workers, ``--devices-per-process`` virtual devices
  each) — the reference's ``local[n]`` testing story at process
  granularity.  A chip belongs to one process and the fan-out assigns
  no device to a child, so it is NOT a way to share local chips: on a
  pod, run one ``--process-id`` process per chip.

Local fan-out is a *supervisor*, the coarse-grained recovery loop of
the reference's failure story (wp-bigdl: relaunch the job from the last
complete checkpoint): any worker exiting nonzero — or a worker whose
heartbeat file goes stale past ``--watchdog-sec`` (a hang in a dead
collective), which gets SIGKILLed — tears down the whole pod
immediately (no survivor is ever left blocked in a collective until
timeout) and, within ``--max-restarts``, relaunches it with
``ZOO_RESUME=1`` so a checkpointing ``Trainer.fit`` resumes from the
newest complete snapshot.  Restarts back off exponentially from
``--restart-backoff``.  Every crash/watchdog incident additionally
harvests the workers' flight recorders (``ZOO_FLIGHTREC_DIR``,
exported per worker) into a ``pod_postmortem.json`` + aggregated
``pod_metrics.prom`` in the run directory — preserved even when the
pod recovers — so "why did rank 1 die" survives the reap.  See
``train/faults.py`` for the full worker-side env contract and
``docs/distributed-training.md`` for the semantics.

Examples:
  zoo-tpu-submit train.py --epochs 10
  zoo-tpu-submit --num-processes 2 --devices-per-process 4 train.py
  zoo-tpu-submit --num-processes 2 --max-restarts 3 --watchdog-sec 300 \\
      train.py
  zoo-tpu-submit --coordinator host0:9876 --num-processes 16 \\
      --process-id 3 train.py
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import re
import runpy
import socket
import subprocess
import sys
import tempfile
import time
from typing import List, Optional, Tuple

from . import envcontract
from .observability import flightrec
from .parallel.distributed import ENV_COORD, ENV_NPROC, ENV_PID
from .train import faults
from .train import metrics as train_metrics


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# worker-0 stderr signatures of the coordinator failing to bind the
# probed port (the _free_port TOCTOU race): retried with a fresh port,
# without consuming the crash-restart budget
_BIND_ERR_RE = re.compile(
    r"(?i)address already in use|errno 98|eaddrinuse|failed to bind|"
    r"bind failed|error binding")
_PORT_RETRIES = 3
_STARTUP_WINDOW_S = 60.0
_MAX_BACKOFF_S = 30.0


def _run_script(script: str, script_args: List[str]):
    sys.argv = [script] + list(script_args)
    runpy.run_path(script, run_name="__main__")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="zoo-tpu-submit",
        description="Run a training/inference script on TPU — single "
                    "process, one process of a pod, or a local "
                    "multi-process cluster.")
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0 (pod mode)")
    parser.add_argument("--num-processes", type=int, default=1)
    parser.add_argument("--process-id", type=int, default=None,
                        help="this process's rank in the pod; omit with "
                             "--num-processes>1 to fan out locally")
    parser.add_argument("--devices-per-process", type=int, default=4,
                        help="virtual CPU devices per local worker "
                             "(local fan-out mode)")
    parser.add_argument("--platform", default=None,
                        help="force JAX_PLATFORMS (e.g. cpu)")
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="local fan-out: relaunch a crashed/hung pod "
                             "up to this many times with ZOO_RESUME=1 "
                             "(0 = supervise + reap only)")
    parser.add_argument("--restart-backoff", type=float, default=1.0,
                        help="base seconds between relaunches "
                             "(doubles per restart, capped at 30s)")
    parser.add_argument("--watchdog-sec", type=float, default=0.0,
                        help="SIGKILL + relaunch the pod when a worker's "
                             "heartbeat file goes stale this long "
                             "(0 disables; heartbeats come from "
                             "Trainer.fit steps, so size the window "
                             "above your longest compile+step)")
    parser.add_argument("--summary-json", default=None,
                        help="write a supervision summary (restarts, "
                             "reasons, rc) to this path on exit")
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.platform:
        # the variable is for child processes; this process has already
        # imported jax (which reads it only at import), so pin the
        # config too — before any backend exists
        os.environ["JAX_PLATFORMS"] = args.platform
        import jax
        jax.config.update("jax_platforms", args.platform)

    if args.num_processes <= 1:
        if args.process_id is not None or args.coordinator:
            parser.error("--process-id/--coordinator require "
                         "--num-processes > 1 (pod mode)")
        _run_script(args.script, args.script_args)
        return 0

    if args.process_id is not None:
        # one process of a real pod: export the env contract and run
        if not args.coordinator:
            parser.error("--coordinator is required with --process-id")
        os.environ[ENV_COORD] = args.coordinator
        os.environ[ENV_NPROC] = str(args.num_processes)
        os.environ[ENV_PID] = str(args.process_id)
        _run_script(args.script, args.script_args)
        return 0

    # local fan-out: a real jax.distributed cluster on this machine,
    # run under the supervisor (crash/hang detection, pod-wide reap,
    # bounded relaunch-with-resume).
    return _run_supervised(args)


def _flight_dir(run_dir: str) -> str:
    """The pod's shared flight-recorder directory: a pre-set
    ``ZOO_FLIGHTREC_DIR`` wins (drills harvest it themselves),
    otherwise it lives with the other supervision artifacts."""
    return (envcontract.env_str(flightrec.ENV_DIR)
            or os.path.join(run_dir, "flightrec"))


def _spawn_pod(args, coordinator: str, run_dir: str, incarnation: int,
               resume: bool) -> Tuple[list, List[str], List[str]]:
    """Launch all worker processes of one pod incarnation.  Worker
    stderr goes to per-worker files (replayed by the supervisor at pod
    end) so bind-race detection can read worker 0's traceback."""
    procs, hb_paths, err_paths = [], [], []
    for pid in range(args.num_processes):
        env = dict(os.environ)
        env[ENV_COORD] = coordinator
        env[ENV_NPROC] = str(args.num_processes)
        env[ENV_PID] = str(pid)
        # every worker records its black box under the shared pod dir;
        # _reap_pod's postmortem harvests it (observability/flightrec)
        env[flightrec.ENV_DIR] = _flight_dir(run_dir)
        env[faults.ENV_RESTART_COUNT] = str(incarnation)
        # local fan-out is a SIMULATED pod of CPU workers: a chip
        # belongs to one process, and nothing here assigns devices to
        # children, so N local workers cannot share it (a real pod
        # runs one process per chip via --process-id)
        env["JAX_PLATFORMS"] = args.platform or "cpu"
        # --devices-per-process owns the worker topology: replace any
        # inherited host-platform device count rather than deferring to it
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{args.devices_per_process}").strip()
        # supervision contract: a fresh heartbeat file per incarnation
        # (stale mtimes from the previous one must not mask a hang),
        # ZOO_RESUME only on relaunches (train/faults.py)
        hb = os.path.join(run_dir, f"hb_p{pid}.r{incarnation}")
        env[faults.ENV_HEARTBEAT] = hb
        hb_paths.append(hb)
        if resume:
            env[faults.ENV_RESUME] = "1"
        err = os.path.join(run_dir, f"stderr_p{pid}.r{incarnation}.log")
        err_paths.append(err)
        with open(err, "wb") as errf:
            procs.append(subprocess.Popen(
                [sys.executable, args.script] + list(args.script_args),
                env=env, stderr=errf))
    return procs, hb_paths, err_paths


def _supervise(procs: list, hb_paths: List[str], watchdog_sec: float,
               started: float, poll_s: float = 0.2):
    """Monitor one pod incarnation until it resolves.

    Returns ``("ok", None)`` when every worker exited zero,
    ``("exit", rank)`` on the first nonzero exit (partial pod death
    must be reaped immediately — survivors are blocked in collectives),
    or ``("watchdog", rank)`` when a live worker's heartbeat file is
    stale past the window.  Staleness only applies once the worker has
    created its heartbeat file (at jax.distributed join, then per
    training step) — the import/cluster-join phase is covered by worker
    exits, not mtimes."""
    while True:
        alive = False
        for rank, p in enumerate(procs):
            rc = p.poll()
            if rc is None:
                alive = True
            elif rc != 0:
                return "exit", rank
        if not alive:
            return "ok", None
        if watchdog_sec:
            now = time.time()
            for rank, (p, hb) in enumerate(zip(procs, hb_paths)):
                if p.poll() is not None:
                    continue
                try:
                    last = os.path.getmtime(hb)
                except OSError:
                    continue  # no heartbeat yet: still starting up
                if now - max(last, started) > watchdog_sec:
                    return "watchdog", rank
        time.sleep(poll_s)


def _reap_pod(procs: list, grace_s: float = 5.0,
              kill_first: Optional[int] = None) -> None:
    """Tear the whole pod down: SIGKILL the hung worker (if any), then
    terminate + grace-wait + kill the rest.  Runs on EVERY pod exit so
    a partial death never leaves survivors blocked in a collective
    until timeout — --max-restarts 0 included."""
    if kill_first is not None and procs[kill_first].poll() is None:
        procs[kill_first].kill()
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + grace_s
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                pass
    for p in procs:
        if p.poll() is None:
            p.kill()
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass


def _replay_stderr(err_paths: List[str]) -> List[str]:
    """Copy each worker's captured stderr to our stderr (tests and
    humans both read the launcher's merged output) and return the text
    per worker for failure classification."""
    texts = []
    for rank, path in enumerate(err_paths):
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            data = b""
        text = data.decode("utf-8", "replace")
        texts.append(text)
        if text.strip():
            sys.stderr.write(f"--- worker {rank} stderr ---\n{text}")
            if not text.endswith("\n"):
                sys.stderr.write("\n")
            sys.stderr.flush()
    return texts


def _run_supervised(args) -> int:
    import shutil
    from .observability.log import get_logger
    slog = get_logger("analytics_zoo_tpu.launcher")
    run_dir = tempfile.mkdtemp(prefix="zoo-pod-")
    coordinator = args.coordinator or f"localhost:{_free_port()}"
    reasons: List[str] = []
    postmortems: List[str] = []
    rc = 1
    try:
        rc = _supervision_loop(args, slog, run_dir, coordinator,
                               reasons, postmortems)
    finally:
        restarts = sum(1 for r in reasons if r in ("exit", "watchdog"))
        port_retries = reasons.count("port")
        if args.summary_json:
            with open(args.summary_json, "w") as f:
                json.dump({"rc": rc, "restarts": restarts,
                           "port_retries": port_retries,
                           "reasons": reasons,
                           "postmortems": postmortems,
                           "metrics": train_metrics.snapshot()}, f)
        if rc == 0 and not postmortems:
            shutil.rmtree(run_dir, ignore_errors=True)
        else:
            # keep heartbeat/stderr/flight-recorder artifacts: even a
            # run that RECOVERED to rc 0 had an incident worth reading
            slog.info("supervision artifacts kept", run_dir=run_dir,
                      rc=rc, postmortems=postmortems)
    return rc


def _write_pod_postmortem(run_dir: str, outcome: str,
                          rank: Optional[int], incarnation: int,
                          procs: list, hb_ages: dict, slog,
                          stale_ranks: Optional[List[int]] = None
                          ) -> Optional[str]:
    """Harvest every worker's flight recorder and land the pod
    post-mortem: per-rank last steps, heartbeat timelines, final spans
    and log tails (flightrec.write_postmortem), merged with the
    supervisor-side evidence only it has — exit codes and
    heartbeat-file ages at reap time.  Also writes the aggregated
    pod-level scrape (``pod_metrics.prom``) beside it.  Best-effort:
    a postmortem failure must never eat the restart itself."""
    supervisor = {
        r: {"rc": p.returncode, "heartbeat_age_s": hb_ages.get(r)}
        for r, p in enumerate(procs)}
    path = os.path.join(run_dir, f"pod_postmortem.i{incarnation}.json")
    latest = os.path.join(run_dir, "pod_postmortem.json")
    try:
        pm = flightrec.write_postmortem(
            _flight_dir(run_dir), path, reason=outcome,
            failed_rank=rank, incarnation=incarnation,
            supervisor=supervisor,
            # a hung collective stalls EVERY participant's heartbeat;
            # the convicted rank is whichever the watchdog found first
            # — the full stale set is the honest evidence
            extra=({"stale_ranks": stale_ranks}
                   if stale_ranks is not None else None))
        flightrec.atomic_write(latest,
                               json.dumps(pm, indent=2, default=str))
    except Exception as e:
        slog.error("could not write pod postmortem", run_dir=run_dir,
                   error=f"{type(e).__name__}: {e}")
        return None
    try:
        from .observability import aggregate as _aggregate
        flightrec.atomic_write(
            os.path.join(run_dir, "pod_metrics.prom"),
            _aggregate.aggregate_dir(_flight_dir(run_dir)))
    except Exception:
        pass  # no snapshots yet is a legal postmortem state
    failed = pm.get("ranks", {}).get(str(rank), {})
    slog.error("pod postmortem written", path=path, reason=outcome,
               failed_rank=rank,
               last_step=failed.get("last_step"),
               heartbeat_age_s=failed.get("heartbeat_age_s"))
    return path


def _supervision_loop(args, slog, run_dir: str, coordinator: str,
                      reasons: List[str],
                      postmortems: Optional[List[str]] = None) -> int:
    restarts = 0
    port_retries = 0
    incarnation = 0
    rc = 1
    while True:
        started = time.time()
        procs, hb_paths, err_paths = _spawn_pod(
            args, coordinator, run_dir, incarnation,
            resume=restarts > 0)
        try:
            outcome, rank = _supervise(procs, hb_paths,
                                       args.watchdog_sec, started)
        except KeyboardInterrupt:
            # grace window first (mid-write checkpoint shards), then kill
            _reap_pod(procs, grace_s=10.0)
            _replay_stderr(err_paths)
            reasons.append("interrupt")
            rc = 130
            break
        if outcome == "ok":
            _replay_stderr(err_paths)
            rc = 0
            break
        # heartbeat-file ages sampled at detection time — reaping takes
        # up to the grace window and must not skew the postmortem.
        # stale_ranks = LIVE workers past the watchdog window (a hung
        # collective stalls every participant; an already-exited
        # worker's aging file is not a hang)
        now = time.time()
        hb_ages = {}
        stale_ranks = []
        for r, hb in enumerate(hb_paths):
            try:
                hb_ages[r] = round(now - os.path.getmtime(hb), 3)
            except OSError:
                hb_ages[r] = None  # worker died before its first beat
            if (outcome == "watchdog" and procs[r].poll() is None
                    and hb_ages[r] is not None
                    and hb_ages[r] > args.watchdog_sec):
                stale_ranks.append(r)
        failed_rc = procs[rank].returncode if outcome == "exit" else None
        _reap_pod(procs, grace_s=5.0,
                  kill_first=rank if outcome == "watchdog" else None)
        texts = _replay_stderr(err_paths)
        incarnation += 1
        # the documented _free_port race: worker 0 died at startup
        # failing to bind the probed coordinator port — retry the pod
        # on a fresh port without consuming the crash-restart budget
        if (outcome == "exit" and rank == 0 and not args.coordinator
                and time.time() - started < _STARTUP_WINDOW_S
                and port_retries < _PORT_RETRIES
                and _BIND_ERR_RE.search(texts[0] if texts else "")):
            port_retries += 1
            reasons.append("port")
            train_metrics.record_restart("port")
            coordinator = f"localhost:{_free_port()}"
            slog.warning("coordinator port collision — relaunching pod "
                         "on a fresh port", retry=port_retries,
                         coordinator=coordinator)
            continue
        # a real incident (crash or hang, not a bind race): harvest the
        # black boxes NOW — the next incarnation reuses the directory
        # namespace and a budget-exhausted exit must still explain itself
        pm = _write_pod_postmortem(
            run_dir, outcome, rank, incarnation - 1, procs, hb_ages,
            slog,
            stale_ranks=stale_ranks if outcome == "watchdog" else None)
        if pm and postmortems is not None:
            postmortems.append(pm)
        if restarts >= args.max_restarts:
            slog.error("pod failed and the restart budget is exhausted",
                       reason=outcome, rank=rank, rc=failed_rc,
                       restarts=restarts,
                       max_restarts=args.max_restarts)
            if failed_rc is None or failed_rc == 0:
                rc = 1
            elif failed_rc > 0:
                rc = failed_rc
            else:  # died on a signal: shell-style 128+N
                rc = 128 - failed_rc
            break
        restarts += 1
        reasons.append(outcome)
        train_metrics.record_restart(outcome)
        backoff = min(args.restart_backoff * (2 ** (restarts - 1)),
                      _MAX_BACKOFF_S)
        slog.warning("pod worker failed — relaunching with ZOO_RESUME",
                     reason=outcome, rank=rank, rc=failed_rc,
                     restart=restarts, max_restarts=args.max_restarts,
                     backoff_s=round(backoff, 3))
        time.sleep(backoff)
    return rc


def shell_main(argv: Optional[List[str]] = None) -> int:
    """``zoo-tpu-shell`` — the interactive-session launcher.

    Parity surface: reference ``scripts/jupyter-with-zoo.sh`` /
    ``pyspark-with-zoo.sh`` — open an interactive environment with the
    framework context already up.  ``zoo-tpu-shell`` starts an IPython
    (or plain) REPL with ``init_nncontext`` done and the common names
    bound; ``zoo-tpu-shell --jupyter`` execs Jupyter with the
    environment prepared the same way.
    """
    parser = argparse.ArgumentParser(
        prog="zoo-tpu-shell",
        description="Interactive REPL/Jupyter with the analytics-zoo-tpu "
                    "context initialized (reference jupyter-with-zoo.sh)")
    parser.add_argument("--jupyter", action="store_true",
                        help="launch jupyter notebook instead of a REPL")
    parser.add_argument("--app-name", default="zoo-tpu-shell")
    parser.add_argument("--platform", default=None,
                        help="force JAX_PLATFORMS (e.g. cpu)")
    parser.add_argument("--cpu-devices", type=int, default=None,
                        help="virtual CPU device count (sets "
                             "--xla_force_host_platform_device_count)")
    parser.add_argument("jupyter_args", nargs=argparse.REMAINDER,
                        help="passed through to jupyter")
    args = parser.parse_args(argv)

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    if args.cpu_devices:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", "")).strip()
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{args.cpu_devices}").strip()
    if args.platform and not args.jupyter:
        # jax is already imported (it reads the variable only at
        # import): pin the platform through jax.config too
        import jax
        jax.config.update("jax_platforms", args.platform)

    if args.jupyter:
        # exec jupyter in the prepared environment (the reference sets
        # PYSPARK_DRIVER_PYTHON=jupyter; here the env vars above are the
        # whole contract)
        cmd = ["jupyter", "notebook"] + [
            a for a in args.jupyter_args if a != "--"]
        os.execvp(cmd[0], cmd)

    import analytics_zoo_tpu as zoo
    ctx = zoo.init_nncontext(args.app_name)
    import jax
    import jax.numpy as jnp
    import numpy as np
    ns = {"zoo": zoo, "ctx": ctx, "jax": jax, "jnp": jnp, "np": np}
    banner = (f"analytics-zoo-tpu shell — ctx up "
              f"(mesh {dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))})\n"
              "bound: zoo, ctx, jax, jnp, np")
    print(banner)
    try:
        from IPython import start_ipython
        # display_banner is a Bool trait — the banner prints above,
        # IPython's own is suppressed via --no-banner
        return start_ipython(argv=["--no-banner"], user_ns=ns) or 0
    except ImportError:
        import code
        code.interact(banner="", local=ns)
        return 0


if __name__ == "__main__":
    sys.exit(main())
