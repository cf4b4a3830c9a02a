"""zoo-tpu-submit launcher (parity: scripts/spark-submit-with-zoo.sh):
single-process run and local multi-process fan-out forming a real
jax.distributed cluster."""

import os
import re
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEMO = textwrap.dedent("""
    import numpy as np
    import jax
    from analytics_zoo_tpu.common.context import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    ctx = init_nncontext(app_name="launcher-test")
    m = Sequential()
    m.add(Dense(8, input_shape=(4,), activation="relu"))
    m.add(Dense(2))
    m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    rng = np.random.default_rng(0)
    h = m.fit(rng.normal(size=(32, 4)).astype(np.float32),
              rng.integers(0, 2, 32).astype(np.int32),
              batch_size=8, nb_epoch=1)
    print(f"RESULT proc={jax.process_index()}/{jax.process_count()} "
          f"devices={jax.device_count()} loss={h['loss'][-1]:.4f}",
          flush=True)
""")


def _submit(args, script_path, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    for k in ("ZOO_TPU_COORDINATOR", "ZOO_TPU_NUM_PROCESSES",
              "ZOO_TPU_PROCESS_ID", "JAX_COORDINATOR_ADDRESS",
              "JAX_NUM_PROCESSES", "JAX_PROCESS_ID"):
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, "-m", "analytics_zoo_tpu.launcher"] + args
        + [str(script_path)],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=timeout)


def test_single_process(tmp_path):
    script = tmp_path / "demo.py"
    script.write_text(DEMO)
    proc = _submit(["--platform", "cpu"], script)
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert "RESULT proc=0/1" in proc.stdout


@pytest.mark.slow
def test_local_fanout_forms_cluster(tmp_path):
    script = tmp_path / "demo.py"
    script.write_text(DEMO)
    proc = _submit(["--num-processes", "2", "--devices-per-process", "4"],
                   script)
    assert proc.returncode == 0, proc.stdout[-2000:]
    lines = [l for l in proc.stdout.splitlines() if "RESULT" in l]
    assert len(lines) == 2, proc.stdout[-2000:]
    assert any("proc=0/2 devices=8" in l for l in lines), lines
    assert any("proc=1/2 devices=8" in l for l in lines), lines
    # replicated state: both processes observed the same loss
    losses = {l.split("loss=")[1] for l in lines}
    assert len(losses) == 1, lines


TRAIN_DEMO = textwrap.dedent("""
    import hashlib
    import os
    import numpy as np
    import optax
    import jax
    from analytics_zoo_tpu.common.context import init_nncontext
    from analytics_zoo_tpu.data.dataset import Dataset
    from analytics_zoo_tpu.train import triggers
    from analytics_zoo_tpu.train.trainer import Trainer
    from analytics_zoo_tpu.pipeline.api.keras import Sequential, objectives
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    import sys
    ckpt_dir = sys.argv[1]
    ctx = init_nncontext(app_name="supervised-drill")
    m = Sequential()
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(4))
    trainer = Trainer(m.to_graph(),
                      objectives.get("sparse_categorical_crossentropy"),
                      optax.sgd(0.1), mesh=ctx.mesh, seed=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.integers(0, 4, 64).astype(np.int32)
    ds = Dataset.from_ndarray(x, y)
    if jax.process_count() > 1:
        ds = ds.shard_by_process()
    trainer.set_checkpoint(ckpt_dir,
                           trigger=triggers.SeveralIteration(2))
    trainer.fit(ds, batch_size=16, end_trigger=triggers.MaxEpoch(3))
    digest = hashlib.sha256(b"".join(
        np.asarray(jax.device_get(leaf)).tobytes()
        for leaf in jax.tree_util.tree_leaves(trainer.state.params)))
    print(f"RESULT proc={jax.process_index()}/{jax.process_count()} "
          f"step={trainer.state.step} "
          f"resumed={1 if os.environ.get('ZOO_RESUME') else 0} "
          f"params={digest.hexdigest()}",
          flush=True)
""")


def _train_pod(tmp_path, name, faults):
    """One supervised 2-process pod of TRAIN_DEMO under ``faults``;
    returns (stdout, the supervisor's summary, each RESULT as
    (rank, step, resumed, digest of the parameters))."""
    import json
    script = tmp_path / "train_demo.py"
    script.write_text(TRAIN_DEMO)
    summary = tmp_path / f"{name}.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["ZOO_CKPT_SYNC"] = "1"
    for k in list(env):
        if k.startswith("ZOO_FAULT_") or k in (
                "ZOO_TPU_COORDINATOR", "ZOO_TPU_NUM_PROCESSES",
                "ZOO_TPU_PROCESS_ID", "ZOO_RESUME"):
            del env[k]
    env.update(faults)
    proc = subprocess.run(
        [sys.executable, "-m", "analytics_zoo_tpu.launcher",
         "--num-processes", "2", "--devices-per-process", "1",
         "--max-restarts", "2", "--restart-backoff", "0.25",
         "--summary-json", str(summary),
         str(script), str(tmp_path / name)],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:]
    # the two ranks share one pipe: a line may hold both RESULTs
    return (proc.stdout, json.loads(summary.read_text()),
            re.findall(r"RESULT proc=(\d)/2 step=(\d+) resumed=(\d) "
                       r"params=([0-9a-f]{64})", proc.stdout))


@pytest.mark.slow
def test_supervisor_recovers_sigkilled_worker_mid_epoch(tmp_path):
    """The full recovery loop on a REAL 2-process jax.distributed
    cluster: worker 1 SIGKILLs itself mid-epoch (ZOO_FAULT_CRASH_STEP)
    and the step-4 checkpoint's shard is byte-flipped after its commit;
    the supervisor reaps + relaunches with ZOO_RESUME, the restore
    convicts the corrupt tag and falls back to the one before, and the
    resumed pod finishes all 12 steps with parameters BIT-EQUAL to a
    pod that nothing interrupted."""
    _, summ, results = _train_pod(tmp_path, "whole", {})
    assert summ["restarts"] == 0
    [whole] = {digest for *_, digest in results}    # replicated state
    assert sorted(results) == [("0", "12", "0", whole),
                               ("1", "12", "0", whole)]

    out, summ, results = _train_pod(tmp_path, "killed", {
        "ZOO_FAULT_CRASH_STEP": "6", "ZOO_FAULT_CRASH_RANK": "1",
        "ZOO_FAULT_CORRUPT_TAG": "4"})
    assert summ["restarts"] == 1 and summ["reasons"] == ["exit"]
    assert "discarding corrupt checkpoint" in out
    # the final incarnation completed on both ranks, resumed, and
    # holds the uninterrupted pod's parameters
    assert sorted(results) == [("0", "12", "1", whole),
                               ("1", "12", "1", whole)]


def test_pod_mode_requires_coordinator(tmp_path):
    script = tmp_path / "demo.py"
    script.write_text("print('hi')")
    proc = _submit(["--num-processes", "4", "--process-id", "1"], script)
    assert proc.returncode != 0
    assert "--coordinator is required" in proc.stdout
    # pod flags without --num-processes must error, not silently run solo
    proc2 = _submit(["--process-id", "3"], script)
    assert proc2.returncode != 0
    assert "--num-processes" in proc2.stdout


def test_zoo_tpu_shell_repl(tmp_path):
    """zoo-tpu-shell (reference jupyter-with-zoo.sh analog): the REPL
    starts with the context up and the standard names bound, honoring
    --platform/--cpu-devices."""
    import subprocess, sys, os
    code = (
        "import sys, io\n"
        "import unittest.mock as mock\n"
        "with mock.patch.dict(sys.modules, {'IPython': None}):\n"
        "    sys.stdin = io.StringIO(\n"
        "        'print(\"NS\", \"zoo\" in dir(), \"ctx\" in dir(), "
        "len(jax.devices()))\\n')\n"
        "    from analytics_zoo_tpu.launcher import shell_main\n"
        "    sys.exit(shell_main(['--platform', 'cpu', "
        "'--cpu-devices', '4']))\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=240,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "NS True True 4" in proc.stdout, proc.stdout


def test_zoo_tpu_shell_ipython_path(tmp_path):
    """The PRIMARY shell path — IPython installed — must reach the REPL
    (regression: passing a str banner to start_ipython's Bool trait
    crashed before the prompt)."""
    import subprocess, sys, os
    pytest.importorskip("IPython")
    code = (
        "import sys, io\n"
        "sys.stdin = io.StringIO('print(\"IPY_OK\", type(ctx).__name__)\\n"
        "exit\\n')\n"
        "from analytics_zoo_tpu.launcher import shell_main\n"
        "sys.exit(shell_main(['--platform', 'cpu', "
        "'--cpu-devices', '2']) or 0)\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["TERM"] = "dumb"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=240,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-500:], proc.stderr[-500:])
    assert "IPY_OK NNContext" in proc.stdout, proc.stdout[-500:]
