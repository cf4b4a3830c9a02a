"""Persistent executable store (serving/execstore.py): fingerprint
invalidation, corruption fallback, zero-compile warm loads, LRU gc
with process-protected entries, the gc|stat CLI, and the no-store-I/O
-on-the-dispatch-path pin.

A note on what "zero-compile" means in ONE process: jax deduplicates
identical in-process compiles (a second ``lower().compile()`` of the
same HLO fires no ``backend_compile`` event even store-off), so the
in-process assertions here pin the STORE's own verdicts (hit / miss /
write / invalid counters) plus bit-exactness and sanitize-clean
loops.  The genuine two-process zero-compile proof — a fresh process
whose ``deploy()`` and ``DecodeEngine.warmup()`` record 0 compile
events against a warmed store — is the last test of this file
(``slow``: it starts two interpreters).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.serving import execstore
from analytics_zoo_tpu.serving.execstore import ExecStore


@pytest.fixture
def store(tmp_path):
    st = execstore.configure(str(tmp_path / "store"))
    yield st
    execstore.disable()


def _entry_files(st: ExecStore):
    return sorted(p for p in os.listdir(st.root) if p.endswith(".zexe"))


# ------------------------------------------------------------ raw store
def test_put_lookup_roundtrip_and_counters(store):
    fp = store.fingerprint("kind", "a", 1)
    assert store.lookup(fp) is None
    assert store.put(fp, b"payload-bytes", meta={"kind": "t", "k": 1})
    ent = store.lookup(fp)
    assert ent is not None
    assert ent.payload == b"payload-bytes"
    assert ent.meta["kind"] == "t" and ent.meta["k"] == 1
    s = store.stats()
    assert (s["miss"], s["hit"], s["write"], s["invalid"]) == (1, 1, 1, 0)
    assert s["entries"] == 1 and s["bytes"] > 0
    # no temp files left behind by the atomic publish
    assert _entry_files(store) == [fp + ".zexe"]


def test_fingerprint_is_order_and_content_sensitive(store):
    assert store.fingerprint("a", "b") != store.fingerprint("b", "a")
    assert store.fingerprint("a") != store.fingerprint("a", None)
    assert store.fingerprint(("x", 1)) == store.fingerprint(("x", 1))


def test_runtime_version_change_rotates_fingerprint(store, monkeypatch):
    """A jax/jaxlib version string bump must land on a different key —
    an executable serialized by another runtime is never even
    consulted."""
    fp_now = store.fingerprint("same-parts")
    monkeypatch.setattr(
        execstore, "_runtime_parts",
        lambda device=None: ("jax", "99.0.0", "jaxlib", "99.0.0",
                             "platform", "cpu", "device_kind", "cpu",
                             "xla_flags", ""))
    assert store.fingerprint("same-parts") != fp_now


@pytest.mark.parametrize("damage", ["bitflip", "truncate"])
def test_corrupt_entry_is_invalid_then_gone(store, damage):
    fp = store.fingerprint("corruptme")
    store.put(fp, b"x" * 256, meta={"kind": "t"})
    path = os.path.join(store.root, fp + ".zexe")
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        if damage == "bitflip":
            mid = len(raw) // 2
            f.write(raw[:mid] + bytes([raw[mid] ^ 0xFF]) + raw[mid + 1:])
        else:
            f.write(raw[: len(raw) // 3])
    assert store.lookup(fp) is None
    s = store.stats()
    assert s["invalid"] == 1
    # the corrupt file was removed so a recompile's write replaces it
    assert not os.path.exists(path)
    assert store.put(fp, b"fresh", meta={"kind": "t"})
    assert store.lookup(fp).payload == b"fresh"


def test_env_var_enables_store(tmp_path, monkeypatch):
    monkeypatch.setenv(execstore.ENV_DIR, str(tmp_path / "envstore"))
    monkeypatch.setenv(execstore.ENV_BUDGET, "12345")
    monkeypatch.setattr(execstore, "_current", None)
    monkeypatch.setattr(execstore, "_env_checked", False)
    st = execstore.current()
    try:
        assert st is not None
        assert st.root == str(tmp_path / "envstore")
        assert st.byte_budget == 12345
    finally:
        execstore.disable()


# ------------------------------------------------------------------- gc
def test_gc_evicts_lru_but_never_this_process_entries(store):
    """Eviction is oldest-mtime first and NEVER removes an entry this
    process wrote — a deploy's own executables must survive the gc
    that its own write triggered."""
    # foreign entries: written through a separate handle, so they are
    # protected in ITS process-set, not in `store`'s
    foreign = ExecStore(store.root)
    fps = []
    for i in range(4):
        fp = foreign.fingerprint("foreign", i)
        foreign.put(fp, bytes(200), meta={"kind": "f"})
        fps.append(fp)
        # stagger mtimes: fps[0] is the oldest
        os.utime(os.path.join(store.root, fp + ".zexe"),
                 (1000 + i, 1000 + i))
    mine = store.fingerprint("mine")
    store.put(mine, bytes(200), meta={"kind": "m"})
    os.utime(os.path.join(store.root, mine + ".zexe"), (10, 10))
    # budget = exactly the three entries that should survive (mine +
    # the two newest foreign); `mine` is the oldest of all but is
    # protected, so the two OLDEST foreign entries go instead
    size_of = {fp: os.path.getsize(os.path.join(store.root,
                                                fp + ".zexe"))
               for fp in fps + [mine]}
    res = store.gc(byte_budget=size_of[mine] + size_of[fps[2]]
                   + size_of[fps[3]])
    assert res["evicted"] == 2
    left = _entry_files(store)
    assert mine + ".zexe" in left
    # the two OLDEST foreign entries went first
    assert fps[0] + ".zexe" not in left and fps[1] + ".zexe" not in left
    assert fps[3] + ".zexe" in left
    assert store.stats()["evicted"] == 2


def test_cli_stat_and_gc(store, capsys):
    fp = store.fingerprint("cli")
    store.put(fp, bytes(512), meta={"kind": "demo"})
    assert execstore.main(["--root", store.root, "stat"]) == 0
    out = capsys.readouterr().out
    assert "1 entries" in out and fp[:16] in out and "demo" in out
    # a fresh CLI process protects nothing: budget 0 clears the store
    assert execstore.main(["--root", store.root, "gc",
                           "--budget", "0"]) == 0
    out = capsys.readouterr().out
    assert "evicted 1" in out
    assert _entry_files(store) == []


def test_stat_by_model_breakdown(store, capsys):
    """``stat --by-model`` aggregates entries/bytes per the writer's
    model tag (what a density fleet keeps on disk, per model);
    untagged entries fold under '-'."""
    for i in range(2):
        store.put(store.fingerprint("ncf", i), bytes(256),
                  meta={"kind": "replica-forward", "model": "ncf"})
    store.put(store.fingerprint("lm"), bytes(1024),
              meta={"kind": "decode-plan", "model": "lm"})
    store.put(store.fingerprint("untagged"), bytes(64),
              meta={"kind": "demo"})
    agg = store.by_model()
    assert agg["ncf"]["entries"] == 2
    assert agg["lm"]["entries"] == 1 and agg["lm"]["bytes"] > 1024
    assert agg["-"]["entries"] == 1
    assert execstore.main(
        ["--root", store.root, "stat", "--by-model"]) == 0
    out = capsys.readouterr().out
    assert "ncf" in out and "lm" in out and "4 entries" in out
    # biggest consumer prints first (the density question): the lm
    # entry's 1 KiB payload outweighs ncf's two 256 B ones
    assert out.index("lm") < out.index("ncf")


def test_registry_deploy_tags_entries_with_model_name(store):
    """The registry threads its model name into every entry the
    deploy persists — the by-model table is populated end to end."""
    from analytics_zoo_tpu.serving import ModelRegistry

    with ModelRegistry(max_batch_size=4) as reg:
        reg.deploy("tagged-mlp", jax_fn=_fwd, params=_mk_params(),
                   warmup_shapes=(8,))
    agg = store.by_model()
    assert agg.get("tagged-mlp", {}).get("entries", 0) >= 1


# ------------------------------------------------- ReplicaSet integration
def _fwd(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _mk_params(seed=0, d=8):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(d, d)).astype(np.float32) * 0.3,
            "b": np.zeros((d,), np.float32)}


def _mk_rs(params=None, **kw):
    from analytics_zoo_tpu.pipeline.inference.serving import ReplicaSet
    return ReplicaSet(_fwd, params if params is not None else _mk_params(),
                      devices=jax.local_devices()[:2], **kw)


def test_replicaset_store_hit_is_bitexact(store, zoolint_sanitize):
    x = np.ones((4, 8), np.float32)
    rs1 = _mk_rs()
    rs1.ensure_compiled(x)
    out1 = jax.device_get(rs1.dispatch(rs1.replicas[0], x))
    assert store.stats()["write"] == 1
    rs2 = _mk_rs()
    with zoolint_sanitize(max_compiles=0, transfer_guard=None):
        secs = rs2.ensure_compiled(x)
        out2 = jax.device_get(rs2.dispatch(rs2.replicas[1], x))
    assert secs > 0.0  # a load was performed (and timed), not skipped
    s = store.stats()
    assert s["hit"] == 1 and s["miss"] == 1 and s["invalid"] == 0
    assert np.array_equal(np.asarray(out1), np.asarray(out2))


def test_weights_change_is_a_store_miss(store):
    x = np.ones((4, 8), np.float32)
    _mk_rs(_mk_params(seed=0)).ensure_compiled(x)
    # same graph, same shapes, different weight VALUES: the executable
    # would be reusable (weights are runtime args) but the key must
    # rotate — an old-weights entry answering a new-weights deploy is
    # the kind of "correct-looking" reuse the fingerprint forbids
    _mk_rs(_mk_params(seed=1)).ensure_compiled(x)
    s = store.stats()
    assert s["miss"] == 2 and s["write"] == 2 and s["hit"] == 0


def test_bucket_config_change_is_a_store_miss(store):
    rs = _mk_rs()
    rs.ensure_compiled(np.ones((4, 8), np.float32))
    rs.ensure_compiled(np.ones((16, 8), np.float32))  # a new ladder top
    s = store.stats()
    assert s["miss"] == 2 and s["write"] == 2 and s["hit"] == 0


def test_replicaset_corrupt_entry_recompiles_never_serves_wrong(store):
    x = np.arange(32, dtype=np.float32).reshape(4, 8)
    rs1 = _mk_rs()
    rs1.ensure_compiled(x)
    expected = jax.device_get(rs1.dispatch(rs1.replicas[0], x))
    # flip a byte in the middle of the only entry
    name = _entry_files(store)[0]
    path = os.path.join(store.root, name)
    raw = open(path, "rb").read()
    mid = len(raw) // 2
    with open(path, "wb") as f:
        f.write(raw[:mid] + bytes([raw[mid] ^ 0xFF]) + raw[mid + 1:])
    rs2 = _mk_rs()
    rs2.ensure_compiled(x)  # falls back to compile, silently
    out = jax.device_get(rs2.dispatch(rs2.replicas[0], x))
    s = store.stats()
    assert s["invalid"] == 1
    assert s["write"] == 2  # the recompile re-persisted the entry
    assert np.array_equal(np.asarray(out), np.asarray(expected))


def test_replicaset_without_store_touches_no_disk(tmp_path):
    """Default (unconfigured) path: no store, no files, PR 5 behavior."""
    assert execstore.current() is None
    rs = _mk_rs()
    assert rs._store is None
    rs.ensure_compiled(np.ones((2, 8), np.float32))
    assert not list(tmp_path.iterdir())


# ----------------------------------------------- DecodeEngine integration
VOCAB, SEQ, BUCKET = 48, 40, 8


@pytest.fixture(scope="module")
def lm():
    from analytics_zoo_tpu.models import TransformerLM
    net = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                       d_model=32, n_heads=4)
    net.ensure_inference_ready()
    return net


def _mk_engine(lm, capacity=2):
    from analytics_zoo_tpu.pipeline.inference.decode import DecodeEngine
    return DecodeEngine(lm.trainer.state.params, lm.hyper,
                        capacity=capacity, max_len=SEQ,
                        prompt_buckets=(BUCKET,))


def _prompts(n=3):
    rng = np.random.default_rng(7)
    return [rng.integers(0, VOCAB, int(rng.integers(3, BUCKET)))
            for _ in range(n)]


def test_decode_warm_engine_loads_all_plans_bit_identical(store, lm):
    e1 = _mk_engine(lm)
    e1.warmup()
    out1 = e1.generate(_prompts(), 5, timeout=120)
    e1.close()
    writes = store.stats()["write"]
    assert writes >= 3  # admit plan + step plan + fused ladder
    e2 = _mk_engine(lm)
    e2.warmup()
    out2 = e2.generate(_prompts(), 5, timeout=120)
    e2.close()
    s = store.stats()
    assert s["hit"] == writes and s["write"] == writes
    assert s["invalid"] == 0
    assert all(np.array_equal(a, b) for a, b in zip(out1, out2))


def test_decode_capacity_change_is_a_store_miss(store, lm):
    e1 = _mk_engine(lm, capacity=2)
    e1.warmup()
    e1.close()
    writes = store.stats()["write"]
    e2 = _mk_engine(lm, capacity=3)  # different slot array: new plans
    e2.warmup()
    e2.close()
    s = store.stats()
    assert s["hit"] == 0 and s["write"] == 2 * writes


def test_decode_corrupt_entries_recompile_and_stay_correct(store, lm):
    e1 = _mk_engine(lm)
    e1.warmup()
    out1 = e1.generate(_prompts(), 5, timeout=120)
    e1.close()
    # corrupt EVERY persisted plan
    for name in _entry_files(store):
        path = os.path.join(store.root, name)
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(raw[: len(raw) - 7])
    e2 = _mk_engine(lm)
    e2.warmup()
    out2 = e2.generate(_prompts(), 5, timeout=120)
    e2.close()
    s = store.stats()
    assert s["invalid"] >= 3  # every plan fell back to a compile
    assert all(np.array_equal(a, b) for a, b in zip(out1, out2))


# ------------------------------------------- deploy-level + hot-path pin
def test_store_routes_single_device_through_replica_path(store):
    """With the store on, even a 1-replica model serves through the
    raw-dispatch ReplicaSet (the only path that can execute a
    store-loaded serialized executable)."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    im = InferenceModel(replicas=1)
    im.load_jax(_fwd, _mk_params())
    try:
        assert im._cache is not None
        assert im._cache.replica_set is not None
        assert im.n_replicas == 1
    finally:
        im.close()


def test_store_off_keeps_single_device_closure_path():
    assert execstore.current() is None
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    im = InferenceModel(replicas=1)
    im.load_jax(_fwd, _mk_params())
    try:
        assert im._cache is not None
        assert im._cache.replica_set is None  # PR 1 path, untouched
    finally:
        im.close()


def test_no_store_io_on_warmed_dispatch_path(store, zoolint_sanitize,
                                             monkeypatch):
    """The satellite pin: with the store ENABLED, a warmed serving
    loop performs no store file I/O at all — lookups exist only where
    a compile would otherwise happen.  Enforced two ways: the lookup
    method is booby-trapped after warmup, and the loop runs
    sanitize-clean (0 compiles, transfer guards on)."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    im = InferenceModel(replicas=2, coalescing=True)
    im.load_jax(_fwd, _mk_params())
    im.warmup((8,))
    x = np.ones((4, 8), np.float32)
    im.predict(x)  # warm the exact live placement combo
    try:
        def _boom(self, fp):
            raise AssertionError(
                "execstore.lookup on the per-dispatch path")

        monkeypatch.setattr(ExecStore, "lookup", _boom)
        with zoolint_sanitize(max_compiles=0):
            for _ in range(8):
                im.predict(x)
    finally:
        im.close()


# ------------------------------------------ two processes, one store
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One process of the cold-start proof: deploy a seeded MLP through the
# registry and warm a decode engine, counting ``backend_compile`` events
# inside exactly those two calls.  The store engages through
# ZOO_EXECSTORE_DIR alone.  argv: work directory, "cold" | "warm".
_DEPLOY_ONCE = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    import jax.numpy as jnp
    from jax._src import monitoring

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda key, _s, **kw: (compiles.append(key)
                               if "backend_compile" in key else None))

    from analytics_zoo_tpu.models import TransformerLM
    from analytics_zoo_tpu.pipeline.inference.decode import DecodeEngine
    from analytics_zoo_tpu.serving import ModelRegistry, execstore

    work, role = sys.argv[1], sys.argv[2]
    store = execstore.current()
    assert store is not None, "ZOO_EXECSTORE_DIR not honoured"
    rng = np.random.default_rng(0)
    params = {f"w{i}": rng.normal(size=(16, 16)).astype(np.float32) * 0.1
              for i in range(4)}

    def mlp(p, x):
        for i in range(4):
            x = jnp.tanh(x @ p[f"w{i}"])
        return x

    res = {}
    reg = ModelRegistry(replicas="all", max_batch_size=8)
    n0 = len(compiles)
    reg.deploy("mlp", jax_fn=mlp, params=params, warmup_shapes=(16,))
    res["deploy_compiles"] = len(compiles) - n0
    out = np.asarray(reg.predict(
        "mlp", rng.normal(size=(4, 16)).astype(np.float32)))

    lm = TransformerLM(vocab_size=64, seq_len=48, n_layers=2,
                       d_model=32, n_heads=4)
    trainer = lm.ensure_inference_ready()
    prompts = [rng.integers(0, 64, int(rng.integers(4, 16)))
               for _ in range(3)]
    # the slot array's zero fills are programs too: built outside the
    # counted call, they are state, not plans a store could serve
    engine = DecodeEngine(trainer.state.params, lm.hyper, capacity=2,
                          max_len=48, prompt_buckets=(16,))
    n1 = len(compiles)
    engine.warmup()
    res["warmup_compiles"] = len(compiles) - n1
    toks = engine.generate(prompts, 6, timeout=300)
    engine.close()
    reg.shutdown()

    expect = os.path.join(work, "expect.npz")
    if role == "cold":
        np.savez(expect, out, *toks)
        res["same_answers"] = True
    else:
        with np.load(expect) as z:
            res["same_answers"] = (
                len(z.files) == 1 + len(toks)
                and all(np.array_equal(got, z[f"arr_{i}"])
                        for i, got in enumerate([out, *toks])))
    res["store"] = {k: store.stats()[k]
                    for k in ("hit", "write", "invalid")}
    print("DEPLOYED " + json.dumps(res), flush=True)
""")


def test_second_process_deploys_from_a_warm_store_with_zero_compiles(
        tmp_path):
    """A first process deploys and decode-warms against an empty store
    and exits; a second, fresh process (nothing shared but the store
    directory) repeats the same deploy and records no compile event
    inside ``deploy()`` or ``DecodeEngine.warmup()``, with the first
    one's answers bit for bit."""
    script = tmp_path / "deploy_once.py"
    script.write_text(_DEPLOY_ONCE)
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "ZOO_EXECSTORE_DIR": str(tmp_path / "store"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}

    def deploy_once(role):
        proc = subprocess.run(
            [sys.executable, str(script), str(tmp_path), role], env=env,
            cwd=REPO, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        [line] = [ln for ln in proc.stdout.splitlines()
                  if ln.startswith("DEPLOYED ")]
        return json.loads(line[len("DEPLOYED "):])

    cold = deploy_once("cold")
    # a zero means something only where an empty store compiles
    assert cold["deploy_compiles"] > 0 and cold["warmup_compiles"] > 0
    assert cold["store"]["write"] > 0
    warm = deploy_once("warm")
    assert warm["deploy_compiles"] == 0 and warm["warmup_compiles"] == 0
    assert warm["same_answers"]
    assert warm["store"]["hit"] > 0 and warm["store"]["invalid"] == 0
