"""Sharded checkpointing: per-shard save (no host-0 gather), restore with
re-sharding onto a different mesh shape, async writer, fit-resume under
fsdp.

Parity: the reference's epoch-trigger checkpoints (Topology.scala:184-194)
+ SURVEY §5's prescription of sharded TrainState snapshots for SPMD
failure recovery (no Spark lineage to lean on).
"""

import os

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.train.checkpoint import (
    async_save_sharded, restore_sharded, read_meta, save_sharded,
    wait_pending)


def _tree():
    rng = np.random.default_rng(0)
    return {"w": rng.normal(size=(16, 8)).astype(np.float32),
            "b": rng.normal(size=(8,)).astype(np.float32),
            "step": np.int32(7)}


def test_roundtrip_across_mesh_shapes(tmp_path):
    """Save under {data:2, fsdp:4} with w sharded over fsdp; restore onto
    {data:8} fully replicated AND onto {data:2, fsdp:2, tensor:2} with a
    different partitioning — values identical each way."""
    tree = _tree()
    mesh1 = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    placed = {
        "w": jax.device_put(tree["w"],
                            NamedSharding(mesh1, P("fsdp", None))),
        "b": jax.device_put(tree["b"], NamedSharding(mesh1, P())),
        "step": tree["step"],
    }
    save_sharded(str(tmp_path), "t1", placed, meta={"epoch": 3})

    # restore onto an 8-wide pure-data mesh, replicated
    mesh2 = mesh_lib.create_mesh({"data": 8})
    restored = restore_sharded(
        str(tmp_path), jax.tree_util.tree_map(np.zeros_like, tree), "t1",
        shardings={"w": NamedSharding(mesh2, P()),
                   "b": NamedSharding(mesh2, P()), "step": None})
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    np.testing.assert_array_equal(np.asarray(restored["b"]), tree["b"])
    assert int(restored["step"]) == 7

    # restore onto a third mesh with a different partitioning of w
    mesh3 = mesh_lib.create_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    restored3 = restore_sharded(
        str(tmp_path), jax.tree_util.tree_map(np.zeros_like, tree), "t1",
        shardings={"w": NamedSharding(mesh3, P("tensor", "fsdp")),
                   "b": NamedSharding(mesh3, P("fsdp")), "step": None})
    np.testing.assert_array_equal(np.asarray(restored3["w"]), tree["w"])
    assert restored3["w"].sharding.spec == P("tensor", "fsdp")
    assert read_meta(str(tmp_path), "t1") == {"epoch": 3}


def test_replicated_leaves_stored_once(tmp_path):
    """replica_id dedup: a fully replicated leaf on 8 devices is written
    exactly once, not 8 times."""
    mesh = mesh_lib.create_mesh({"data": 8})
    placed = {"w": jax.device_put(np.ones((4, 4), np.float32),
                                  NamedSharding(mesh, P()))}
    path = save_sharded(str(tmp_path), "t2", placed)
    with np.load(path) as data:
        assert len(data.files) == 1
        assert data[data.files[0]].shape == (4, 4)


def test_async_save_sharded_joins(tmp_path):
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    placed = {"w": jax.device_put(np.arange(32, dtype=np.float32
                                            ).reshape(8, 4),
                                  NamedSharding(mesh, P("fsdp", None)))}
    async_save_sharded(str(tmp_path), "t3", placed, meta={"step": 1})
    wait_pending(str(tmp_path))
    restored = restore_sharded(str(tmp_path),
                               {"w": np.zeros((8, 4), np.float32)}, "t3")
    np.testing.assert_array_equal(restored["w"],
                                  np.arange(32).reshape(8, 4))


def test_missing_shard_file_detected(tmp_path):
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    placed = {"w": jax.device_put(np.ones((8, 4), np.float32),
                                  NamedSharding(mesh, P("fsdp", None)))}
    path = save_sharded(str(tmp_path), "t4", placed)
    # corrupt: drop half the entries by rewriting the shard file
    with np.load(path) as data:
        keys = sorted(data.files)
        kept = {k: data[k] for k in keys[: len(keys) // 2]}
    np.savez(path, **kept)
    with pytest.raises(ValueError, match="elements|missing"):
        restore_sharded(str(tmp_path), {"w": np.zeros((8, 4), np.float32)},
                        "t4")


def test_stale_shards_from_larger_pod_ignored(tmp_path):
    """Re-saving a tag with fewer processes must not merge stale shard
    files left by an earlier larger-pod save: the manifest records
    n_processes and restore reads exactly that set."""
    import shutil
    mesh = mesh_lib.create_mesh({"data": 8})
    placed = {"w": jax.device_put(np.ones((4, 4), np.float32),
                                  NamedSharding(mesh, P()))}
    path = save_sharded(str(tmp_path), "t6", placed)
    # forge a stale shard file from a hypothetical process 1 of an older,
    # larger-pod save, holding DIFFERENT data
    stale = os.path.join(str(tmp_path), "ckpt_t6.shard-p1.npz")
    np.savez(stale, **{"0|0:4,0:4": np.full((4, 4), 99.0, np.float32)})
    restored = restore_sharded(str(tmp_path),
                               {"w": np.zeros((4, 4), np.float32)}, "t6")
    np.testing.assert_array_equal(restored["w"], np.ones((4, 4)))


def test_fit_resume_under_fsdp(tmp_path):
    """Interrupted fit under the fsdp strategy resumes from the sharded
    epoch checkpoint and lands on the SAME params as the uninterrupted
    2-epoch run (epoch counting + shuffle seeds included)."""
    from analytics_zoo_tpu.data.dataset import Dataset
    from analytics_zoo_tpu.train.trainer import Trainer
    from analytics_zoo_tpu.pipeline.api.keras import Sequential, objectives
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.train import triggers

    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    rng = np.random.default_rng(3)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.integers(0, 4, 64).astype(np.int32)
    ds = Dataset.from_ndarray(x, y)

    def make_trainer():
        m = Sequential()
        # explicit names: auto-numbered layers flatten in LEXICOGRAPHIC
        # order (dense_10 sorts before dense_9), so two builds that
        # straddle a digit boundary of the process-wide counter would
        # zip() the wrong leaves together below
        m.add(Dense(4096, activation="relu", input_shape=(8,),
                    name="hid"))
        m.add(Dense(4, name="out"))
        return Trainer(m.to_graph(),
                       objectives.get("sparse_categorical_crossentropy"),
                       optax.sgd(0.05, momentum=0.9), mesh=mesh,
                       strategy="fsdp", seed=0)

    # uninterrupted: 2 epochs
    t_full = make_trainer()
    t_full.fit(ds, batch_size=16, end_trigger=triggers.MaxEpoch(2))

    # interrupted: 1 epoch with checkpointing, then resume in a NEW trainer
    ckpt = str(tmp_path / "ckpt")
    t_a = make_trainer()
    t_a.set_checkpoint(ckpt)
    t_a.fit(ds, batch_size=16, end_trigger=triggers.MaxEpoch(1))
    t_b = make_trainer()
    t_b.load_weights(ckpt)  # latest = epoch1, re-sharded onto fsdp
    assert t_b.state.epoch == 1
    t_b.fit(ds, batch_size=16, end_trigger=triggers.MaxEpoch(2))

    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_flatten_with_path(t_full.state.params)[0],
            jax.tree_util.tree_flatten_with_path(t_b.state.params)[0]):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-4, atol=1e-5, err_msg=str(pa))
    # the resumed trainer's params still carry the fsdp shardings
    flat = jax.tree_util.tree_leaves(t_b.state.params)
    assert any(getattr(l.sharding, "spec", P()) != P() for l in flat)


def test_keras_fit_auto_resume(tmp_path):
    """fit(resume=True): the crash-recovery one-liner (SURVEY §5).  A
    fresh run starts normally; a re-run of the SAME script after an
    interruption restores the newest snapshot and continues epochs."""
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    def make():
        m = Sequential()
        m.add(Dense(4, input_shape=(6,)))
        m.compile(optimizer="sgd", loss="mean_squared_error")
        m.set_checkpoint(str(tmp_path / "ckpt"))
        return m

    rs = np.random.RandomState(0)
    x = rs.rand(64, 6).astype(np.float32)
    y = rs.rand(64, 4).astype(np.float32)

    # fresh run: resume=True with an empty dir just starts
    m1 = make()
    m1.fit(x, y, batch_size=16, nb_epoch=2, resume=True)
    assert m1.trainer.state.epoch == 2
    from analytics_zoo_tpu.train.checkpoint import wait_pending
    wait_pending()

    # "crashed" -> new process = new model object; same script re-runs
    m2 = make()
    m2.fit(x, y, batch_size=16, nb_epoch=3, resume=True)
    # resumed at epoch 2, trained 3 MORE epochs
    assert m2.trainer.state.epoch == 5

    # resume without set_checkpoint is a usage error
    m3 = Sequential()
    m3.add(Dense(4, input_shape=(6,)))
    m3.compile(optimizer="sgd", loss="mean_squared_error")
    import pytest as _pytest
    with _pytest.raises(ValueError, match="set_checkpoint"):
        m3.fit(x, y, batch_size=16, nb_epoch=1, resume=True)


def test_restore_fills_post_save_state_leaf_by_name(tmp_path):
    """Structure evolution (r5): a checkpoint saved BEFORE a layer grew
    a new state leaf (BatchNormalization's debias ``count``) must still
    restore — leaves match by manifest name, and the absent ``count``
    fills from its registered default (inf = converged pass-through).
    An absent leaf with NO registered default still fails loudly."""
    from analytics_zoo_tpu.train.checkpoint import (restore_sharded,
                                                    save_sharded)
    old = {"params": {"dense": {"W": np.arange(6, dtype=np.float32)
                                .reshape(2, 3)}},
           "model_state": {"bn_7": {
               "moving_mean": np.array([1.0, 2.0], np.float32),
               "moving_var": np.array([3.0, 4.0], np.float32)}}}
    save_sharded(str(tmp_path), 1, old)

    template = {"params": {"dense": {"W": np.zeros((2, 3), np.float32)}},
                "model_state": {"bn_7": {
                    "moving_mean": np.zeros(2, np.float32),
                    "moving_var": np.ones(2, np.float32),
                    "count": np.zeros((), np.float32)}}}
    out = restore_sharded(str(tmp_path), template, 1)
    np.testing.assert_array_equal(out["params"]["dense"]["W"],
                                  old["params"]["dense"]["W"])
    np.testing.assert_array_equal(
        out["model_state"]["bn_7"]["moving_mean"], [1.0, 2.0])
    assert np.isinf(out["model_state"]["bn_7"]["count"])

    bad_template = dict(template)
    bad_template["params"] = {"dense": {
        "W": np.zeros((2, 3), np.float32),
        "brand_new_bias": np.zeros(3, np.float32)}}
    with pytest.raises(ValueError, match="no restore default"):
        restore_sharded(str(tmp_path), bad_template, 1)


def test_flat_restore_fills_post_save_state_leaf_by_name(tmp_path):
    """The FLAT format (save_checkpoint/restore_checkpoint — the
    NNModel.save path) gets the same structure-evolution bridge via its
    name manifest."""
    from analytics_zoo_tpu.train.checkpoint import (restore_checkpoint,
                                                    save_checkpoint)
    old = {"model_state": {"bn": {
        "moving_mean": np.array([1.0, 2.0], np.float32),
        "moving_var": np.array([3.0, 4.0], np.float32)}},
        "params": {"d": {"W": np.ones((2, 2), np.float32)}}}
    save_checkpoint(str(tmp_path), 2, old)
    template = {"model_state": {"bn": {
        "moving_mean": np.zeros(2, np.float32),
        "moving_var": np.ones(2, np.float32),
        "count": np.zeros((), np.float32)}},
        "params": {"d": {"W": np.zeros((2, 2), np.float32)}}}
    out = restore_checkpoint(str(tmp_path), template, 2)
    np.testing.assert_array_equal(out["model_state"]["bn"]["moving_var"],
                                  [3.0, 4.0])
    assert np.isinf(out["model_state"]["bn"]["count"])
    np.testing.assert_array_equal(out["params"]["d"]["W"],
                                  np.ones((2, 2)))


def test_restore_survives_autonumber_digit_boundary_flip(tmp_path):
    """Dict keys flatten lexicographically, so auto-numbered layer names
    crossing a digit boundary flip leaf ORDER: a save from a build with
    dense_99+dense_100 lists the 100 BEFORE the 99, while the restoring
    build's dense_101+dense_102 keep construction order. Blind
    positional loading puts weights in the wrong layers (caught live as
    a broadcast error, r5); the name/shape matcher must place them
    correctly in BOTH formats."""
    from analytics_zoo_tpu.train.checkpoint import (restore_checkpoint,
                                                    restore_sharded,
                                                    save_checkpoint,
                                                    save_sharded)
    w_big = np.arange(32, dtype=np.float32).reshape(8, 4)
    w_small = np.arange(8, dtype=np.float32).reshape(4, 2)
    # saved build: auto-numbers straddle the 2->3 digit boundary, so
    # flatten order is [dense_100 (small), dense_99 (big)]
    saved = {"params": {"dense_99": {"W": w_big},
                        "dense_100": {"W": w_small}}}
    # restoring build: same model, later counter — order [big, small]
    template = {"params": {"dense_101": {"W": np.zeros((8, 4),
                                                       np.float32)},
                           "dense_102": {"W": np.zeros((4, 2),
                                                       np.float32)}}}
    save_checkpoint(str(tmp_path / "flat"), 1, saved)
    out = restore_checkpoint(str(tmp_path / "flat"), template, 1)
    np.testing.assert_array_equal(out["params"]["dense_101"]["W"], w_big)
    np.testing.assert_array_equal(out["params"]["dense_102"]["W"],
                                  w_small)

    save_sharded(str(tmp_path / "sh"), 1, saved)
    out = restore_sharded(str(tmp_path / "sh"), template, 1)
    np.testing.assert_array_equal(out["params"]["dense_101"]["W"], w_big)
    np.testing.assert_array_equal(out["params"]["dense_102"]["W"],
                                  w_small)


def test_restore_bridges_renamed_layers(tmp_path):
    """A checkpoint saved under a layer's OLD name — TransformerLM's
    pre-generate() ``embedding_1``/``positionalembedding_1`` vs today's
    ``tok_embed``/``pos_embed`` — restores through the RESTORE_RENAMES
    alias table.  Aliases run only over leaves the primary name+shape
    matcher left unpaired, so models legitimately containing both
    spellings keep their direct matches."""
    from analytics_zoo_tpu.train.checkpoint import (restore_checkpoint,
                                                    restore_sharded,
                                                    save_checkpoint,
                                                    save_sharded)
    tok = np.arange(12, dtype=np.float32).reshape(4, 3)
    pos = 10.0 * np.arange(6, dtype=np.float32).reshape(2, 3)
    saved = {"params": {
        "embedding_1": {"weights": tok},
        "positionalembedding_1": {"weights": pos}}}
    template = {"params": {
        "tok_embed": {"weights": np.zeros((4, 3), np.float32)},
        "pos_embed": {"weights": np.zeros((2, 3), np.float32)}}}
    save_checkpoint(str(tmp_path / "flat"), 1, saved)
    out = restore_checkpoint(str(tmp_path / "flat"), template, 1)
    np.testing.assert_array_equal(out["params"]["tok_embed"]["weights"],
                                  tok)
    np.testing.assert_array_equal(out["params"]["pos_embed"]["weights"],
                                  pos)
    save_sharded(str(tmp_path / "sh"), 1, saved)
    out = restore_sharded(str(tmp_path / "sh"), template, 1)
    np.testing.assert_array_equal(out["params"]["tok_embed"]["weights"],
                                  tok)

    # a save with BOTH spellings present: the direct match wins — the
    # alias pass never hijacks a template leaf the primary matcher
    # already paired
    both_saved = {"params": {
        "embedding_1": {"weights": tok},
        "positionalembedding_1": {"weights": pos},
        "tok_embed": {"weights": 2.0 * tok}}}
    both_tmpl = {"params": {
        "tok_embed": {"weights": np.zeros((4, 3), np.float32)}}}
    save_checkpoint(str(tmp_path / "both"), 1, both_saved)
    out = restore_checkpoint(str(tmp_path / "both"), both_tmpl, 1)
    np.testing.assert_array_equal(
        out["params"]["tok_embed"]["weights"], 2.0 * tok)

    # WITHOUT the full migration signature the aliases stay inert and
    # structure drift keeps failing loudly.  (a) no positionalembedding
    # sibling in the save; (b) a CURRENT model whose auto-named
    # PositionalEmbedding direct-matches — its template has no
    # unmatched pos_embed, so a leftover generic embedding leaf must
    # not silently pair with a same-shape template leaf that happens to
    # be named tok_embed.
    loose_saved = {"params": {"embedding_1": {"weights": tok}}}
    loose_tmpl = {"params": {
        "tok_embed": {"weights": np.zeros((4, 3), np.float32)}}}
    save_checkpoint(str(tmp_path / "loose"), 1, loose_saved)
    with pytest.raises(ValueError, match="no restore default"):
        restore_checkpoint(str(tmp_path / "loose"), loose_tmpl, 1)

    live_saved = {"params": {
        "positionalembedding_1": {"weights": pos},
        "embedding_1": {"weights": tok}}}
    live_tmpl = {"params": {
        "positionalembedding_1": {"weights": np.zeros((2, 3),
                                                      np.float32)},
        "tok_embed": {"weights": np.zeros((4, 3), np.float32)}}}
    save_checkpoint(str(tmp_path / "live"), 1, live_saved)
    with pytest.raises(ValueError, match="no restore default"):
        restore_checkpoint(str(tmp_path / "live"), live_tmpl, 1)


def test_commit_manifest_written_last_and_covers_all_files(tmp_path):
    """Crash-safe commit: every save ends with ckpt_<tag>.commit.json
    recording byte sizes + sha256 of every file the tag comprises —
    the atomic rename of that manifest IS the commit point."""
    import hashlib
    import json
    from analytics_zoo_tpu.train.checkpoint import (read_commit,
                                                    verify_commit)
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    placed = {"w": jax.device_put(np.ones((8, 4), np.float32),
                                  NamedSharding(mesh, P("fsdp", None)))}
    save_sharded(str(tmp_path), "c1", placed, meta={"step": 1})
    commit = read_commit(str(tmp_path), "c1")
    assert set(commit["files"]) == {"ckpt_c1.shard-p0.npz",
                                    "ckpt_c1.json"}
    assert commit["n_processes"] == 1
    for fn, rec in commit["files"].items():
        path = tmp_path / fn
        assert path.stat().st_size == rec["bytes"]
        assert hashlib.sha256(path.read_bytes()).hexdigest() == \
            rec["sha256"]
    assert verify_commit(str(tmp_path), "c1", deep=True) == (True, "ok")


def test_torn_tag_without_commit_skipped_for_newest_complete(tmp_path):
    """Selection ignores a tag whose shards exist but whose commit
    never landed (the crash-mid-async-save signature): latest_tag and
    tag-less restore both fall back to the newest COMPLETE tag."""
    from analytics_zoo_tpu.train.checkpoint import latest_tag
    t1 = {"w": np.full((4, 4), 1.0, np.float32)}
    t2 = {"w": np.full((4, 4), 2.0, np.float32)}
    save_sharded(str(tmp_path), 1, t1, meta={"step": 1})
    save_sharded(str(tmp_path), 2, t2, meta={"step": 2})
    # tear tag 2: shards on disk, commit manifest gone
    os.remove(str(tmp_path / "ckpt_2.commit.json"))
    assert latest_tag(str(tmp_path)) == "1"
    out = restore_sharded(str(tmp_path),
                          {"w": np.zeros((4, 4), np.float32)})
    np.testing.assert_array_equal(out["w"], t1["w"])
    assert read_meta(str(tmp_path)) == {"step": 1}


def test_checksum_mismatch_deletes_tag_and_falls_back(tmp_path):
    """A committed tag whose shard bytes were damaged after the commit
    (bit rot, torn overwrite) is convicted by its sha256 at restore,
    DELETED, and selection falls back — a crash may cost lost steps,
    never a wrong or torn restore.  With no complete tag left, restore
    is a clean FileNotFoundError (cold start)."""
    t1 = {"w": np.full((4, 4), 1.0, np.float32)}
    t2 = {"w": np.full((4, 4), 2.0, np.float32)}
    save_sharded(str(tmp_path), 1, t1, meta={"step": 1})
    save_sharded(str(tmp_path), 2, t2, meta={"step": 2})
    shard2 = tmp_path / "ckpt_2.shard-p0.npz"
    data = bytearray(shard2.read_bytes())
    data[len(data) // 2] ^= 0xFF  # same size, different bytes
    shard2.write_bytes(bytes(data))
    out = restore_sharded(str(tmp_path),
                          {"w": np.zeros((4, 4), np.float32)})
    np.testing.assert_array_equal(out["w"], t1["w"])
    # the corrupt tag was deleted wholesale, not just skipped
    assert not any("ckpt_2" in f for f in os.listdir(tmp_path))
    # damage the survivor too: no complete tag left -> cold start
    shard1 = tmp_path / "ckpt_1.shard-p0.npz"
    data = bytearray(shard1.read_bytes())
    data[len(data) // 2] ^= 0xFF
    shard1.write_bytes(bytes(data))
    with pytest.raises(FileNotFoundError):
        restore_sharded(str(tmp_path),
                        {"w": np.zeros((4, 4), np.float32)})


def test_explicit_corrupt_tag_raises_instead_of_fallback(tmp_path):
    """An explicitly requested tag that fails its checksums raises
    (there is no meaningful fallback for a caller who named the tag)."""
    tree = {"w": np.full((4, 4), 3.0, np.float32)}
    save_sharded(str(tmp_path), "x", tree)
    shard = tmp_path / "ckpt_x.shard-p0.npz"
    data = bytearray(shard.read_bytes())
    data[len(data) // 2] ^= 0xFF
    shard.write_bytes(bytes(data))
    with pytest.raises(ValueError, match="commit manifest"):
        restore_sharded(str(tmp_path),
                        {"w": np.zeros((4, 4), np.float32)}, "x")


def test_undeletable_corrupt_tag_raises_instead_of_spinning(tmp_path,
                                                            monkeypatch):
    """When the corrupt tag cannot actually be removed (read-only
    mirror, permissions — discard_tag swallows the OSError), selection
    must refuse loudly instead of re-verifying the same tag forever."""
    from analytics_zoo_tpu.train import checkpoint as ckpt_lib
    tree = {"w": np.full((4, 4), 3.0, np.float32)}
    save_sharded(str(tmp_path), 1, tree)
    shard = tmp_path / "ckpt_1.shard-p0.npz"
    data = bytearray(shard.read_bytes())
    data[len(data) // 2] ^= 0xFF
    shard.write_bytes(bytes(data))
    monkeypatch.setattr(ckpt_lib, "discard_tag",
                        lambda *a, **k: None)  # deletion silently fails
    with pytest.raises(ValueError, match="could not be removed"):
        restore_sharded(str(tmp_path),
                        {"w": np.zeros((4, 4), np.float32)})


def test_legacy_directory_without_commits_still_restores(tmp_path):
    """Directories written before the commit protocol (no manifest on
    ANY tag) keep the legacy newest-tag behavior — old checkpoints
    stay loadable."""
    tree = {"w": np.full((2, 2), 5.0, np.float32)}
    save_sharded(str(tmp_path), 3, tree)
    os.remove(str(tmp_path / "ckpt_3.commit.json"))
    from analytics_zoo_tpu.train.checkpoint import latest_tag
    assert latest_tag(str(tmp_path)) == "3"
    out = restore_sharded(str(tmp_path),
                          {"w": np.zeros((2, 2), np.float32)})
    np.testing.assert_array_equal(out["w"], tree["w"])


def test_restore_same_shape_stack_keeps_construction_order(tmp_path):
    """A stack of SAME-shape auto-numbered layers (the transformer-block
    case) must restore in construction order even when (a) the saved
    names straddle a digit boundary (lexicographic flatten lists
    dense_10 before dense_9) and (b) the two builds' auto-number ranges
    OVERLAP (saved dense_10 and template dense_10 are different
    layers)."""
    from analytics_zoo_tpu.train.checkpoint import (restore_checkpoint,
                                                    save_checkpoint)
    a = np.full((4, 4), 1.0, np.float32)
    b = np.full((4, 4), 2.0, np.float32)
    c = np.full((4, 4), 3.0, np.float32)
    saved = {"params": {"dense_9": {"W": a}, "dense_10": {"W": b},
                        "dense_11": {"W": c}}}
    # overlapping range: template's FIRST layer is named dense_10
    template = {"params": {"dense_10": {"W": np.zeros((4, 4),
                                                      np.float32)},
                           "dense_11": {"W": np.zeros((4, 4),
                                                      np.float32)},
                           "dense_12": {"W": np.zeros((4, 4),
                                                      np.float32)}}}
    save_checkpoint(str(tmp_path), 1, saved)
    out = restore_checkpoint(str(tmp_path), template, 1)
    np.testing.assert_array_equal(out["params"]["dense_10"]["W"], a)
    np.testing.assert_array_equal(out["params"]["dense_11"]["W"], b)
    np.testing.assert_array_equal(out["params"]["dense_12"]["W"], c)
