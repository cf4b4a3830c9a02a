"""Sharded training at full speed: the declarative train-state layout.

Pins the tentpole's behavior end to end on host devices:

* fsdp loss trajectory is BITWISE equal to the replicated run (same
  mesh, same batch sharding — only the param/opt-state layout changes);
  params track within float tolerance (GSPMD re-associates the gradient
  reduction: reduce-scatter vs all-reduce, ~1 ulp/step);
* the fsdp+tp column-split leg agrees to a few f32 ulp (loss AND
  params; separately compiled programs, so not promised bitwise);
* gradient accumulation (lax.scan inside the ONE compiled step)
  reproduces the unaccumulated trajectory within documented f32
  tolerance and attributes its host-side split to the ``grad_accum``
  profiler phase;
* bf16 mixed precision keeps f32 master weights and f32 moments;
* ``ZOO_TRAIN_STRATEGY`` / ``ZOO_TRAIN_ACCUM`` / ``ZOO_TRAIN_DTYPE``
  resolve through the env contract, constructor args winning;
* optimizer state is sharded WITH its params (ZeRO-style): per-device
  moment bytes shrink by the fsdp factor;
* a sharded checkpoint saved on one mesh shape restores onto a
  DIFFERENT mesh shape bit-identically, takes that mesh's layout, and
  a round-trip back resumes the interrupted fit to bit-identical final
  state (params AND optimizer moments).
"""

import numpy as np
import optax
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.data.dataset import Dataset
from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.pipeline.api.keras import Sequential, objectives
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.train import triggers
from analytics_zoo_tpu.train.trainer import Trainer


def _mesh(axes):
    """A sub-mesh over the first N of the forced host devices, so the
    2-way and 4-way legs coexist inside the 8-device test process."""
    import math
    n = math.prod(axes.values())
    return mesh_lib.create_mesh(axes, devices=jax.devices()[:n])


def _dataset(rows=64, dim=8, classes=4, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, dim)).astype(np.float32)
    y = rng.integers(0, classes, rows).astype(np.int32)
    return Dataset.from_ndarray(x, y)


def _trainer(mesh, width=4096, dim=8, classes=4, **kw):
    """A model whose first kernel (dim x width) crosses the fsdp
    min-size threshold so the strategy actually shards something."""
    m = Sequential()
    # explicit names: auto-numbered layers flatten in LEXICOGRAPHIC
    # order, so two builds' leaf orders diverge across a digit boundary
    # (dense_10 sorts before dense_9) and zip() would pair wrong leaves
    m.add(Dense(width, activation="relu", input_shape=(dim,),
                name="hid"))
    m.add(Dense(classes, name="out"))
    kw.setdefault("optimizer", optax.adam(1e-3))
    opt = kw.pop("optimizer")
    return Trainer(m.to_graph(),
                   objectives.get("sparse_categorical_crossentropy"),
                   opt, mesh=mesh, seed=0, **kw)


def _param_leaves(trainer):
    return jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]


# ----------------------------------------------------------- bitwise


def test_fsdp_losses_track_replicated():
    """Same mesh, same data sharding; only the param/opt layout differs.
    fsdp row-shards a kernel's contraction dim, so GSPMD re-associates
    reductions (partial sums + psum) at the ulp level even in the
    forward pass — the trajectory is pinned to tight float tolerance,
    not bitwise (the gather-only tp leg below holds to a few ulp)."""
    mesh = _mesh({"data": 1, "fsdp": 2})
    ds = _dataset()
    rep = _trainer(mesh, strategy="replicate")
    h_rep = rep.fit(ds, batch_size=32,
                    end_trigger=triggers.MaxIteration(4))
    t_fsdp = _trainer(mesh, strategy="fsdp")
    h_fsdp = t_fsdp.fit(ds, batch_size=32,
                        end_trigger=triggers.MaxIteration(4))
    np.testing.assert_allclose(h_rep["loss"], h_fsdp["loss"], rtol=1e-5)
    # params re-associate the grad reduction: tolerance, documented
    specs = [l.sharding.spec for _, l in _param_leaves(t_fsdp)]
    assert any(s != P() for s in specs)  # fsdp actually sharded
    for (pa, la), (pb, lb) in zip(_param_leaves(rep),
                                  _param_leaves(t_fsdp)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-6, rtol=0, err_msg=str(pa))


# two differently-compiled CPU programs (replicated vs tensor-split)
# may round a reduction differently: a few f32 ulp, stated, not bitwise
_ULP = float(np.finfo(np.float32).eps)


def test_fsdp_tp_column_split_fully_bitwise():
    """Tensor-split Dense kernels change only the layout, never the
    per-element math (no cross-batch reduction is re-associated): loss
    AND params agree with the replicated run to a few f32 ulp (the two
    are separately compiled programs, so XLA may order a reduction
    differently — bit-equality is only promised for one executable)."""
    mesh = _mesh({"data": 1, "fsdp": 1, "tensor": 2})
    ds = _dataset()
    rep = _trainer(mesh, strategy="replicate")
    h_rep = rep.fit(ds, batch_size=32,
                    end_trigger=triggers.MaxIteration(4))
    tp = _trainer(mesh, strategy="fsdp_tp", tp_rules={r"W$": 1})
    h_tp = tp.fit(ds, batch_size=32,
                  end_trigger=triggers.MaxIteration(4))
    np.testing.assert_allclose(h_rep["loss"], h_tp["loss"],
                               rtol=4 * _ULP, atol=0)
    specs = [l.sharding.spec for _, l in _param_leaves(tp)]
    assert P(None, "tensor") in specs
    for (pa, la), (pb, lb) in zip(_param_leaves(rep),
                                  _param_leaves(tp)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=4 * _ULP, atol=4 * _ULP,
                                   err_msg=str(pa))


# ------------------------------------------------------ accumulation


def test_grad_accum_matches_unaccumulated_trajectory():
    mesh = _mesh({"data": 2})
    ds = _dataset(rows=64, dim=16)
    t1 = _trainer(mesh, width=64, dim=16, accum_steps=1)
    h1 = t1.fit(ds, batch_size=32, end_trigger=triggers.MaxIteration(4))
    t2 = _trainer(mesh, width=64, dim=16, accum_steps=2)
    h2 = t2.fit(ds, batch_size=32, end_trigger=triggers.MaxIteration(4))
    # mean-of-means == full-batch mean up to f32 re-association
    np.testing.assert_allclose(h1["loss"], h2["loss"], rtol=1e-5)
    for (pa, la), (_, lb) in zip(_param_leaves(t1), _param_leaves(t2)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=1e-6, err_msg=str(pa))


def test_grad_accum_requires_divisible_batch():
    mesh = _mesh({"data": 2})
    t = _trainer(mesh, width=64, dim=16, accum_steps=3)
    with pytest.raises(ValueError, match="accum"):
        t.fit(_dataset(rows=64, dim=16), batch_size=32,
              end_trigger=triggers.MaxIteration(1))


def test_grad_accum_phase_attributed_in_profiler():
    mesh = _mesh({"data": 2})
    t = _trainer(mesh, width=64, dim=16, accum_steps=2)
    prof = t.enable_step_profiler()
    t.fit(_dataset(rows=64, dim=16), batch_size=32,
          end_trigger=triggers.MaxIteration(2))
    snap = prof.snapshot()
    assert snap["steps"] == 2
    assert "grad_accum" in snap["phases"]
    assert all("grad_accum_ms" in e for e in prof.timeline())


def test_sharded_fit_compiles_its_step_once():
    """Two epochs under fsdp with accumulation: exactly one XLA compile
    lands in the profiled steps.  The sharded layout never re-traces or
    reshards a step (the second epoch runs the first one's executable,
    and the state the step returns goes back in as it is)."""
    mesh = _mesh({"data": 1, "fsdp": 2})
    t = _trainer(mesh, strategy="fsdp", accum_steps=2)
    prof = t.enable_step_profiler()
    t.fit(_dataset(), batch_size=32, end_trigger=triggers.MaxEpoch(2))
    assert prof.steps == 4
    assert prof.compiles == 1


# -------------------------------------------------------------- bf16


def test_bf16_keeps_f32_master_weights_and_moments():
    mesh = _mesh({"data": 2})
    ds = _dataset(rows=64, dim=16)
    f32 = _trainer(mesh, width=64, dim=16)
    h32 = f32.fit(ds, batch_size=32,
                  end_trigger=triggers.MaxIteration(4))
    bf = _trainer(mesh, width=64, dim=16, compute_dtype=jnp.bfloat16)
    h16 = bf.fit(ds, batch_size=32,
                 end_trigger=triggers.MaxIteration(4))
    for _, leaf in _param_leaves(bf):
        assert leaf.dtype == jnp.float32  # master weights
    moments = [l for l in jax.tree_util.tree_leaves(bf.state.opt_state)
               if hasattr(l, "dtype") and np.ndim(l) > 0]
    assert moments and all(l.dtype == jnp.float32 for l in moments)
    # bf16 compute tracks the f32 trajectory loosely but finitely
    assert np.all(np.isfinite(h16["loss"]))
    np.testing.assert_allclose(h32["loss"], h16["loss"], atol=0.05,
                               rtol=0.05)


# --------------------------------------------------------- env knobs


def test_env_contract_resolves_training_knobs(monkeypatch):
    monkeypatch.setenv("ZOO_TRAIN_STRATEGY", "fsdp")
    monkeypatch.setenv("ZOO_TRAIN_ACCUM", "2")
    monkeypatch.setenv("ZOO_TRAIN_DTYPE", "bf16")
    mesh = _mesh({"data": 1, "fsdp": 2})
    t = _trainer(mesh, width=64, dim=16)
    assert t.strategy == "fsdp"
    assert t.accum_steps == 2
    assert t.compute_dtype == jnp.bfloat16
    # constructor args win over the environment
    t2 = _trainer(mesh, width=64, dim=16, strategy="replicate",
                  accum_steps=1, compute_dtype=jnp.float32)
    assert t2.strategy == "replicate"
    assert t2.accum_steps == 1
    assert t2.compute_dtype == jnp.float32
    # unknown dtype name degrades to full precision, loudly not fatally
    monkeypatch.setenv("ZOO_TRAIN_DTYPE", "float128")
    t3 = _trainer(mesh, width=64, dim=16)
    assert t3.compute_dtype is None


# ------------------------------------------------- opt-state memory


def test_fsdp_shards_optimizer_moments():
    """ZeRO-style: the Adam moments of a sharded param live sharded —
    each device holds 1/fsdp of the moment bytes, not a full copy."""
    mesh = _mesh({"data": 1, "fsdp": 2})
    t = _trainer(mesh, strategy="fsdp")
    t.fit(_dataset(), batch_size=32, end_trigger=triggers.MaxIteration(1))
    sharded_moments = [
        l for l in jax.tree_util.tree_leaves(t.state.opt_state)
        if hasattr(l, "sharding") and np.ndim(l) >= 2
        and l.sharding.spec != P()]
    assert sharded_moments
    for leaf in sharded_moments:
        shard = leaf.addressable_shards[0].data
        assert shard.nbytes * 2 == np.asarray(leaf).nbytes


# --------------------------------------- cross-mesh checkpoint resume


def test_cross_mesh_checkpoint_resume_bit_identical(tmp_path):
    """The acceptance pin: save the sharded TrainState mid-fit on mesh
    Y = {fsdp:2}, restore onto mesh X = {fsdp:4} (leaves bit-identical,
    layout re-planned for X), save from X, restore back onto a fresh Y
    trainer and finish the fit — final params AND optimizer moments are
    BITWISE equal to the uninterrupted run."""
    mesh_y = _mesh({"data": 1, "fsdp": 2})
    mesh_x = _mesh({"data": 1, "fsdp": 4})
    ds = _dataset()

    t_full = _trainer(mesh_y, strategy="fsdp")
    t_full.fit(ds, batch_size=32, end_trigger=triggers.MaxIteration(4))

    # interrupted: 2 steps (one full epoch) on Y, then save
    t_a = _trainer(mesh_y, strategy="fsdp")
    t_a.fit(ds, batch_size=32, end_trigger=triggers.MaxIteration(2))
    t_a.save_weights(str(tmp_path / "y"), tag="mid")

    # restore onto X: values bitwise, layout follows X's 4-way plan
    t_x = _trainer(mesh_x, strategy="fsdp")
    t_x.load_weights(str(tmp_path / "y"), tag="mid")
    assert t_x.state.step == 2 and t_x.state.epoch == 1
    a_leaves = jax.tree_util.tree_leaves(t_a.state.as_tree())
    x_leaves = jax.tree_util.tree_leaves(t_x.state.as_tree())
    for la, lx in zip(a_leaves, x_leaves):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lx))
    four_way = [l for l in jax.tree_util.tree_leaves(t_x.state.params)
                if l.sharding.spec != P()]
    assert four_way
    for leaf in four_way:
        shard = leaf.addressable_shards[0].data
        assert shard.nbytes * 4 == np.asarray(leaf).nbytes

    # round-trip: save from X, restore onto a FRESH Y trainer, resume
    t_x.save_weights(str(tmp_path / "x"), tag="mid2")
    t_b = _trainer(mesh_y, strategy="fsdp")
    t_b.load_weights(str(tmp_path / "x"), tag="mid2")
    t_b.fit(ds, batch_size=32, end_trigger=triggers.MaxIteration(4))
    assert t_b.state.step == 4

    for lf, lb in zip(jax.tree_util.tree_leaves(t_full.state.as_tree()),
                      jax.tree_util.tree_leaves(t_b.state.as_tree())):
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lb))
