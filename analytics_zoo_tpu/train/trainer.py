"""Trainer: the compiled-SPMD training engine.

This single component replaces the reference's BigDL Optimizer /
DistriOptimizer machinery — the per-iteration "2 Spark jobs" ("model
forward-backward" then "parameter synchronization" via shuffle+broadcast
AllReduce, reference: docs/docs/wp-bigdl.md:113-160, driven from
Topology.scala:281, NNEstimator.scala:399, net.py:398-424).  Under jit the
whole iteration is ONE XLA computation: grad → (XLA-inserted psum over ICI
when the batch axis is sharded) → optax update, with the optimizer step
sharded alongside the params.

Semantics preserved from the reference:
* incremental fit — successive ``fit`` calls continue epoch counting
  (Topology.scala:284-297 reflective epoch bookkeeping);
* Trigger-driven validation / checkpoint / termination;
* TrainSummary scalars Loss / LearningRate / Throughput;
* gradient clipping composed into the optimizer chain.
"""

from __future__ import annotations

import itertools
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import optax

from .. import envcontract
from ..common.utils import pad_leading
from ..data.dataset import (Dataset, check_batch_divisibility,
                            prefetch_iterator, shard_batch)
from ..observability import flightrec
from ..observability import profile as profile_lib
from ..observability import trace as trace_lib
from ..parallel import distributed as dist_lib
from ..parallel import mesh as mesh_lib
from ..parallel import sharding as sharding_lib
from . import faults
from . import metrics as train_metrics
from . import stepprof
from . import triggers as trigger_lib
from .checkpoint import async_save_sharded, save_sharded
from .checkpoint import wait_pending as checkpoint_lib_wait_pending
from .summary import TrainSummary, ValidationSummary


# zero-pad the trailing partial batch of evaluate/predict to keep one
# compiled shape (shared helper: common/utils.py)
_pad_tail = pad_leading


class TrainState:
    """Mutable host-side holder of the on-device training pytrees."""

    def __init__(self, params, model_state, opt_state, step=0, epoch=0,
                 rng=None):
        self.params = params
        self.model_state = model_state
        self.opt_state = opt_state
        self.step = step
        self.epoch = epoch
        self.rng = rng

    def as_tree(self):
        return {"params": self.params, "model_state": self.model_state,
                "opt_state": self.opt_state}

    def load_tree(self, tree):
        self.params = tree["params"]
        self.model_state = tree["model_state"]
        self.opt_state = tree["opt_state"]


def _collect_aux(state) -> Any:
    """Differentiable auxiliary penalties that layers surface in their
    state under the reserved key ``aux_loss`` (SwitchMoE router
    balancing, W_regularizer penalties — already scaled by the layer).
    Training sums them into the loss INSIDE the grad closure so the
    penalty actually reaches the parameters; evaluate includes them so
    train and validation losses stay comparable (Keras semantics).
    Traverses RECURSIVELY: nested models (a Sequential added into
    another Sequential) nest their state one level per container."""
    total = 0.0
    if isinstance(state, dict):
        for key, sub in state.items():
            if key == "aux_loss":
                total = total + sub
            else:
                total = total + _collect_aux(sub)
    return total


def build_train_step(model, loss_fn, optimizer, compute_dtype=None,
                     jit: bool = True, donate: bool = True,
                     accum_steps: int = 1, in_shardings=None,
                     out_shardings=None):
    """THE training iteration: grad → (XLA-inserted psum when the batch is
    sharded) → optax update, with optional bf16 mixed precision (bf16
    compute/activations, f32 master weights; grads return f32 through the
    cast's transpose so the optax update — moments included — runs in
    f32) and optional gradient accumulation.  Single source of truth —
    the Trainer, the driver dry run and tests/test_tpu_compile.py all
    lower this same function.

    ``accum_steps > 1``: ``x``/``y`` carry a LEADING microbatch axis
    ``(accum, micro, ...)`` and the step runs a ``lax.scan`` over it
    inside the ONE compiled program — gradients are accumulated in the
    master dtype and averaged (mean-of-means equals the full-batch mean
    for equal microbatches), the loss is the mean of microbatch losses,
    and microbatch ``i`` draws ``fold_in(rng, i)`` so the per-step
    ``fold_in(rng, step)`` determinism contract extends one level down.
    ``accum_steps == 1`` is byte-for-byte the historical single-shot
    step (no scan, rng consumed unsplit) so existing bit-exactness pins
    keep holding.

    ``in_shardings`` / ``out_shardings`` are forwarded to ``jax.jit`` —
    the sharded train-state layout (params + ZeRO optimizer state +
    batch) compiles in one pass with the whole state donated; ``None``
    entries let jax infer from the arguments (the replicated-batch
    fallback path stays compilable).

    Signature of the returned step:
        (params, model_state, opt_state, rng, x, y)
            -> (params, model_state, opt_state, loss)
    """
    cast = compute_dtype
    collect_aux = _collect_aux
    accum = max(int(accum_steps), 1)

    def compute_loss(p, mstate, step_rng, x, y):
        xin, p_in = x, p
        if cast is not None:
            castf = lambda a: (a.astype(cast) if jnp.issubdtype(
                a.dtype, jnp.floating) else a)
            xin = jax.tree_util.tree_map(castf, xin)
            p_in = jax.tree_util.tree_map(castf, p_in)
        y_pred, new_state = model.apply(
            p_in, mstate, xin, training=True, rng=step_rng)
        with jax.named_scope(profile_lib.SCOPE_LOSS):
            per_sample = loss_fn(y, y_pred.astype(jnp.float32)
                                 if cast is not None else y_pred)
            loss = jnp.mean(per_sample) + collect_aux(new_state)
        return loss, new_state

    def train_step(params, model_state, opt_state, rng, x, y):
        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)
        if accum == 1:
            (loss, new_state), grads = grad_fn(params, model_state, rng,
                                               x, y)
        else:
            def micro_step(carry, inp):
                g_acc, loss_acc, mstate = carry
                i, xi, yi = inp
                (mloss, mstate), g = grad_fn(
                    params, mstate, jax.random.fold_in(rng, i), xi, yi)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, loss_acc + mloss, mstate), None

            # accumulate in the MASTER dtype (grads already left the
            # bf16 region through the cast's transpose)
            with jax.named_scope(profile_lib.SCOPE_GRAD_ACCUM):
                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (g_sum, loss_sum, new_state), _ = jax.lax.scan(
                    micro_step,
                    (zeros, jnp.zeros((), jnp.float32), model_state),
                    (jnp.arange(accum), x, y))
                inv = 1.0 / accum
                grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
                loss = loss_sum * inv
        with jax.named_scope(profile_lib.SCOPE_OPTIMIZER_UPDATE):
            updates, new_opt_state = optimizer.update(grads, opt_state,
                                                      params)
            new_params = optax.apply_updates(params, updates)
        return new_params, new_state, new_opt_state, loss

    if not jit:
        return train_step
    kwargs = {}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    return jax.jit(
        profile_lib.named(profile_lib.PROGRAM_TRAIN_STEP, train_step),
        donate_argnums=(0, 1, 2) if donate else (), **kwargs)


#: env-contract knobs (declared in envcontract.VARS): deployment-wide
#: defaults for the sharding strategy / accumulation factor / compute
#: dtype — explicit constructor arguments always win
ENV_STRATEGY = "ZOO_TRAIN_STRATEGY"
ENV_ACCUM = "ZOO_TRAIN_ACCUM"
ENV_DTYPE = "ZOO_TRAIN_DTYPE"


def _dtype_from_env():
    """Resolve ``ZOO_TRAIN_DTYPE`` into a compute dtype (None = full
    f32).  An operator typo degrades to full precision with a warning —
    the env contract's "never crash a worker at import" rule."""
    name = (envcontract.env_str(ENV_DTYPE) or "").strip().lower()
    if not name:
        return None
    if name in ("bf16", "bfloat16"):
        return jnp.bfloat16
    if name in ("f16", "fp16", "float16"):
        return jnp.float16
    if name not in ("f32", "fp32", "float32"):
        from ..observability.log import get_logger
        get_logger("analytics_zoo_tpu.train").warning(
            "unknown ZOO_TRAIN_DTYPE — training in full f32", value=name)
    return None


class Trainer:
    def __init__(self, model, loss_fn: Callable, optimizer,
                 metrics: Sequence = (), mesh=None,
                 strategy: Optional[str] = None, seed: int = 0,
                 compute_dtype=None, accum_steps: Optional[int] = None,
                 tp_rules: Optional[Dict[str, int]] = None):
        """``model`` is any Layer (usually a GraphModule); ``loss_fn`` maps
        (y_true, y_pred) -> per-sample loss; ``optimizer`` is an optax
        transformation.

        ``strategy`` names the parameter/optimizer sharding plan
        (``parallel/sharding.py`` rule tables: replicate | fsdp | tp |
        fsdp_tp); ``tp_rules`` maps param-path regexes to the axis index
        sharded over ``tensor``.  ``accum_steps`` > 1 splits every global
        batch into that many microbatches scanned inside the one
        compiled step.  ``compute_dtype=jnp.bfloat16`` enables mixed
        precision (bf16 compute, f32 master weights + moments).  Each of
        strategy / accum_steps / compute_dtype falls back to its env
        knob (ZOO_TRAIN_STRATEGY / ZOO_TRAIN_ACCUM / ZOO_TRAIN_DTYPE)
        when not given."""
        self.model = model
        self.loss_fn = loss_fn
        # the optimizer actually stepped is the base masked by the
        # model's layer.trainable flags (freeze/unfreeze support)
        self._base_optimizer = optimizer
        self.optimizer = self._mask_from_flags(optimizer)
        self.metrics = list(metrics)
        self.mesh = mesh or mesh_lib.get_default_mesh()
        self.strategy = strategy or envcontract.env_str(
            ENV_STRATEGY, "replicate")
        self.tp_rules = dict(tp_rules) if tp_rules else None
        self.accum_steps = max(int(accum_steps) if accum_steps is not None
                               else envcontract.env_int(ENV_ACCUM, 1), 1)
        self.seed = seed
        self.compute_dtype = (compute_dtype if compute_dtype is not None
                              else _dtype_from_env())
        self.state: Optional[TrainState] = None
        self.train_summary: Optional[TrainSummary] = None
        self.val_summary: Optional[ValidationSummary] = None
        self._train_step = None
        self._eval_step = None
        self._eval_step_overrides: Dict[str, Any] = {}
        self._predict_step = None
        self._param_shardings = None
        self._opt_shardings = None
        self._batch_sharding = mesh_lib.data_sharding(self.mesh)
        # microbatched layout (accum, micro, ...): the data axes move to
        # dim 1, the scanned accumulation axis stays unsharded
        self._microbatch_sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(
                None, *self._batch_sharding.spec))
        self._repl_sharding = mesh_lib.replicated(self.mesh)

    # ---- freeze support --------------------------------------------
    def _frozen_names(self) -> set:
        return {l.name for l in getattr(self.model, "layers", [])
                if not getattr(l, "trainable", True)}

    def _mask_from_flags(self, base):
        """Wrap ``base`` so layers with ``trainable=False`` receive
        EXACTLY zero updates, with a state structure that is INVARIANT
        under freeze/unfreeze: ``base``'s statistics always cover the
        full parameter tree, and the frozen set lives only in the update
        closure.  Toggling flags therefore never re-initializes
        optimizer state — still-training layers keep their momentum /
        Adam moments exactly, matching the reference's freeze
        (scaleW/scaleB=0, which never touches OptimMethod state;
        NetUtils.scala:216-277).

        Both the gradients entering and the updates leaving ``base`` are
        zeroed for frozen layers: zeroing the gradients keeps frozen
        layers' moments from absorbing gradient signal while frozen
        (they decay toward zero, equivalent to a fresh start on
        unfreeze); zeroing the updates guarantees exactly-zero movement
        even under stateful optimizers whose update is nonzero at zero
        gradient (momentum, Adam bias correction)."""
        frozen = frozenset(self._frozen_names())

        def _zero_frozen(tree):
            if not frozen:
                return tree
            return {k: (jax.tree_util.tree_map(jnp.zeros_like, sub)
                        if k in frozen else sub)
                    for k, sub in tree.items()}

        def update(grads, state, params=None):
            updates, new_state = base.update(_zero_frozen(grads), state,
                                             params)
            return _zero_frozen(updates), new_state

        from ..pipeline.api.keras.optimizers import ZooOptimizer
        return ZooOptimizer(base.init, update,
                            lr_fn=getattr(base, "lr_fn", None))

    def invalidate_compiled(self):
        """Drop the compiled step functions (they re-trace lazily) —
        TrainState (weights, optimizer state, epoch/step counters)
        survives."""
        self._train_step = None
        self._eval_step = None
        self._eval_step_overrides = {}
        self._predict_step = None

    def refresh_optimizer(self):
        """Re-derive the optimizer mask from the model's current
        trainable flags.  Optimizer STATISTICS are untouched — the mask
        wrapper's state structure is invariant under freeze/unfreeze
        (``_mask_from_flags``), so still-training layers keep their
        moments bit-for-bit and freshly-frozen weights cannot move on
        stale momentum (their updates are hard-zeroed)."""
        self.optimizer = self._mask_from_flags(self._base_optimizer)
        self.invalidate_compiled()

    # ------------------------------------------------------------------
    def ensure_initialized(self):
        if self.state is not None:
            return
        rng = jax.random.PRNGKey(self.seed)
        init_rng, loop_rng = jax.random.split(rng)
        params, model_state = self.model.init(
            init_rng, getattr(self.model, "batch_input_shape", None))
        # place according to strategy; XLA keeps them there across steps.
        # The optimizer state is initialized from the PLACED params so its
        # moment buffers inherit the same shardings (fsdp shards optimizer
        # state alongside params, ZeRO-style) — init-before-placement
        # would pin momentum to one device and conflict after a restore.
        self._param_shardings = sharding_lib.shard_params(
            params, self.mesh, self.strategy, tp_rules=self.tp_rules)
        params = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(p, s), params, self._param_shardings)
        model_state = jax.device_put(model_state, self._repl_sharding)
        self.state = TrainState(params, model_state,
                                self._init_opt_state(params),
                                rng=loop_rng)

    def _init_opt_state(self, params):
        """Optimizer state from the PLACED params (moments inherit their
        shardings), then committed whole to the layout the compiled step
        declares.  optax's housekeeping scalars (Adam's ``count``) are
        born uncommitted on the default device, and an uncommitted
        first-step argument makes the step compile a SECOND time when
        the committed output comes back in as step two's input."""
        opt_state = self.optimizer.init(params)
        return jax.device_put(
            opt_state, sharding_lib.opt_state_sharding_tree(
                opt_state, params, self._param_shardings, self.mesh))

    def adopt_weights(self, params, model_state=None):
        """Replace weights with an externally provided pytree, re-placed
        under this trainer's shardings — used when compile() supersedes an
        inference-only trainer so pre-loaded weights survive.

        Shardings come from ``jax.eval_shape`` (abstract init) so no
        throwaway random initialization is materialized.  Raises
        ValueError when the provided tree doesn't match the model's
        parameter structure/shapes (e.g. the architecture changed since
        the weights were produced)."""
        rng = jax.random.PRNGKey(self.seed)
        init_rng, loop_rng = jax.random.split(rng)
        abs_params, abs_state = jax.eval_shape(
            lambda r: self.model.init(
                r, getattr(self.model, "batch_input_shape", None)),
            init_rng)
        same_struct = (jax.tree_util.tree_structure(params)
                       == jax.tree_util.tree_structure(abs_params))
        if not same_struct or any(
                tuple(np.shape(p)) != tuple(a.shape)
                for p, a in zip(jax.tree_util.tree_leaves(params),
                                jax.tree_util.tree_leaves(abs_params))):
            raise ValueError(
                "adopted weights do not match the model's parameter "
                "structure (did the architecture change?)")
        self._param_shardings = sharding_lib.shard_params(
            abs_params, self.mesh, self.strategy, tp_rules=self.tp_rules)
        placed = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(p, s), params,
            self._param_shardings)
        if model_state is None:
            if jax.tree_util.tree_leaves(abs_state):
                # stateful model with no adopted state: materialize one
                _, model_state = self.model.init(
                    init_rng, getattr(self.model, "batch_input_shape",
                                      None))
            else:
                model_state = abs_state
        model_state = jax.device_put(model_state, self._repl_sharding)
        if self.state is None:
            self.state = TrainState(placed, model_state,
                                    self._init_opt_state(placed),
                                    rng=loop_rng)
        else:
            self.state.params = placed
            self.state.model_state = model_state
            self.state.opt_state = self._init_opt_state(placed)

    # ------------------------------------------------------------------
    def _mesh_scoped(self, fn):
        """Wrap a (possibly jitted) step so every call — including the
        trace-triggering first one — runs under this trainer's mesh as
        the ACTIVE mesh, letting mesh-aware layers (ring attention)
        discover the compile(mesh=...) mesh instead of only the
        process default."""
        def wrapped(*a, **k):
            with mesh_lib.active_mesh(self.mesh):
                return fn(*a, **k)
        return wrapped

    def _state_plan(self):
        """The declarative sharded train-state layout: explicit jit
        shardings over (params, model_state, opt_state, rng) — params per
        the strategy rule tables, optimizer state WITH its params
        (ZeRO-style, ``sharding.opt_state_sharding_tree``), model state
        and rng replicated.  Batch entries stay ``None`` (inferred from
        the placed arguments) so the replicated-batch fallback path keeps
        compiling.  Returns ``(in_shardings, out_shardings)`` for
        ``build_train_step``."""
        st = self.state
        self._opt_shardings = sharding_lib.opt_state_sharding_tree(
            st.opt_state, st.params, self._param_shardings, self.mesh)
        # model_state as a PREFIX (one sharding covers the whole
        # subtree): training-mode state may grow keys (aux_loss) the
        # init-time structure doesn't have
        in_sh = (self._param_shardings, self._repl_sharding,
                 self._opt_shardings, self._repl_sharding, None, None)
        out_sh = (self._param_shardings, self._repl_sharding,
                  self._opt_shardings, None)
        return in_sh, out_sh

    def _build_train_step(self):
        self.ensure_initialized()
        in_sh, out_sh = self._state_plan()
        return build_train_step(self.model, self.loss_fn, self.optimizer,
                                compute_dtype=self.compute_dtype,
                                accum_steps=self.accum_steps,
                                in_shardings=in_sh, out_shardings=out_sh)

    def lower_train_step(self, x, y):
        """AOT-lower THE step ``fit`` runs, for one host batch of the
        global batch size: ``.compile()`` the result to read
        ``as_text()`` / ``memory_analysis()`` of exactly that program.
        Inspection only — lowering needs shapes, so no state is donated
        or advanced, and ``fit`` keeps its own compiled step."""
        self.ensure_initialized()
        st = self.state
        bx, by = self._stage_batch(x, y)
        with mesh_lib.active_mesh(self.mesh):
            return self._build_train_step().lower(
                st.params, st.model_state, st.opt_state, st.rng, bx, by)

    def _build_eval_step(self, metrics: Optional[Sequence] = None):
        model = self.model
        metrics = self.metrics if metrics is None else list(metrics)
        loss_fn = self.loss_fn

        def eval_step(params, model_state, accs, loss_acc, x, y, mask):
            y_pred, eval_state = model.apply(params, model_state, x,
                                             training=False)
            new_accs = [m.update(a, y, y_pred, mask)
                        for m, a in zip(metrics, accs)]
            if loss_fn is not None:
                # include auxiliary penalties (regularizers / MoE aux)
                # per sample so the reported evaluate loss is comparable
                # with the training loss (Keras includes them too)
                from ..pipeline.api.keras.objectives import _batch_mean
                # sequence losses arrive per-position (batch, T, ...):
                # collapse to per-SAMPLE so masking stays (batch,)
                per_sample = _batch_mean(
                    loss_fn(y, y_pred) + _collect_aux(eval_state))
                w = mask.reshape(-1).astype(jnp.float32)
                # neutralize masked-out padding BEFORE weighting: padded
                # tail samples can legitimately be NaN (e.g. class_nll's
                # out-of-range guard on zero-padded labels rebased by
                # zero_based_label=False), and NaN * 0 is NaN
                per_sample = jnp.where(w > 0, per_sample, 0.0)
                loss_acc = {"sum": loss_acc["sum"]
                            + jnp.sum(per_sample * w),
                            "n": loss_acc["n"] + jnp.sum(w)}
            return new_accs, loss_acc

        return jax.jit(eval_step)

    def _build_predict_step(self):
        model = self.model

        def predict_step(params, model_state, x):
            y_pred, _ = model.apply(params, model_state, x, training=False)
            return y_pred

        # the batch buffer is freshly device_put per step by the prefetch
        # thread and never read after the step — donating it lets XLA
        # write activations into it instead of allocating.  CPU doesn't
        # implement input donation (it would warn per call), so gate it.
        donate = (2,) if jax.default_backend() in ("tpu", "gpu") else ()
        return jax.jit(predict_step, donate_argnums=donate)

    # ------------------------------------------------------------------
    _warned_replicated = False

    def _split_microbatches(self, x, y):
        """Host-side (accum, micro, ...) view of a batch — a zero-copy
        numpy reshape on the prefetch thread, attributed to the
        ``grad_accum`` profiler phase by the caller.  The scanned
        accumulation axis leads; the data axes shard dim 1."""
        accum = self.accum_steps

        def split(a):
            a = np.asarray(a)
            if a.shape[0] % accum:
                raise ValueError(
                    f"per-host batch ({a.shape[0]}) must divide "
                    f"accum_steps ({accum})")
            return a.reshape((accum, a.shape[0] // accum) + a.shape[1:])

        sx = (tuple(split(a) for a in x) if isinstance(x, (tuple, list))
              else split(x))
        if y is None:
            return sx, None
        sy = (tuple(split(a) for a in y) if isinstance(y, (tuple, list))
              else split(y))
        return sx, sy

    def _stage_batch(self, x, y):
        """Host batch -> placed step input: the microbatch split (under
        gradient accumulation) then the per-shard upload."""
        if self.accum_steps > 1:
            x, y = self._split_microbatches(x, y)
        return self._put_batch(x, y, microbatched=self.accum_steps > 1)

    def _put_batch(self, x, y, microbatched: bool = False):
        """Place a host-local batch onto the mesh, per-shard: the
        ``device_put``/``make_array_from_process_local_data`` under
        ``put_global`` transfers each device's slice independently (and
        asynchronously), so upload overlaps compute across the mesh.
        Multi-host: ``x``/``y`` are this host's shard of the global batch
        and every process's shards are assembled into one global array
        (per-host feeding, reference net.py:458-468).  ``microbatched``
        batches arrive pre-split as (accum, micro, ...) — the data axes
        shard dim 1 and cross-process assembly concatenates there."""
        first = x[0] if isinstance(x, (tuple, list)) else x
        batch_dim = 1 if microbatched else 0
        dp = mesh_lib.dp_size(self.mesh)
        nproc = dist_lib.process_count()
        global_rows = np.shape(first)[batch_dim] * nproc
        divisible = global_rows % max(dp, 1) == 0
        if not divisible and nproc > 1:
            raise ValueError(
                f"global batch ({global_rows}) must divide the data-"
                f"parallel degree ({dp}) in multi-host execution")
        if not divisible and not Trainer._warned_replicated:
            # correct but every device redundantly computes the full batch
            Trainer._warned_replicated = True
            from ..observability.log import get_logger
            get_logger("analytics_zoo_tpu.train").warning(
                "batch does not divide the data-parallel degree — "
                "falling back to replicated compute (every device runs "
                "the full batch). Pad the batch for full speed.",
                batch=np.shape(first)[batch_dim], data_parallel=dp)
        if divisible:
            sharding = (self._microbatch_sharding if microbatched
                        else self._batch_sharding)
        else:
            sharding = self._repl_sharding
        put = lambda a: dist_lib.put_global(a, sharding,
                                            batch_sharded=divisible,
                                            batch_dim=batch_dim)
        xs = (tuple(put(a) for a in x) if isinstance(x, (tuple, list))
              else put(x))
        if y is None:
            return xs, None
        ys = (tuple(put(a) for a in y) if isinstance(y, (tuple, list))
              else put(y))
        return xs, ys

    def set_tensorboard(self, log_dir: str, app_name: str,
                        profile: bool = False, profile_steps: int = 10):
        """Parity: KerasNet.setTensorBoard (Topology.scala:157-175).

        ``profile=True`` additionally captures ONE ``jax.profiler`` trace
        per fit (the first ``profile_steps`` steps) under
        ``<log_dir>/<app_name>/plugins/profile`` so TensorBoard shows the
        step timeline alongside the scalars — the reference's ``timing()``
        wall-clock wrappers, upgraded to a real device trace
        (InferenceSupportive.scala:37-44; SURVEY §5)."""
        self.train_summary = TrainSummary(log_dir, app_name)
        self.val_summary = ValidationSummary(log_dir, app_name)
        self._profile_dir = (os.path.join(log_dir, app_name)
                             if profile else None)
        self._profile_steps = int(profile_steps)

    _profile_dir: Optional[str] = None
    _profile_steps: int = 10

    def set_checkpoint(self, path: str, over_write: bool = True,
                       trigger=None):
        """Parity: KerasNet.setCheckpoint (Topology.scala:184-194)."""
        self._ckpt_path = path
        self._ckpt_overwrite = over_write
        self._ckpt_trigger = trigger or trigger_lib.EveryEpoch()

    _ckpt_path: Optional[str] = None
    _ckpt_trigger = None
    _auto_resumed = False
    _resume_epoch_step = 0
    _step_profiler: "Optional[stepprof.StepProfiler]" = None

    def enable_step_profiler(self, timeline_path: Optional[str] = None
                             ) -> "stepprof.StepProfiler":
        """Turn on the per-step phase profiler (data_wait -> h2d ->
        step_dispatch -> ckpt_save; train/stepprof.py) for subsequent
        ``fit`` calls.  ``timeline_path`` additionally publishes the
        bounded per-step timeline as JSONL at fit end.  Also reachable
        without code changes via ``ZOO_STEP_PROFILE=1`` /
        ``ZOO_STEP_TIMELINE=<path>``."""
        self._step_profiler = stepprof.StepProfiler(
            timeline_path=timeline_path)
        return self._step_profiler

    def _maybe_auto_resume(self):
        """Supervised-restart contract: under ``ZOO_RESUME`` (set by the
        launcher on every pod relaunch) a checkpointing fit restores the
        newest COMPLETE snapshot before training.  No complete snapshot
        → clean cold start (coarse-grained recovery may cost lost steps,
        never a torn restore)."""
        if (self._ckpt_path is None or not faults.resume_requested()
                or self._auto_resumed
                or self.state.step or self.state.epoch):
            return
        self._auto_resumed = True
        from ..observability.log import get_logger
        slog = get_logger("analytics_zoo_tpu.train")
        try:
            self.load_weights(self._ckpt_path)
        except FileNotFoundError:
            train_metrics.record_ckpt_restore("cold_start")
            slog.warning(
                "ZOO_RESUME set but no complete checkpoint found — "
                "cold start", path=self._ckpt_path)
            return
        except Exception as e:
            # a torn/unreadable checkpoint (e.g. a crash during the
            # FIRST save, before any commit existed, leaves a legacy-
            # looking directory) must never be worse than a cold start
            # under the supervisor contract — a raise here would
            # crash-loop every resumed incarnation.  The explicit
            # load_weights path still fails loudly.
            train_metrics.record_ckpt_restore("cold_start")
            slog.error(
                "ZOO_RESUME restore failed — cold start",
                path=self._ckpt_path,
                error=f"{type(e).__name__}: {e}")
            return
        slog.info("resumed from checkpoint", path=self._ckpt_path,
                  epoch=self.state.epoch, step=self.state.step,
                  epoch_step=self._resume_epoch_step)

    # ------------------------------------------------------------------
    def fit(self, dataset: Dataset, batch_size: int, end_trigger=None,
            validation_data: Optional[Dataset] = None,
            validation_trigger=None, validation_batch_size: int = None,
            shuffle: bool = True, verbose: bool = False) -> Dict[str, List]:
        """Run the optimization loop until ``end_trigger`` fires.

        Returns a history dict of per-iteration losses and validation
        results.  Successive calls continue from the current epoch
        (incremental-fit parity).

        ``batch_size`` is the GLOBAL batch.  In multi-host execution each
        process feeds ``batch_size // process_count`` rows of its local
        dataset shard per step (per-host feeding, reference
        net.py:458-468); single-process it is the whole batch."""
        self.ensure_initialized()
        faults.refresh()  # supervisor env contract (heartbeat/faults)
        faults.heartbeat()
        # cross-process observability: the flight recorder (black box
        # the supervisor harvests on abnormal exit) and the step
        # profiler both arm from the env contract; each costs one None
        # check per step when absent
        recorder = flightrec.install_from_env()
        prof = self._step_profiler
        if prof is None:
            prof = self._step_profiler = stepprof.from_env()
        if recorder is not None:
            # add_collector dedups by function identity, so wiring on
            # every fit is free AND survives a recorder being replaced
            # (shutdown + re-configure) between fits
            recorder.add_collector(train_metrics.train_families)
            if prof is not None:
                recorder.add_collector(prof.families)
        self._maybe_auto_resume()
        # mid-epoch resume (iteration-trigger checkpoints): skip the
        # batches the restored position already consumed so the replayed
        # step sequence matches the uninterrupted run deterministically
        resume_skip = int(self._resume_epoch_step or 0)
        self._resume_epoch_step = 0
        if self._train_step is None:
            self._train_step = self._mesh_scoped(
                self._build_train_step())
        check_batch_divisibility(batch_size, mesh_lib.dp_size(self.mesh),
                                 dist_lib.process_count())
        per_host_bs = batch_size // dist_lib.process_count()
        if per_host_bs % self.accum_steps:
            raise ValueError(
                f"per-host batch ({per_host_bs}) must divide "
                f"accum_steps ({self.accum_steps}) — every microbatch "
                "keeps one compiled shape")
        end_trigger = end_trigger or trigger_lib.MaxEpoch(
            self.state.epoch + 1)
        validation_trigger = validation_trigger or trigger_lib.EveryEpoch()
        history: Dict[str, List] = {"loss": [], "val": []}
        st = self.state

        lr_fn = getattr(self.optimizer, "lr_fn", None)
        stop = False
        # one profiler trace per fit (default off): first N steps
        profiling = False
        profile_end_step = None
        if self._profile_dir is not None:
            # a trace that was asked for and cannot start is an error,
            # not a quieter run
            jax.profiler.start_trace(self._profile_dir)
            profiling = True
            profile_end_step = st.step + self._profile_steps

        def _stop_profile():
            nonlocal profiling
            if profiling:
                profiling = False
                jax.profiler.stop_trace()

        try:
            while True:
                record = {"epoch": st.epoch, "iteration": st.step}
                if stop or end_trigger(record):
                    break
                epoch_start, epoch_samples = time.time(), 0
                # per-epoch device-side loss buffer: NO per-step host sync —
                # losses stay on device and are fetched in one bulk transfer at
                # the epoch boundary (the round-1 `float(loss)` per step
                # destroyed async dispatch).  Loss-dependent triggers (MinLoss)
                # still work: the record carries the device scalar and only
                # such a trigger pays the sync.
                epoch_losses = []
                epoch_start_step = st.step - resume_skip
                batch_it = dataset.batches(per_host_bs, shuffle=shuffle,
                                           seed=self.seed, epoch=st.epoch)
                if resume_skip:
                    # the epoch's batch order is deterministic in
                    # (seed, epoch); dropping the first k batches is the
                    # data-pipeline fast-forward to the restored step
                    batch_it = itertools.islice(batch_it, resume_skip,
                                                None)
                    resume_skip = 0
                accum = self.accum_steps
                if prof is None:
                    put_fn = lambda b: self._stage_batch(*b)
                else:
                    def put_fn(b):
                        # grad_accum (host microbatch split) and h2d
                        # measured ON the prefetch thread, shipped with
                        # the batch so the consuming step's span can
                        # attribute them
                        accum_s = 0.0
                        if accum > 1:
                            t0 = time.perf_counter()
                            b = self._split_microbatches(*b)
                            accum_s = time.perf_counter() - t0
                        t0 = time.perf_counter()
                        out = self._put_batch(*b, microbatched=accum > 1)
                        return out, time.perf_counter() - t0, accum_s
                dev_it = prefetch_iterator(batch_it, put_fn)
                step_it = (dev_it if prof is None
                           else prof.timed_iter(dev_it))
                while True:
                    # host spans on the profiler's clock (inert without
                    # a profiler session): the step from asking for its
                    # batch to its dispatch returning, and inside it the
                    # wait, the enqueue and the checkpoint.  An epoch's
                    # last span holds only the wait that found the
                    # source exhausted.
                    with profile_lib.annotate("train/step",
                                              step_num=st.step + 1):
                        with profile_lib.annotate("train/data_wait"):
                            item = next(step_it, None)
                        if item is None:
                            break
                        if prof is None:
                            bx, by = item
                            span = None
                        else:
                            (bx, by), h2d_s, accum_s = item
                            span = prof.begin_step(st.step + 1, h2d_s,
                                                   accum_s=accum_s)
                        step_rng = jax.random.fold_in(st.rng, st.step)
                        with profile_lib.annotate("train/step_dispatch"):
                            if span is None:
                                st.params, st.model_state, st.opt_state, \
                                    loss = self._train_step(
                                        st.params, st.model_state,
                                        st.opt_state, step_rng, bx, by)
                            else:
                                # the span is ACTIVE across the dispatch
                                # so backend_compile events attribute to
                                # the exact step that paid the compile
                                span.phase_start("step_dispatch")
                                with trace_lib.activate(span):
                                    st.params, st.model_state, \
                                        st.opt_state, loss = \
                                        self._train_step(
                                            st.params, st.model_state,
                                            st.opt_state, step_rng, bx, by)
                                span.phase_end()
                        st.step += 1
                        faults.heartbeat()
                        train_metrics.record_step()
                        if recorder is not None:
                            # liveness marker BEFORE the fault hook: a
                            # crash at step k must leave the step-k record
                            # (the postmortem's "last completed step")
                            recorder.record_step(st.step)
                            if not st.step & 15:
                                # throttle-CHECK every 16th step: the call
                                # itself is measurable in a contended loop
                                # and the snapshot cadence is seconds
                                recorder.snapshot_metrics()
                        # injected faults land BEFORE the checkpoint
                        # trigger: a crash at step k must never leave a
                        # step-k tag
                        faults.maybe_fault(st.step)
                        epoch_samples += batch_size
                        epoch_losses.append(loss)
                        if profiling and st.step >= profile_end_step:
                            # trace covers real work
                            jax.block_until_ready(loss)
                            _stop_profile()
                        it_record = {"epoch": st.epoch,
                                     "iteration": st.step, "loss": loss}
                        if self._ckpt_path and not isinstance(
                                self._ckpt_trigger,
                                trigger_lib.EveryEpoch) \
                                and self._ckpt_trigger(it_record):
                            if span is not None:
                                span.phase_start("ckpt_save")
                            save = (save_sharded
                                    if faults.sync_checkpoints()
                                    else async_save_sharded)
                            with profile_lib.annotate("train/ckpt_save"):
                                save(self._ckpt_path, st.step,
                                     st.as_tree(),
                                     meta={"step": st.step,
                                           "epoch": st.epoch,
                                           "epoch_step":
                                               st.step - epoch_start_step})
                            if span is not None:
                                span.phase_end()
                    if span is not None:
                        prof.finish_step(span, st.step)
                    if end_trigger(it_record):
                        # remember the firing so the outer loop terminates even
                        # for triggers the outer record can't re-evaluate
                        # (e.g. MinLoss — the per-epoch record carries no loss)
                        stop = True
                        break
                # stop the worker deterministically — an iteration-level
                # end trigger breaks out with batches still buffered
                dev_it.close()
                st.epoch += 1
                # one bulk host transfer for the whole epoch's scalars
                with profile_lib.annotate("train/loss_fetch",
                                          steps=len(epoch_losses)):
                    losses_host = ([float(v) for v in np.asarray(
                        jax.device_get(epoch_losses))]
                        if epoch_losses else [])
                base_step = st.step - len(losses_host)
                history["loss"].extend(losses_host)
                elapsed = max(time.time() - epoch_start, 1e-9)
                if self.train_summary is not None:
                    # add_scalar self-gates on any set_summary_trigger
                    for i, lossf in enumerate(losses_host):
                        step_i = base_step + i + 1
                        self.train_summary.add_scalar("Loss", lossf, step_i)
                        if lr_fn is not None:
                            self.train_summary.add_scalar(
                                "LearningRate", float(lr_fn(step_i - 1)),
                                step_i)
                    self.train_summary.add_scalar(
                        "Throughput", epoch_samples / elapsed, st.step)
                    self.train_summary.flush()
                epoch_record = {"epoch": st.epoch, "iteration": st.step,
                                "epoch_finished": True,
                                "loss": history["loss"][-1]
                                if history["loss"] else None}
                if verbose:
                    # a resumed epoch whose checkpoint sat exactly on
                    # the epoch boundary replays zero batches: no loss
                    lossf = epoch_record["loss"]
                    print(f"[zoo-tpu] epoch {st.epoch} step {st.step} "
                          f"loss "
                          f"{'n/a' if lossf is None else f'{lossf:.4f}'} "
                          f"({epoch_samples / elapsed:.0f} samples/s)")
                if validation_data is not None and validation_trigger(
                        epoch_record):
                    results = self.evaluate(validation_data,
                                            validation_batch_size or batch_size)
                    history["val"].append({"epoch": st.epoch, **results})
                    if self.val_summary is not None:
                        for k, v in results.items():
                            self.val_summary.add_scalar(k, v, st.step)
                        self.val_summary.flush()
                    if verbose:
                        print(f"[zoo-tpu]   validation: {results}")
                faults.heartbeat()
                if self._ckpt_path and isinstance(self._ckpt_trigger,
                                                  trigger_lib.EveryEpoch):
                    async_save_sharded(self._ckpt_path, f"epoch{st.epoch}",
                                       st.as_tree(),
                                       meta={"step": st.step,
                                             "epoch": st.epoch,
                                             "epoch_step": 0})
        finally:
            # the trace must stop even when fit raises mid-epoch, or
            # profiling stays broken for the process ('trace already
            # started')
            _stop_profile()
            if prof is not None:
                prof.flush(recorder)  # buffered step entries
                try:
                    prof.write_timeline()
                except OSError as e:
                    from ..observability.log import get_logger
                    get_logger("analytics_zoo_tpu.train").warning(
                        "could not write step timeline",
                        path=prof.timeline_path,
                        error=f"{type(e).__name__}: {e}")
            if recorder is not None:
                recorder.snapshot_metrics(force=True)
        if self._ckpt_path:
            # fit returning means "checkpoints are on disk" — join the
            # async writers, then barrier so EVERY pod process's shards
            # are on disk before any process restores
            checkpoint_lib_wait_pending(self._ckpt_path)
            from .checkpoint import _pod_barrier
            _pod_barrier("zoo_fit_ckpt_done")
        return history

    # ------------------------------------------------------------------
    def evaluate(self, dataset: Dataset, batch_size: int,
                 metrics: Optional[Sequence] = None) -> Dict[str, float]:
        """Evaluate over the FULL dataset — the trailing partial batch is
        padded to the compiled batch shape and masked out of every metric,
        so n % batch_size != 0 loses no samples (reference evaluates the
        whole set, Topology.scala:353).

        ``metrics`` overrides the compiled metric set for this call —
        parity with the reference's ``evaluate(rdd, batch, valMethods)``
        (Topology.scala:353); names or Metric instances.
        """
        self.ensure_initialized()
        if metrics is None:
            use_metrics = self.metrics
            if self._eval_step is None:
                self._eval_step = self._mesh_scoped(
                    self._build_eval_step())
            eval_step = self._eval_step
        else:
            from ..pipeline.api.keras import metrics as metrics_lib
            zero_based = getattr(self.loss_fn, "zero_based_label", True)
            use_metrics = [metrics_lib.get(m, zero_based_label=zero_based)
                           for m in metrics]
            # cache override steps by the metrics' FULL config so an
            # epoch loop with the same valMethods doesn't re-jit, while a
            # custom Metric subclass differing in any constructor
            # attribute (not just name/k/neg_num) gets its own step
            def _metric_key(m):
                # callables are keyed by OBJECT (identity compare, and
                # the key tuple keeps them alive so ids can't be
                # recycled); everything else by repr
                cfg = tuple(sorted(
                    (k, v if callable(v) else repr(v))
                    for k, v in vars(m).items()))
                return (type(m).__module__, type(m).__qualname__,
                        m.name, cfg)
            key = tuple(_metric_key(m) for m in use_metrics)
            if self._eval_step_overrides.get("key") != key:
                self._eval_step_overrides = {
                    "key": key,
                    "step": self._mesh_scoped(
                        self._build_eval_step(use_metrics))}
            eval_step = self._eval_step_overrides["step"]
        accs = [m.init() for m in use_metrics]
        loss_acc = {"sum": jnp.zeros(()), "n": jnp.zeros(())}
        dp = mesh_lib.dp_size(self.mesh)
        nproc = dist_lib.process_count()
        per_host_bs = max(batch_size // nproc, 1)
        if nproc > 1:
            # the pod must run sharded — round the per-host batch up so
            # the global batch divides dp (padding is masked out anyway)
            if dp % nproc != 0:
                raise ValueError(
                    f"data-parallel degree ({dp}) must be a multiple of "
                    f"the process count ({nproc}) for multi-host evaluate")
            local_dp = dp // nproc
            per_host_bs = -(-per_host_bs // local_dp) * local_dp
        batch_size = per_host_bs * nproc
        sharded = batch_size % max(dp, 1) == 0
        mask_sharding = (self._batch_sharding if sharded
                         else self._repl_sharding)
        full_mask = dist_lib.put_global(
            np.ones((per_host_bs if sharded else batch_size,), np.float32),
            mask_sharding, batch_sharded=sharded)
        # per-row validity from shard_by_process wrap-around fillers:
        # they keep the pod in lockstep but must not count in metrics
        valid = getattr(dataset, "valid", None)
        offset = 0
        for bx, by in dataset.batches(per_host_bs, shuffle=False,
                                      drop_remainder=False):
            first = bx[0] if isinstance(bx, (tuple, list)) else bx
            n_real = len(first)
            v_slice = (None if valid is None
                       else valid[offset:offset + n_real])
            offset += n_real
            if v_slice is not None and v_slice.all():
                v_slice = None  # fully valid: reuse the cached full mask
            if n_real < per_host_bs or v_slice is not None:
                pad = per_host_bs - n_real
                if pad:
                    bx = _pad_tail(bx, pad)
                    if by is not None:
                        by = _pad_tail(by, pad)
                mask = np.zeros((per_host_bs,), np.float32)
                mask[:n_real] = (1.0 if v_slice is None
                                 else v_slice.astype(np.float32))
                # multi-host always runs sharded (rounded above), so the
                # replicated branch only exists single-process
                mask_dev = dist_lib.put_global(mask, mask_sharding,
                                               batch_sharded=sharded)
            else:
                mask_dev = full_mask
            faults.heartbeat()
            bx, by = self._put_batch(bx, by)
            accs, loss_acc = eval_step(
                self.state.params, self.state.model_state, accs, loss_acc,
                bx, by, mask_dev)
        results = {m.name: float(m.result(a))
                   for m, a in zip(use_metrics, accs)}
        if self.loss_fn is not None and float(loss_acc["n"]) > 0:
            results["loss"] = float(loss_acc["sum"]) / float(loss_acc["n"])
        return results

    # ------------------------------------------------------------------
    def predict(self, dataset_or_x, batch_size: int = 32) -> Any:
        """Forward the dataset.  ``batch_size`` is global; multi-host, each
        process feeds its local shard and receives its own rows back (the
        reference's partition-local predict, Topology.scala:393-397)."""
        self.ensure_initialized()
        if self._predict_step is None:
            self._predict_step = self._mesh_scoped(
                self._build_predict_step())
        if isinstance(dataset_or_x, Dataset):
            ds = dataset_or_x
        else:
            ds = Dataset.from_ndarray(dataset_or_x)
        outs = []
        n = ds.size
        if n == 0:  # size None (unknown stream length) passes through
            raise ValueError("predict called with an empty dataset")
        nproc = dist_lib.process_count()
        per_host_bs = max(batch_size // nproc, 1)
        if nproc > 1:
            # same rounding as evaluate: the pod must run sharded
            dp = mesh_lib.dp_size(self.mesh)
            if dp % nproc != 0:
                raise ValueError(
                    f"data-parallel degree ({dp}) must be a multiple of "
                    f"the process count ({nproc}) for multi-host predict")
            local_dp = dp // nproc
            per_host_bs = -(-per_host_bs // local_dp) * local_dp
        def _prep(batch):
            """Host-side pad + device_put — runs on the prefetch thread,
            overlapped with the previous batch's device compute."""
            bx, _ = batch
            pad = 0
            first = bx[0] if isinstance(bx, (tuple, list)) else bx
            if len(first) < per_host_bs:
                # pad the trailing batch to keep one compiled shape
                pad = per_host_bs - len(first)
                bx = _pad_tail(bx, pad)
            placed, _ = self._put_batch(bx, None)
            return placed, pad

        from ..common.prefetch import prefetch
        dev_it = prefetch(ds.batches(per_host_bs, shuffle=False,
                                     drop_remainder=False), _prep)
        for bx, pad in dev_it:
            y = self._predict_step(self.state.params, self.state.model_state,
                                   bx)
            # multi-host: fetch only the rows this host fed
            y = jax.tree_util.tree_map(dist_lib.local_rows, y)
            if pad:
                y = jax.tree_util.tree_map(lambda a: a[:-pad], y)
            outs.append(y)
        if isinstance(outs[0], (tuple, list)):
            return type(outs[0])(
                np.concatenate([o[i] for o in outs])[:n]
                for i in range(len(outs[0])))
        return np.concatenate(outs)[:n]

    # ------------------------------------------------------------------
    def save_weights(self, directory: str, tag="final"):
        """Per-shard save: each pod process writes only its addressable
        shards (no host-0 gather) — SURVEY §5's sharded-TrainState story."""
        from .checkpoint import save_sharded
        self.ensure_initialized()
        save_sharded(directory, tag, self.state.as_tree(),
                     meta={"step": self.state.step,
                           "epoch": self.state.epoch})

    def load_weights(self, directory: str, tag=None):
        """Restore with RE-SHARDING: the checkpoint's global leaves are
        re-placed under this trainer's shardings, so a snapshot taken on a
        different mesh shape or strategy restores cleanly."""
        from .checkpoint import restore_sharded, read_meta
        from jax.sharding import NamedSharding
        self.ensure_initialized()
        template = self.state.as_tree()

        def target_sharding(l):
            if not isinstance(l, jax.Array):
                return None
            # leaves born off-mesh (e.g. optax's scalar step count gets a
            # SingleDeviceSharding at init) must land replicated on the
            # mesh, or the restored state pins jit to one device
            if isinstance(l.sharding, NamedSharding):
                return l.sharding
            return self._repl_sharding

        shardings = jax.tree_util.tree_map(target_sharding, template)
        tree = restore_sharded(directory, template, tag,
                               shardings=shardings)
        self.state.load_tree(tree)
        meta = read_meta(directory, tag)
        self.state.step = int(meta.get("step", self.state.step))
        self.state.epoch = int(meta.get("epoch", self.state.epoch))
        # iteration-trigger snapshots land mid-epoch: the next fit()
        # fast-forwards this many batches into the restored epoch
        self._resume_epoch_step = int(meta.get("epoch_step", 0))
