"""SwitchMoE: mixture-of-experts as a Keras-API layer.

Extension scope (no reference analog — SURVEY §2.10: the reference is
data-parallel only): wraps the functional switch-MoE block
(``analytics_zoo_tpu.parallel.expert``) in the layer contract so
Sequential/Model users get an MoE FFN with one ``add``.  When the
active mesh (the one ``compile(mesh=...)`` hands the trainer) carries
an ``expert`` axis that divides the expert and token counts, the layer
runs EXPERT-PARALLEL automatically (``moe_sharded``: experts sharded,
tokens by all_to_all, per-shard capacity); otherwise it runs the
single-device formulation with replicated experts.

Input (batch, seq, d_model) or (batch, d_model); output the same shape
with a residual connection (so capacity-dropped tokens pass through
unchanged).  The load-balancing aux loss (scaled by ``aux_weight``) is surfaced
through the layer state under the reserved key ``aux_loss``, which
``build_train_step`` sums into the training loss inside the gradient
closure — the router receives the Switch balancing gradient with no
user wiring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .....core import initializers
from .....core.module import Layer, register_layer
from .....observability.log import get_logger
from .....ops.moe import moe_sublayer
from .....parallel.expert import (MoEParams, expert_capacity,
                                  init_moe_params, moe_sharded,
                                  switch_moe)

#: layer name -> reason, recorded whenever a SwitchMoE falls back to the
#: replicated formulation DESPITE an expert mesh axis being present — a
#: silent perf cliff otherwise (VERDICT r4 #6).  The strategy report
#: surfaces a snapshot; ``clear_fallback_log`` resets between compiles.
EXPERT_FALLBACKS: dict = {}
_slog = get_logger("analytics_zoo_tpu.moe")


def clear_fallback_log():
    EXPERT_FALLBACKS.clear()


def _note_fallback(name: str, reason: str):
    if name not in EXPERT_FALLBACKS:
        # warn once per layer (at trace time — once per compile, not
        # per step)
        _slog.warning(
            "SwitchMoE: expert mesh axis present but not usable — "
            "running REPLICATED (every device computes all experts). "
            "This is a perf cliff at scale; fix the divisibility to "
            "get expert parallelism.", layer=name, reason=reason)
    EXPERT_FALLBACKS[name] = reason


@register_layer
class SwitchMoE(Layer):
    """Switch-routed MoE FFN with residual: y = x + MoE(x)."""

    stateful = True

    def __init__(self, n_experts: int = 8, hidden_dim: int = None,
                 capacity_factor: float = 1.25, aux_weight: float = 0.01,
                 residual: bool = True, input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.n_experts = int(n_experts)
        self.hidden_dim = hidden_dim
        self.capacity_factor = float(capacity_factor)
        # the Switch paper's load-balancing coefficient; the trainer sums
        # every layer's state["aux_loss"] into the training loss
        self.aux_weight = float(aux_weight)
        # residual=False emits bare MoE(x) so pre-norm stacks can
        # compose LN -> MoE -> Dropout -> Merge like any other sublayer
        # (capacity-dropped tokens then contribute zero, which the
        # OUTER residual passes through unchanged — same semantics)
        self.residual = bool(residual)

    def _dims(self, input_shape):
        d = input_shape[-1]
        h = self.hidden_dim or 4 * d
        return d, h

    def init_params(self, rng, input_shape):
        d, h = self._dims(input_shape)
        p = init_moe_params(rng, d, h, self.n_experts)
        return dict(p._asdict())

    def init_state(self, input_shape):
        return {"aux_loss": jnp.zeros(())}

    def call(self, params, state, inputs, training=False, rng=None):
        d = inputs.shape[-1]
        flat = inputs.reshape(-1, d)
        p = MoEParams(**{k: params[k]
                         for k in MoEParams._fields})
        # opportunistic expert parallelism: when the ACTIVE mesh (the
        # one compile(mesh=...) handed the trainer) carries an 'expert'
        # axis that divides both the expert count and the token count,
        # experts shard over it and tokens travel by all_to_all;
        # otherwise the single-device formulation runs (replicated
        # experts — always correct)
        from .....parallel.mesh import get_active_mesh
        mesh = get_active_mesh()
        esize = (mesh.shape["expert"]
                 if mesh is not None and "expert" in mesh.axis_names
                 else 0)
        if esize > 1 and self.n_experts % esize == 0 \
                and flat.shape[0] % esize == 0:
            out, aux = moe_sharded(
                flat, p, mesh, capacity_factor=self.capacity_factor)
        else:
            if esize > 1:
                _note_fallback(
                    self.name,
                    (f"expert count {self.n_experts} is not divisible "
                     f"by the axis size {esize}"
                     if self.n_experts % esize else
                     f"token count {flat.shape[0]} is not divisible by "
                     f"the axis size {esize}"))
            cap = expert_capacity(flat.shape[0], self.n_experts,
                                  self.capacity_factor)
            out, aux = switch_moe(flat, p, capacity=cap)
        y = out.reshape(inputs.shape)
        if self.residual:
            y = inputs + y
        return y, {"aux_loss": self.aux_weight * aux}

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(n_experts=self.n_experts, hidden_dim=self.hidden_dim,
                   capacity_factor=self.capacity_factor,
                   aux_weight=self.aux_weight, residual=self.residual)
        return cfg


@register_layer
class TopKMoE(Layer):
    """Top-k, dropless mixture of gated (SwiGLU) experts with shared
    experts beside them, told which experts it holds (``ops/moe.py``):

        s = sigmoid(h Wr);  I = top-k of s;  g_e = s_e / sum_{I} s
        y = sum_{e in I, e held} g_e E_e(h) + mean_j S_j(h)

    The router keeps all ``n_experts`` outputs; ``experts_held = (first,
    count)`` names the contiguous range whose weights live here (default:
    all of them), stacked ``(count, d, hidden)``: one chip's share of an
    expert-parallel layer, with nothing standing in for the others.
    No capacity, no dropped token, no auxiliary loss, no state.  Input
    ``(batch, seq, d)`` or ``(tokens, d)``, output the same shape in
    float32, WITHOUT a residual."""

    def __init__(self, n_experts, top_k, hidden_dim, n_shared=0,
                 experts_held=None, init="glorot_uniform",
                 input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.hidden_dim, self.n_shared = int(hidden_dim), int(n_shared)
        first, count = experts_held or (0, self.n_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {experts_held} is no range of "
                             f"the {n_experts} experts")
        self.experts_held = (int(first), int(count))
        self.init_name = init

    def init_params(self, rng, input_shape):
        d, f = input_shape[-1], self.hidden_dim
        init = initializers.get(self.init_name)
        ks = iter(jax.random.split(rng, 7))

        def stack(n, shape):
            return jax.vmap(lambda r: init(r, shape))(
                jax.random.split(next(ks), n))

        count = self.experts_held[1]
        p = {"router": init(next(ks), (d, self.n_experts)),
             "w_gate": stack(count, (d, f)), "w_up": stack(count, (d, f)),
             "w_down": stack(count, (f, d))}
        if self.n_shared:
            p.update(s_gate=stack(self.n_shared, (d, f)),
                     s_up=stack(self.n_shared, (d, f)),
                     s_down=stack(self.n_shared, (f, d)))
        return p

    def call(self, params, state, inputs, training=False, rng=None):
        flat = inputs.reshape(-1, inputs.shape[-1])
        m, _ = moe_sublayer(params, flat, self.top_k, self.experts_held)
        return m.reshape(inputs.shape)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(n_experts=self.n_experts, top_k=self.top_k,
                   hidden_dim=self.hidden_dim, n_shared=self.n_shared,
                   experts_held=list(self.experts_held),
                   init=self.init_name)
        return cfg
