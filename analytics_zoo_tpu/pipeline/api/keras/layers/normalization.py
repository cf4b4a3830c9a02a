"""Normalization layers.

Parity surface: reference zoo/.../pipeline/api/keras/layers/
{BatchNormalization, WithinChannelLRN2D}.scala.  BatchNorm carries its moving
stats in the layer *state* collection (non-trainable pytree), updated
functionally — the jit-safe analogue of BigDL's mutable runningMean/runningVar
buffers.  Cross-replica statistics: when training data-parallel under jit with
a sharded batch axis, XLA computes global batch statistics automatically
because ``jnp.mean`` over a sharded axis lowers to a psum over ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .....core import shapes as shape_utils
from .....core.module import Layer, register_layer
from .....observability import profile as _profile


@register_layer
class BatchNormalization(Layer):
    stateful = True

    def __init__(self, epsilon=1e-3, momentum=0.99, beta_init="zero",
                 gamma_init="one", dim_ordering=None, input_shape=None,
                 name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.epsilon = float(epsilon)
        self.momentum = float(momentum)
        self.data_format = shape_utils.normalize_data_format(dim_ordering)

    def _channel_axis(self, ndim):
        return 1 if self.data_format == "channels_first" and ndim > 2 else -1

    def _num_features(self, input_shape):
        return input_shape[self._channel_axis(len(input_shape))]

    def init_params(self, rng, input_shape):
        n = self._num_features(input_shape)
        return {"gamma": jnp.ones((n,)), "beta": jnp.zeros((n,))}

    def init_state(self, input_shape):
        n = self._num_features(input_shape)
        # ``count`` = number of EMA updates applied, used to DEBIAS the
        # moving statistics at inference (below).  Imported pretrained
        # stats are already-converged averages: loaders set count=inf so
        # the debias denominator is exactly 1 and they pass through
        # untouched (models/weight_loading.py).
        return {"moving_mean": jnp.zeros((n,)),
                "moving_var": jnp.ones((n,)),
                "count": jnp.zeros((), jnp.float32)}

    def apply(self, params, state, inputs, training=False, rng=None):
        from .....ops.batchnorm import (batch_norm_train,
                                        batch_norm_inference)
        ndim = inputs.ndim
        ch_axis = self._channel_axis(ndim) % ndim

        if training:
            # restructured train-mode core (ops/batchnorm.py): one-pass
            # fused statistics + closed-form custom VJP — statistics
            # accumulate in f32 regardless of compute dtype, and the
            # moving-stat update is stop-gradient (BigDL running stats).
            # USE_NAIVE: a trace-time switch nothing sets (ROADMAP D3).
            from .....ops import batchnorm as bn_lib
            bn_fn = (bn_lib.batch_norm_train_naive if bn_lib.USE_NAIVE
                     else batch_norm_train)
            out, mean, var = bn_fn(
                inputs, params["gamma"], params["beta"],
                self.epsilon, ch_axis)
            m = self.momentum
            new_state = {
                "moving_mean": m * state["moving_mean"] + (1 - m) * mean,
                "moving_var": m * state["moving_var"] + (1 - m) * var,
            }
            if "count" in state:
                new_state["count"] = state["count"] + 1.0
        else:
            mean = state["moving_mean"]
            var = state["moving_var"]
            cnt = state.get("count")
            if cnt is not None:
                # Debias against the (0, 1) init, Adam-style: after t
                # updates the EMA still carries weight m^t on its init
                # value — with the Keras-1 default m=0.99 that is 37 %
                # after 100 steps, which through a deep BN stack makes
                # short-trained models evaluate near chance even though
                # training converged.  ema_t = m^t·init + (1−m^t)·avg,
                # so the unbiased batch-stat average is
                # (ema_t − m^t·init) / (1 − m^t); count=0 falls back to
                # the init and count=inf (imported stats) is exact
                # pass-through.
                m = self.momentum
                decay = jnp.power(m, cnt)
                denom = jnp.maximum(1.0 - decay, 1e-12)
                mean = jnp.where(cnt > 0, mean / denom,
                                 jnp.zeros_like(mean))
                var = jnp.where(cnt > 0, (var - decay) / denom,
                                jnp.ones_like(var))
            out = batch_norm_inference(
                inputs, params["gamma"], params["beta"],
                mean, var, self.epsilon, ch_axis)
            new_state = state
        return out, new_state

    def call(self, params, state, inputs, training=False, rng=None):
        return self.apply(params, state, inputs, training=training,
                          rng=rng)[0]

    def get_config(self):
        cfg = super().get_config()
        cfg.update(epsilon=self.epsilon, momentum=self.momentum,
                   dim_ordering=self.data_format)
        return cfg


@register_layer
class WithinChannelLRN2D(Layer):
    """Local response normalization within channels (reference WithinChannelLRN2D.scala)."""

    def __init__(self, size=5, alpha=1.0, beta=0.75, input_shape=None,
                 name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.size = int(size)
        self.alpha = float(alpha)
        self.beta = float(beta)

    def call(self, params, state, inputs, training=False, rng=None):
        from jax import lax
        # average squares over a size×size spatial window, per channel (NHWC)
        sq = jnp.square(inputs)
        window = (1, self.size, self.size, 1)
        summed = lax.reduce_window(sq, 0.0, lax.add, window, (1, 1, 1, 1),
                                   "SAME")
        counts = lax.reduce_window(jnp.ones_like(sq), 0.0, lax.add, window,
                                   (1, 1, 1, 1), "SAME")
        scale = (1.0 + self.alpha * summed / counts) ** self.beta
        return inputs / scale

    def get_config(self):
        cfg = super().get_config()
        cfg.update(size=self.size, alpha=self.alpha, beta=self.beta)
        return cfg


@register_layer
class LRN2D(Layer):
    """Cross-channel local response normalization (AlexNet-style)."""

    def __init__(self, alpha=1e-4, k=1.0, beta=0.75, n=5, dim_ordering=None,
                 input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.alpha, self.k, self.beta, self.n = (
            float(alpha), float(k), float(beta), int(n))
        self.data_format = shape_utils.normalize_data_format(dim_ordering)

    def call(self, params, state, inputs, training=False, rng=None):
        x = inputs
        if self.data_format == "channels_first":
            x = jnp.moveaxis(x, 1, -1)
        sq = jnp.square(x)
        half = self.n // 2
        pads = [(0, 0)] * (x.ndim - 1) + [(half, half)]
        padded = jnp.pad(sq, pads)
        acc = sum(
            padded[..., i:i + x.shape[-1]] for i in range(self.n))
        y = x / (self.k + self.alpha / self.n * acc) ** self.beta
        if self.data_format == "channels_first":
            y = jnp.moveaxis(y, -1, 1)
        return y

    def get_config(self):
        cfg = super().get_config()
        cfg.update(alpha=self.alpha, k=self.k, beta=self.beta, n=self.n,
                   dim_ordering=self.data_format)
        return cfg


@register_layer
class LayerNorm(Layer):
    """Layer normalization over the feature axis (TPU-era extension;
    required by the attention/transformer stack in ops/attention.py).
    ``bias=False`` keeps the gain alone (``(x - mean) / sqrt(var + eps) *
    gamma``), as the ``cohere2_moe`` family has it; statistics are taken
    in float32 whatever the input's dtype."""

    def __init__(self, epsilon=1e-5, bias=True, input_shape=None,
                 name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.epsilon = float(epsilon)
        self.bias = bool(bias)

    def init_params(self, rng, input_shape):
        n = input_shape[-1]
        if not self.bias:
            return {"gamma": jnp.ones((n,))}
        return {"gamma": jnp.ones((n,)), "beta": jnp.zeros((n,))}

    def call(self, params, state, inputs, training=False, rng=None):
        if not self.bias:
            x = inputs.astype(jnp.float32)
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
            return (x - mean) / jnp.sqrt(var + self.epsilon) \
                * params["gamma"].astype(jnp.float32)
        mean = jnp.mean(inputs, axis=-1, keepdims=True)
        var = jnp.var(inputs, axis=-1, keepdims=True)
        y = (inputs - mean) / jnp.sqrt(var + self.epsilon)
        return y * params["gamma"] + params["beta"]

    def get_config(self):
        cfg = super().get_config()
        cfg["epsilon"] = self.epsilon
        if not self.bias:
            cfg["bias"] = False     # omitted when True (byte-stability)
        return cfg


@register_layer
class RMSNorm(Layer):
    """Root-mean-square normalization over the feature axis, gain only:
    ``x * rsqrt(mean(x^2) + eps) * gamma``, statistics and output in
    float32 whatever the input's dtype."""

    def __init__(self, epsilon=1e-6, input_shape=None, name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.epsilon = float(epsilon)

    def init_params(self, rng, input_shape):
        return {"gamma": jnp.ones((input_shape[-1],))}

    def call(self, params, state, inputs, training=False, rng=None):
        return rms_norm(params["gamma"], inputs, self.epsilon)

    def get_config(self):
        cfg = super().get_config()
        cfg["epsilon"] = self.epsilon
        return cfg


@jax.named_scope(_profile.SCOPE_NORM)
def rms_norm(gamma, x, eps):
    """:class:`RMSNorm`'s arithmetic, for the code that reads its
    parameters by name (the decode paths)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gamma.astype(jnp.float32)
