"""Train-mode BatchNorm core with a hand-written VJP.

Why this exists (round 3's chip run, record withdrawn): train-mode BN batch
statistics cost ~10 ms of a 52 ms ResNet-50 step on the v5e.  The naive
formulation autodiffed by XLA has two structural inefficiencies:

1. ``jnp.var`` is two reduction passes over the activation (mean first,
   then ``mean((x - mean)**2)``), and the f32 cast of a bf16 activation
   doubles the bytes each pass reads.
2. The autodiff backward re-derives the chain through both passes,
   emitting more per-channel reductions than the closed form needs, and
   saves the f32-cast input as residual.

This kernel restructures both directions:

- **forward**: ONE fused reduction pass computes ``sum(x)`` and
  ``sum(x*x)`` together (multi-output reduction, f32 accumulation via
  dot-free elementwise + reduce; XLA fuses the pair), then
  ``var = E[x^2] - E[x]^2``.  The activation is read once, in its
  native dtype.
- **residuals**: ``xhat`` in the COMPUTE dtype (bf16 under mixed
  precision — half the bytes of the naive form's saved f32 x) plus the
  per-channel ``inv`` and ``gamma`` vectors.
- **backward**: the closed form needs exactly two per-channel
  reductions — ``sum(dy)`` and ``sum(dy * xhat)`` — which are ALSO
  dgamma/dbeta, so one fused pass over (dy, xhat) yields all reduction
  work, followed by one elementwise pass for
  ``dx = inv * gamma * (dy - mean(dy) - xhat * mean(dy * xhat))``.

Moving-statistics updates are *not* differentiated through (parity with
BigDL's SpatialBatchNormalization running stats and torch's BN): the
returned ``mean``/``var`` carry an implicit stop_gradient.

Numerical note: ``E[x^2] - E[x]^2`` cancels catastrophically only when
``|mean| >> std``; statistics accumulate in f32 (bf16 inputs are
upcast per-element inside the fused reduction, never materialized), the
same precision/structure cuDNN and tf.keras use.  ``var`` is clamped at
0 against tiny negative residuals.

Reference frame: BigDL SpatialBatchNormalization
(zoo/.../nn/SpatialBatchNormalization + keras BatchNormalization.scala)
computes identical mathematics engine-side; this is its TPU-shaped
restructuring, not a translation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _reduce_axes_and_count(x, ch_axis):
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    n = 1
    for a in axes:
        n *= x.shape[a]
    return axes, n


from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def batch_norm_train(x, gamma, beta, eps, ch_axis):
    """Train-mode batch norm over every axis except ``ch_axis`` (static
    int; ``eps`` static float).

    Returns ``(out, mean, var)``; ``mean``/``var`` are f32 per-channel
    batch statistics for the caller's moving-average update and are NOT
    differentiated through.
    """
    out, mean, var, _, _ = _bn_forward(x, gamma, beta, eps, ch_axis)
    return out, mean, var


def _bn_forward(x, gamma, beta, eps, ch_axis):
    axes, n = _reduce_axes_and_count(x, ch_axis)
    x32 = x.astype(jnp.float32)
    # one fused pass: both reductions read x once (XLA multi-output fusion)
    s1 = jnp.sum(x32, axis=axes)
    s2 = jnp.sum(x32 * x32, axis=axes)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)

    dt = x.dtype
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    mean_b = mean.astype(dt).reshape(bshape)
    inv_b = inv.astype(dt).reshape(bshape)
    xhat = (x - mean_b) * inv_b
    out = xhat * gamma.astype(dt).reshape(bshape) \
        + beta.astype(dt).reshape(bshape)
    return out, mean, var, xhat, inv


def _bn_fwd(x, gamma, beta, eps, ch_axis):
    out, mean, var, xhat, inv = _bn_forward(x, gamma, beta, eps, ch_axis)
    # residuals: compute-dtype xhat (bf16 under mixed precision) + two
    # per-channel vectors — about half the naive form's saved f32 x
    return (out, mean, var), (xhat, inv, gamma)


def _bn_bwd(eps, ch_axis, res, cts):
    xhat, inv, gamma = res
    dy = cts[0]  # mean/var cotangents are moving-stat updates: stop-grad
    axes, n = _reduce_axes_and_count(xhat, ch_axis)

    dy32 = dy.astype(jnp.float32)
    xhat32 = xhat.astype(jnp.float32)
    # ONE fused reduction pass over (dy, dy*xhat): these two vectors are
    # simultaneously dbeta, dgamma, and the backward's only reductions
    s_dy = jnp.sum(dy32, axis=axes)
    s_dyx = jnp.sum(dy32 * xhat32, axis=axes)

    dt = dy.dtype
    bshape = [1] * dy.ndim
    bshape[ch_axis] = dy.shape[ch_axis]
    mean_dy = (s_dy / n).astype(dt).reshape(bshape)
    mean_dyx = (s_dyx / n).astype(dt).reshape(bshape)
    scale = (inv.astype(dt).reshape(bshape)
             * gamma.astype(dt).reshape(bshape))
    dx = scale * (dy - mean_dy - xhat * mean_dyx)
    dgamma = s_dyx.astype(gamma.dtype)
    dbeta = s_dy.astype(gamma.dtype)
    return dx.astype(dt), dgamma, dbeta


batch_norm_train.defvjp(_bn_fwd, _bn_bwd)


# A/B switch for the perf harness: when True, BatchNormalization traces
# the pre-r4 naive formulation (jnp.mean + jnp.var + autodiff backward)
# instead of the restructured custom-VJP core.  Trace-time only — flip
# it between building two jitted step functions to measure both.
USE_NAIVE = False


def set_naive_bn(flag: bool):
    global USE_NAIVE
    USE_NAIVE = bool(flag)


def batch_norm_train_naive(x, gamma, beta, eps, ch_axis):
    """The pre-restructuring formulation (two reduction passes over an
    f32 cast, XLA-autodiff backward); nothing selects it (ROADMAP D3)."""
    axes, _ = _reduce_axes_and_count(x, ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.var(x32, axis=axes)
    dt = x.dtype
    inv = gamma.astype(dt).reshape(bshape) * (
        1.0 / jnp.sqrt(var.astype(dt).reshape(bshape) + eps))
    out = (x - mean.astype(dt).reshape(bshape)) * inv \
        + beta.astype(dt).reshape(bshape)
    return out, jax.lax.stop_gradient(mean), jax.lax.stop_gradient(var)


def batch_norm_inference(x, gamma, beta, mean, var, eps, ch_axis):
    """Eval-mode BN with moving statistics (plain XLA; fuses fully)."""
    dt = x.dtype
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps).astype(dt)
    return (x - mean.astype(dt).reshape(bshape)) \
        * (inv.reshape(bshape) * gamma.astype(dt).reshape(bshape)) \
        + beta.astype(dt).reshape(bshape)
