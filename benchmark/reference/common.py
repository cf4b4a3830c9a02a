"""What every family's plain reference shares: the seed's key, per-leaf
norms, the fake fp8 rounding of the controls, and the loop that follows
the program's first optimizer steps (Adam written out; nothing here
imports the program or optax)."""

import functools

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def fp8(x):
    """Round to float8_e4m3 with a per-tensor scale; straight-through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30) / 448.0
    q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    q = (q.astype(jnp.float32) * scale).astype(x.dtype)
    return x + lax.stop_gradient(q - x)


def leaf_norms(tree):
    """{"layer/leaf": l2 norm} as one small device computation."""
    flat = {f"{layer}/{leaf}": v for layer, d in tree.items()
            for leaf, v in d.items()}
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(flat)


def diff_norms(a, b):
    return leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
        a, b))(a, b))


def train_steps(loss_fn, make_params, opt, x, y, steps=3, rows=None,
                batch_rows=None):
    """Follow the program's first ``steps`` optimizer steps from the
    seed's weights.  ``loss_fn(params, x, y)`` is the mean loss of a
    block of rows; ``make_params()`` the seed's weights; ``opt`` the
    configuration's ``train`` object (Adam's lr, b1, b2, eps).
    ``x``/``y``: (steps, batch, ...), one batch a step; each batch goes
    through in blocks of ``rows`` rows whose gradients are averaged
    (``None``: the whole batch at once, as batch statistics need).
    ``batch_rows`` (a fault's knob): use only the first that many rows
    of each batch.

    Returns the numbers the check compares: each step's loss, the
    per-leaf norm of the first gradient, the per-leaf norm of the
    parameters' change after the last step."""
    lr, b1, b2, eps = opt["lr"], opt["b1"], opt["b2"], opt["eps"]
    grad = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, t):
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                   v, g)
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), p, m, v)
        return p, m, v

    p = make_params()
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, gnorm = [], None
    for step in range(steps):
        n = batch_rows or x.shape[1]
        blocks = range(0, n, rows or n)
        g_sum, loss_sum = None, 0.0
        for r in blocks:
            loss, g = grad(p, jnp.asarray(x[step, r:min(r + (rows or n), n)]),
                           jnp.asarray(y[step, r:min(r + (rows or n), n)]))
            g_sum = g if g_sum is None else add(g_sum, g)
            loss_sum += float(loss)
        g = jax.jit(lambda g: jax.tree_util.tree_map(
            lambda a: a / len(blocks), g), donate_argnums=0)(g_sum)
        losses.append(loss_sum / len(blocks))
        if step == 0:
            gnorm = jax.device_get(leaf_norms(g))
        p, m, v = adam(p, m, v, g, jnp.float32(step + 1))
        del g, g_sum
    del m, v
    dnorm = jax.device_get(diff_norms(p, make_params()))
    return {"loss": losses,
            "grad_norm": {k: float(v) for k, v in gnorm.items()},
            "dparam_norm": {k: float(v) for k, v in dnorm.items()}}
