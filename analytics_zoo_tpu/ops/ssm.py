"""Mamba-2 state-space layers: the one implementation that the keras
layer (``pipeline/api/keras/layers/ssm.py``), the decode engine's admit
plans and its decode step all call.

A layer, per head ``h`` (``P`` channels of ``x``, a state of ``P x N``;
``n_groups`` 1, so ``B`` and ``C`` are shared by the heads):

    [z | xBC | dt] = h W_in                  z: d_inner, xBC: d_inner + 2N
    xBC  = silu(causal depthwise conv1d(xBC) + b_conv)        kernel K
    [x | B | C] = xBC
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               S_{-1} = 0
    y_t  = S_t C_t + D x_t
    out  = (w * rmsnorm(y * silu(z))) W_out

A prompt goes through :func:`ssd_scan`, Mamba-2's chunked dual form: in
a chunk the masked ``(C B^T o decay)`` products on the MXU, between
chunks a scan over the chunk states.  A decode step goes through
:func:`ssm_decode`, which on a TPU is the pallas kernel
``zoo_ssm_decode`` updating each slot's float32 state in place, and
through :func:`conv_step` over a rolling window of the last ``K - 1``
inputs of the convolution.

Products take the weights' dtype (bfloat16 as served) with float32
accumulation; the state, the decays, softplus, the norm and every sum
are float32."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import profile as _profile
from .attention import _on_tpu


def mixer_dims(p):
    """``(d_inner, heads, head_dim, d_state, d_conv)`` of a mixer's
    parameters (``n_groups`` 1)."""
    d_inner = p["norm"].shape[0]
    heads = p["A_log"].shape[0]
    d_conv, conv_dim = p["conv_w"].shape
    return (d_inner, heads, d_inner // heads, (conv_dim - d_inner) // 2,
            d_conv)


# ------------------------------------------------------------ convolution
def causal_conv(x, w, b):
    """Depthwise causal convolution over positions of ``x (batch, s,
    channels)`` with the kernel ``w (K, channels)`` and bias ``b``:
    ``y_t = sum_k w_k x_{t - K + 1 + k} + b``, zeros before the first
    position.  Float32."""
    k = w.shape[0]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = sum(xp[:, i:i + s] * w[i] for i in range(k))
    return y + b.astype(jnp.float32)


def conv_window(x, length, width):
    """The last ``width`` rows of ``x (1, s, channels)`` before position
    ``length``, zeros where ``length < width``: what :func:`conv_step`
    needs to go on from there."""
    xp = jnp.pad(x, ((0, 0), (width, 0), (0, 0)))
    return lax.dynamic_slice_in_dim(xp, length, width, axis=1)


def conv_step(window, x_new, w, b):
    """One position of :func:`causal_conv`: ``window (batch, K - 1,
    channels)`` holds the previous inputs, oldest first.  Returns ``(y
    (batch, channels) float32, window')``, the window rolled by one in
    its own dtype."""
    full = jnp.concatenate([window, x_new[:, None].astype(window.dtype)],
                           axis=1)
    y = jnp.sum(full.astype(jnp.float32) * w.astype(jnp.float32)[None],
                axis=1) + b.astype(jnp.float32)
    return y, full[:, 1:]


# --------------------------------------------------------- the chunked scan
def ssd_scan(x, dt, A, B, C, D, lengths=None, chunk=256,
             dtype=jnp.bfloat16):
    """The recurrence over a prompt in Mamba-2's chunked dual form.

    ``x (b, s, heads, P)``, ``dt (b, s, heads)`` (after softplus), ``A
    (heads,)`` (negative), ``B``, ``C (b, s, N)``, ``D (heads,)``;
    ``lengths (b,)``: ``dt`` is 0 at positions ``>= length``, so past it
    the state neither decays nor takes anything in and ``final_state``
    is the state AT ``length`` (the rows of ``y`` there are not live).
    A length that is no multiple of ``chunk`` is padded with ``dt`` 0.
    The products take ``dtype`` operands (the weights' dtype) with
    float32 accumulation.  Returns ``(y (b, s, heads, P) float32,
    final_state (b, heads, P, N) float32)``."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    dt = dt.astype(jnp.float32)
    if lengths is not None:
        live = jnp.arange(s)[None, :] < jnp.reshape(lengths, (-1, 1))
        dt = jnp.where(live[..., None], dt, 0.0)
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)]
                               + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc, l = (s + pad) // chunk, chunk
    xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(b, nc, l, h, p)
    # the log-decays, cumulated inside each chunk: (b, nc, heads, l)
    acum = jnp.cumsum((dt * A.astype(jnp.float32)).reshape(b, nc, l, h),
                      axis=2).transpose(0, 1, 3, 2)
    Bc = B.reshape(b, nc, l, n).astype(dtype)
    Cc = C.reshape(b, nc, l, n).astype(dtype)
    # inside a chunk: y_t += sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t}
    # a_r) dt_s x_s
    cb = jnp.einsum("bctn,bcsn->bcts", Cc, Bc,
                    preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((l, l), bool))
    seg = acum[..., :, None] - acum[..., None, :]         # (b,nc,h,t,s)
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bchts,bcshp->bcthp",
                   (cb[:, :, None] * decay).astype(dtype),
                   xdt.astype(dtype), preferred_element_type=jnp.float32)
    # each chunk's own state at its end, then the states carried over
    to_end = jnp.exp(acum[..., -1:] - acum)               # (b,nc,h,l)
    states = jnp.einsum(
        "bcsn,bcshp->bchpn", Bc,
        (xdt * to_end.transpose(0, 1, 3, 2)[..., None]).astype(dtype),
        preferred_element_type=jnp.float32)
    chunk_decay = jnp.exp(acum[..., -1])                  # (b,nc,h)

    def carry(state, inp):
        own, dec = inp
        return state * dec[..., None, None] + own, state

    final, before = lax.scan(
        carry, jnp.zeros((b, h, p, n), jnp.float32),
        (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    # what the state before each chunk gives its positions
    y = y + jnp.einsum("bctn,cbhpn->bcthp", Cc, before.astype(dtype),
                       preferred_element_type=jnp.float32) \
        * jnp.exp(acum).transpose(0, 1, 3, 2)[..., None]
    y = y.reshape(b, nc * l, h, p)[:, :s]
    return y + D.astype(jnp.float32)[:, None] \
        * x[:, :s].astype(jnp.float32), final


# ------------------------------------------------- the decode step's update
#: heads of one slot a grid step of ``zoo_ssm_decode`` takes: 32 heads of
#: a 64 x 128 float32 state are 1 MiB in and 1 MiB out
_HEAD_BLOCK = 32


def _ssm_decode_kernel(s_ref, u_ref, a_ref, b_ref, c_ref, so_ref, y_ref):
    """One slot's block of heads: ``S' = a S + u B^T`` (``u = dt x``, ``a
    = exp(dt A)``) with ``S`` lane-dense along the state's N, then ``y =
    S' C`` summed along the lanes."""
    s = s_ref[...] * a_ref[...][:, :, None] \
        + u_ref[...][:, :, None] * b_ref[...][None]
    so_ref[...] = s
    y_ref[...] = jnp.sum(s * c_ref[...][None], axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode_call(state, u, a, B, C, interpret: bool = False):
    """The kernel over ``state (b, heads, P, N)`` float32, ``u (b, heads,
    P)``, ``a (b, heads)``, ``B``, ``C (b, N)``; the state goes in and out
    through ``input_output_aliases``: read once, written once.  A jit of
    its own, lowered once a program (a ``pallas_call`` is lowered where
    it is called)."""
    b, h, p, n = state.shape
    hb = min(_HEAD_BLOCK, h)

    def block(*shape):
        return pl.BlockSpec((None, hb) + shape,
                            lambda i, j: (i, j) + (0,) * len(shape))

    row = pl.BlockSpec((None, 1, n), lambda i, j: (i, 0, 0))
    new, y = pl.pallas_call(
        _ssm_decode_kernel,
        grid=(b, h // hb),
        in_specs=[block(p, n), block(p), block(1), row, row],
        out_specs=[block(p, n), block(p)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, h, p), jnp.float32)],
        input_output_aliases={0: 0},
        interpret=interpret,
        name=_profile.KERNEL_SSM_DECODE,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))}),
    )(state, u.astype(jnp.float32), a.astype(jnp.float32)[..., None],
      B.astype(jnp.float32)[:, None], C.astype(jnp.float32)[:, None])
    return y, new


def _ssm_decode_reference(state, u, a, B, C):
    """The kernel's twin in ``jax.numpy``: what runs off the chip."""
    s = state * a[..., None, None] + u[..., None] \
        * B.astype(jnp.float32)[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", s, C.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    return y, s


def ssm_decode(state, x, dt, A, B, C, D):
    """One position of the recurrence for every slot: ``state (b, heads,
    P, N)`` float32, ``x (b, heads, P)``, ``dt (b, heads)`` (after
    softplus), ``A``, ``D (heads,)``, ``B``, ``C (b, N)``.  Returns ``(y
    (b, heads, P) float32, state')``.  On a TPU the pallas kernel
    ``zoo_ssm_decode``, which updates the state in place; otherwise its
    twin in ``jax.numpy``."""
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    u = dt[..., None] * x
    a = jnp.exp(dt * A.astype(jnp.float32))
    if _on_tpu():
        y, state = _ssm_decode_call(state, u, a, B, C)
    else:
        y, state = _ssm_decode_reference(state, u, a, B, C)
    return y + D.astype(jnp.float32)[:, None] * x, state


# --------------------------------------------------------------- the mixer
def gated_rmsnorm(y, z, w, eps):
    """``w * rmsnorm(y * silu(z))`` over the last axis (all of
    ``d_inner``: one group), float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    return g * lax.rsqrt(var + eps) * w.astype(jnp.float32)


@jax.named_scope(_profile.SCOPE_SSM_PROJ)
def _split_proj(p, h):
    """``h W_in`` split into ``z`` (float32), ``xBC`` (in the weights'
    dtype: what the convolution and its window take) and ``dt`` (float32,
    before softplus)."""
    d_inner, heads, _, n, _ = mixer_dims(p)
    w = p["in_proj"]
    zxd = jnp.einsum("...e,ef->...f", h.astype(w.dtype), w,
                     preferred_element_type=jnp.float32)
    conv_dim = d_inner + 2 * n
    return (zxd[..., :d_inner], zxd[..., d_inner:d_inner + conv_dim]
            .astype(w.dtype), zxd[..., d_inner + conv_dim:])


@jax.named_scope(_profile.SCOPE_SSM_PROJ)
def _out(p, y, z, eps):
    w = p["out_proj"]
    g = gated_rmsnorm(y, z, p["norm"], eps)
    return jnp.einsum("...f,fe->...e", g.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _dt(p, dt):
    return jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def mamba2_mixer(p, h, eps, chunk, lengths=None):
    """The mixer over a prompt ``h (b, s, d)``: returns ``(out (b, s, d)
    float32, (window (b, K - 1, conv_dim), state (b, heads, P, N)))``,
    the convolution's last inputs and the state at ``lengths`` (at the
    end where not given): what a decode step goes on from."""
    d_inner, heads, hd, n, k = mixer_dims(p)
    b, s, _ = h.shape
    with jax.named_scope(_profile.SCOPE_SSM):
        z, xbc, dt = _split_proj(p, h)
        with jax.named_scope(_profile.SCOPE_SSM_CONV):
            at = s if lengths is None else lengths
            window = jax.vmap(lambda r, n_: conv_window(r[None], n_, k - 1)[0]
                              )(xbc, jnp.broadcast_to(at, (b,)))
            act = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
        with jax.named_scope(_profile.SCOPE_SSM_SCAN):
            y, state = ssd_scan(
                act[..., :d_inner].reshape(b, s, heads, hd), _dt(p, dt),
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                act[..., d_inner:d_inner + n], act[..., d_inner + n:],
                p["D"], lengths=lengths, chunk=chunk,
                dtype=p["in_proj"].dtype)
            y = y.reshape(b, s, d_inner)
        return _out(p, y, z, eps), (window, state)


def mamba2_mixer_step(p, h, window, state, eps):
    """The mixer at one position of every slot: ``h (b, d)``, the slots'
    convolution windows and states.  Returns ``(out (b, d) float32,
    window', state')``."""
    d_inner, heads, hd, n, _ = mixer_dims(p)
    b = h.shape[0]
    with jax.named_scope(_profile.SCOPE_SSM):
        z, xbc, dt = _split_proj(p, h)
        with jax.named_scope(_profile.SCOPE_SSM_CONV):
            act, window = conv_step(window, xbc, p["conv_w"], p["conv_b"])
            act = jax.nn.silu(act)
        with jax.named_scope(_profile.SCOPE_SSM_SCAN):
            y, state = ssm_decode(
                state, act[:, :d_inner].reshape(b, heads, hd), _dt(p, dt),
                -jnp.exp(p["A_log"].astype(jnp.float32)),
                act[:, d_inner:d_inner + n], act[:, d_inner + n:], p["D"])
            y = y.reshape(b, d_inner)
        return _out(p, y, z, eps), window, state
