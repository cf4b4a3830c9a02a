"""State-space and gated feed-forward layers of hybrid language models
(``models/granitehybrid.py``): a Mamba-2 mixer and a SwiGLU MLP.  The
arithmetic is ``ops/ssm.py``'s, shared with the decode engine's plans."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .....core import initializers
from .....core.module import Layer, register_layer
from .....observability import profile as _profile
from .....ops.ssm import mamba2_mixer


def mamba2_init(rng, heads):
    """Mamba-2's published initialisation of the per-head leaves:
    ``A_log = log(U[1, 16])``, ``D = 1``, and ``dt_bias`` such that
    ``softplus(dt_bias)`` is log-uniform in ``[0.001, 0.1]``."""
    ka, kd = jax.random.split(rng)
    dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return {"A_log": jnp.log(jax.random.uniform(ka, (heads,), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((heads,)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}     # softplus^-1


@register_layer
class Mamba2Mixer(Layer):
    """A Mamba-2 mixer (``n_groups`` 1, no projection biases): input
    ``(batch, seq, d)``, output the same shape in float32 (the equations
    are ``ops/ssm.py``'s).  ``d_inner = n_heads * head_dim``; the prompt
    goes through the chunked scan in chunks of ``chunk``."""

    def __init__(self, n_heads, head_dim, d_state, d_conv=4, chunk=256,
                 epsilon=1e-5, init="glorot_uniform", input_shape=None,
                 name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.n_heads, self.head_dim = int(n_heads), int(head_dim)
        self.d_state, self.d_conv = int(d_state), int(d_conv)
        self.chunk, self.epsilon = int(chunk), float(epsilon)
        self.init_name = init

    def init_params(self, rng, input_shape):
        d = input_shape[-1]
        inner = self.n_heads * self.head_dim
        conv_dim = inner + 2 * self.d_state
        init = initializers.get(self.init_name)
        ks = jax.random.split(rng, 4)
        return {"in_proj": init(ks[0], (d, inner + conv_dim + self.n_heads)),
                "conv_w": initializers.uniform(ks[1], (self.d_conv,
                                                       conv_dim), scale=0.5),
                "conv_b": jnp.zeros((conv_dim,)),
                "norm": jnp.ones((inner,)),
                "out_proj": init(ks[2], (inner, d)),
                **mamba2_init(ks[3], self.n_heads)}

    def call(self, params, state, inputs, training=False, rng=None):
        out, _ = mamba2_mixer(params, inputs, self.epsilon, self.chunk)
        return out

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(n_heads=self.n_heads, head_dim=self.head_dim,
                   d_state=self.d_state, d_conv=self.d_conv,
                   chunk=self.chunk, epsilon=self.epsilon,
                   init=self.init_name)
        return cfg


@jax.named_scope(_profile.SCOPE_MLP)
def gated_mlp(params, h):
    """``(silu(h W[:, :f]) * (h W[:, f:])) W_out`` with one ``input_linear
    (d, 2f)``: products in the weights' dtype with float32 accumulation,
    float32 out."""
    w_in, w_out = params["input_linear"], params["output_linear"]
    f = w_out.shape[0]
    u = jnp.einsum("...e,ef->...f", h.astype(w_in.dtype), w_in,
                   preferred_element_type=jnp.float32)
    a = jax.nn.silu(u[..., :f]) * u[..., f:]
    return jnp.einsum("...f,fe->...e", a.astype(w_out.dtype), w_out,
                      preferred_element_type=jnp.float32)


@register_layer
class GatedMLP(Layer):
    """A SwiGLU feed-forward block with one fused ``input_linear`` (gate
    and up side by side, ``(d, 2 * hidden_dim)``) and an
    ``output_linear``, no biases: :func:`gated_mlp`."""

    def __init__(self, hidden_dim, init="glorot_uniform", input_shape=None,
                 name=None):
        super().__init__(input_shape=input_shape, name=name)
        self.hidden_dim = int(hidden_dim)
        self.init_name = init

    def init_params(self, rng, input_shape):
        d, f = input_shape[-1], self.hidden_dim
        init = initializers.get(self.init_name)
        k1, k2 = jax.random.split(rng)
        return {"input_linear": init(k1, (d, 2 * f)),
                "output_linear": init(k2, (f, d))}

    def call(self, params, state, inputs, training=False, rng=None):
        return gated_mlp(params, inputs)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(hidden_dim=self.hidden_dim, init=self.init_name)
        return cfg
