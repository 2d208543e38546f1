"""The pieces more than one decoder under `models/` is built from: named
leaves created in the run dtype, the matrix product with float32
accumulation, the RMSNorm with float32 statistics, the rotary embedding
(two halves paired) and the gated (SwiGLU) MLP. Plain functions over
`jax` arrays: a model file calls them from its whole-sequence forward and
from its serving steps alike.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn

_F32 = jnp.float32


def leaf(layer, cfg, shape, std, mean=0.0):
    """A parameter in the run dtype: mean + N(0, std), or nought where
    the caller binds every leaf itself (`cfg.init == "zeros"`)."""
    init = nn.initializer.Constant(0.0) if cfg.init == "zeros" \
        else nn.initializer.Normal(mean, std)
    return layer.create_parameter(list(shape), dtype=cfg.dtype,
                                  default_initializer=init)


class Leaves(nn.Layer):
    """Named parameters and nothing else: `weight=(shape, std)` draws
    N(0, std); `(shape, std, 1.0)` draws 1 + N(0, std)."""

    def __init__(self, cfg, **leaves):
        super().__init__(dtype=cfg.dtype)
        for name, spec in leaves.items():
            setattr(self, name, leaf(self, cfg, *spec))


def dot(a, w):
    """Operands as they are stored, float32 accumulation, the result in
    the activations' type."""
    return jnp.dot(a, w, preferred_element_type=_F32).astype(a.dtype)


def rms_norm(x, gain, eps):
    """Statistics in float32, the result in x's dtype."""
    xf = x.astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                            + eps)
    return (xf * gain.astype(_F32)).astype(x.dtype)


def rotary(x, positions, theta):
    """x `[.., n, d]` at `positions [.., ]` (broadcast over `n`): value i
    of the first half pairs with value i of the second, turned by
    `positions * theta^(-2 i / d)`; float32, the result in x's dtype."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = positions.astype(_F32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2].astype(_F32), x[..., d // 2:].astype(_F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def gated_mlp(u, w_gate_up, w_down):
    """`(silu(u W_g) * (u W_u)) W_d` with `[W_g | W_u]` side by side; the
    gate in float32."""
    gate_up = dot(u, w_gate_up)
    h = gate_up.shape[-1] // 2
    hidden = jax.nn.silu(gate_up[..., :h].astype(_F32)) \
        * gate_up[..., h:].astype(_F32)
    return dot(hidden.astype(u.dtype), w_down)
