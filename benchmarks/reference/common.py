"""The plain pieces both references are made of: float32 `jax.numpy`,
no kernels, no cache, nothing imported from the program under test.

Every matrix product goes through `mm`, so that the control of
"How `correct` is decided" can put the same mathematics into the nearest
lower precision: `mm_f32` is the reference (float32 at `highest`, which
on a TPU means the full six bf16 passes), `mm_fp8` rounds both operands
to float8 e4m3 with one scale a tensor, the way an fp8 linear layer
would, and multiplies those.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    """Rounded to e4m3 with one scale a tensor; the gradient passes
    straight through the rounding, as fp8 training recipes have it."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm_fp8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def _bf16(x):
    x = x.astype(jnp.float32)
    return x + jax.lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def mm_bf16(a, b):
    """Operands rounded to bfloat16: the control for a float32 cell
    (the CPU tests run the program in float32)."""
    return jnp.matmul(_bf16(a), _bf16(b), precision=HIGHEST)


MM = {"f32": mm_f32, "fp8": mm_fp8, "bf16": mm_bf16}


def layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def attention(q, k, v, causal):
    """[B,S,H,D] each -> [B,S,H,D]; plain softmax(QK^T/sqrt(D))V."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    if causal:
        sq, sk = s.shape[-2:]
        keep = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def self_attention(x, w_qkv, b_qkv, w_o, b_o, heads, causal, mm):
    """Fused projection whose columns are [q | k | v], each split into
    heads in order — the layout both models' papers describe as three
    projections, written as one."""
    b, s, h = x.shape
    qkv = (mm(x, w_qkv) + b_qkv).reshape(b, s, 3, heads, h // heads)
    o = attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], causal)
    return mm(o.reshape(b, s, h), w_o) + b_o


def cross_entropy_mean(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)
