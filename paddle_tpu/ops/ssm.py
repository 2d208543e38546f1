"""State-space (Mamba-2 / SSD) sequence mixing over a per-slot state pool.

The recurrence, a head (`S` is `[P, N]`, `a < 0`, `d` the softplus'd
step):

    S_t = exp(d_t a) S_{t-1} + d_t x_t (x) B_t        y_t = S_t C_t + D x_t

Two forms of the same mathematics, both starting from a CARRIED state:

* `ssd_chunk_scan` — a prefill chunk of one slot: the chunked (SSD) form,
  `chunk_size` tokens at a time as matrix products (the decays inside a
  chunk are a lower-triangular matrix), the state handed from chunk to
  chunk. Plain XLA: the products are einsums at `highest`.
* `ssm_decode_step` — one token for every slot: the one-step recurrence
  over the pool `[layers, rows, heads, P, N]`, a Pallas kernel on the
  chip (`ops/pallas/ssm.py`), gather / scatter in XLA elsewhere.

Heads read B and C of their group (`heads // groups` heads a group), and
nothing here repeats B or C: heads are kept as `[groups, heads a group]`.

`SSM_PATH_STATS` counts which decode form was traced, as
`PAGED_PATH_STATS` does for the paged kernel: never a silent fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.device import on_tpu, pallas_interpret

SSM_BACKENDS = ("auto", "xla", "pallas")
SSM_PATH_STATS = {"xla": 0, "pallas": 0}
_HIGHEST = jax.lax.Precision.HIGHEST


def reset_ssm_path_stats():
    for k in SSM_PATH_STATS:
        SSM_PATH_STATS[k] = 0


def resolve_ssm_backend(backend, state_size=128):
    """`auto` takes the kernel on a TPU where the state's last axis fills
    whole 128-lane tiles; an explicit choice always wins (off the chip
    `pallas` runs the interpreter)."""
    if backend not in SSM_BACKENDS:
        raise ValueError(f"backend must be one of {SSM_BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if on_tpu() and state_size % 128 == 0 else "xla"


def causal_conv_chunk(xbc, carried, weight, bias, n_valid):
    """Depthwise causal conv over one slot's chunk, then SiLU.
    xbc `[C, D]`; carried `[K-1, D]` the inputs before the chunk; weight
    `[K, D]` (tap K-1 on the current token); the first `n_valid` rows of
    the chunk are real. -> (`[C, D]`, the last K-1 real inputs)."""
    k = weight.shape[0]
    inputs = jnp.concatenate([carried.astype(xbc.dtype), xbc], axis=0)
    rows = xbc.shape[0]
    out = sum(weight[j].astype(jnp.float32)
              * inputs[j:j + rows].astype(jnp.float32) for j in range(k))
    out = jax.nn.silu(out + bias.astype(jnp.float32))
    kept = jax.lax.dynamic_slice_in_dim(inputs, n_valid, k - 1, axis=0)
    return out, kept.astype(carried.dtype)


def causal_conv_step(xbc, carried, weight, bias):
    """One token a slot. xbc `[slots, D]`; carried `[slots, K-1, D]`.
    -> (`[slots, D]` float32, the new carried inputs)."""
    inputs = jnp.concatenate(
        [carried.astype(xbc.dtype), xbc[:, None]], axis=1)
    out = jnp.einsum("skd,kd->sd", inputs.astype(jnp.float32),
                     weight.astype(jnp.float32), precision=_HIGHEST)
    out = jax.nn.silu(out + bias.astype(jnp.float32))
    return out, inputs[:, 1:].astype(carried.dtype)


def _ssd_chunk(state, x, dt, a, b, c):
    """One SSD chunk. state `[g, r, P, N]`; x `[Q, g, r, P]`; dt
    `[Q, g, r]` (0 on rows past the prompt); a `[g, r]`; b, c
    `[Q, g, N]`. All float32. -> (y `[Q, g, r, P]`, the state after)."""
    q = x.shape[0]
    la = jnp.cumsum(dt * a, axis=0)                       # [Q, g, r] <= 0
    scores = jnp.einsum("tgn,sgn->gts", c, b, precision=_HIGHEST)
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    gap = la[:, None] - la[None, :]                       # [t, s, g, r]
    decay = jnp.exp(jnp.where(causal[:, :, None, None], gap, -jnp.inf))
    mix = scores[:, None] * jnp.transpose(decay * dt[None], (2, 3, 0, 1))
    y = jnp.einsum("grts,sgrp->tgrp", mix, x, precision=_HIGHEST)
    y = y + jnp.exp(la)[..., None] * jnp.einsum(
        "grpn,tgn->tgrp", state, c, precision=_HIGHEST)
    tail = jnp.exp(la[-1][None] - la) * dt                # [Q, g, r]
    state = jnp.exp(la[-1])[..., None, None] * state + jnp.einsum(
        "sgrp,sgn->grpn", x * tail[..., None], b, precision=_HIGHEST)
    return y, state


def ssd_chunk_scan(x, dt, a, b, c, d_skip, state, n_valid,
                   chunk_size=128):
    """A chunk of ONE slot's prompt through the recurrence, from the
    carried `state`. x `[T, heads, P]`; dt `[T, heads]` float32, softplus
    done; a, d_skip `[heads]`; b, c `[T, groups, N]`; state
    `[heads, P, N]` float32; rows at and past `n_valid` (the prompt's
    padding) leave the state as it is. -> (y `[T, heads, P]` float32,
    the state after the last real row)."""
    t, heads, p = x.shape
    g, n = b.shape[1], b.shape[2]
    r = heads // g
    f32 = jnp.float32
    dt = jnp.where(jnp.arange(t)[:, None] < n_valid, dt.astype(f32), 0.0)
    xg = x.astype(f32).reshape(t, g, r, p)
    dtg, ag = dt.reshape(t, g, r), a.astype(f32).reshape(g, r)
    bf, cf = b.astype(f32), c.astype(f32)
    st = state.astype(f32).reshape(g, r, p, n)
    ys = []
    for lo in range(0, t, chunk_size):          # static: T / chunk_size
        hi = min(lo + chunk_size, t)
        y, st = _ssd_chunk(st, xg[lo:hi], dtg[lo:hi], ag, bf[lo:hi],
                           cf[lo:hi])
        ys.append(y)
    y = jnp.concatenate(ys, axis=0).reshape(t, heads, p)
    y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
    return y, st.reshape(heads, p, n).astype(state.dtype)


def _decode_xla(pool, layer, rows, x, dt, a, d_skip, b, c):
    slots, heads, p = x.shape
    g = b.shape[1]
    f32 = jnp.float32
    state = pool[layer, rows].reshape(slots, g, heads // g, p, -1)
    xg = x.astype(f32).reshape(slots, g, heads // g, p)
    dtg = dt.astype(f32).reshape(slots, g, heads // g)
    decay = jnp.exp(dtg * a.astype(f32).reshape(g, -1))
    state = decay[..., None, None] * state + \
        (dtg[..., None] * xg)[..., None] \
        * b.astype(f32)[:, :, None, None, :]
    y = jnp.sum(state * c.astype(f32)[:, :, None, None, :], axis=-1)
    y = y.reshape(slots, heads, p) \
        + d_skip.astype(f32)[:, None] * x.astype(f32)
    pool = pool.at[layer, rows].set(
        state.reshape(slots, heads, p, -1).astype(pool.dtype))
    return y.astype(x.dtype), pool


def ssm_decode_step(pool, layer, rows, x, dt, a, d_skip, b, c,
                    backend="auto"):
    """One token for every slot. pool `[layers, rows, heads, P, N]`
    float32; `rows` `[slots]` int32, the slot's row of the pool (0, the
    null row, for lanes that do not decode this step: it takes their
    garbage as the null block does); x `[slots, heads, P]`; dt
    `[slots, heads]` float32, softplus done; b, c `[slots, groups, N]`.
    -> (y `[slots, heads, P]`, the pool with `layer` updated)."""
    resolved = resolve_ssm_backend(backend, pool.shape[-1])
    SSM_PATH_STATS[resolved] += 1
    if resolved == "pallas":
        from .pallas.ssm import ssm_decode_update

        return ssm_decode_update(pool, layer, rows, x, dt, a, d_skip, b,
                                 c, interpret=pallas_interpret())
    return _decode_xla(pool, layer, rows, x, dt, a, d_skip, b, c)
