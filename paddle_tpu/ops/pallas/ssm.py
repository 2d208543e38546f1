"""Pallas kernel of the state-space decode step: one token a slot.

    S <- exp(d A) S + (d x) (x) B        S: [heads, P, N] float32 a slot
    y  = S C + D x

The state pool is `[layers, rows, heads, P, N]` float32 and stays in
HBM; a program of the grid takes one slot's `hb` heads (one group's, so
B and C are one row each), and the pool's block is chosen by the slot's
ROW, a scalar-prefetch operand: idle and prefilling lanes ride row 0,
the null row, as idle lanes ride the null block of the paged pool. The
pool aliases its output, so a step moves every live slot's state through
the chip once (read and write): the kernel is bound by those bytes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .naming import kernel_name

#: the name the device trace shows (`kernel_metadata`), and the
#: benchmark's `ssm_decode_roofline` looks for
KERNEL_NAME = "ssm_decode_update"


def _kernel(rows_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref,
            y_ref, out_ref):
    del rows_ref
    x = x_ref[0].astype(jnp.float32)                  # [hb, P]
    dt = dt_ref[0]                                    # [hb, 1] float32
    state = s_ref[0, 0]                               # [hb, P, N]
    decay = jnp.exp(dt * a_ref[...])                  # [hb, 1]
    b = b_ref[0, 0].astype(jnp.float32)               # [1, N]
    c = c_ref[0, 0].astype(jnp.float32)
    state = decay[:, :, None] * state + \
        (dt * x)[:, :, None] * b[:, None, :]
    out_ref[0, 0] = state
    y = jnp.sum(state * c[:, None, :], axis=-1) + d_ref[...] * x
    y_ref[0] = y.astype(y_ref.dtype)


def ssm_decode_update(pool, layer, rows, x, dt, a, d_skip, b, c,
                      interpret=False):
    """pool `[layers, rows, heads, P, N]` float32; `rows` `[slots]`
    int32 (0 = the null row); x `[slots, heads, P]`; dt `[slots, heads]`
    float32, softplus done; a, d_skip `[heads]` float32 (a negative);
    b, c `[slots, groups, N]`. -> (y `[slots, heads, P]` in x's dtype,
    the pool with the slots' rows of `layer` updated)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    hb = heads // groups
    layer = int(layer)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, groups),
        in_specs=[
            pl.BlockSpec((1, hb, p), lambda s, g, rows: (s, g, 0)),
            pl.BlockSpec((1, hb, 1), lambda s, g, rows: (s, g, 0)),
            pl.BlockSpec((hb, 1), lambda s, g, rows: (g, 0)),
            pl.BlockSpec((hb, 1), lambda s, g, rows: (g, 0)),
            # a row of its own axis: a block's last two dims are whole
            # tiles or the array's own
            pl.BlockSpec((1, 1, 1, n), lambda s, g, rows: (s, g, 0, 0)),
            pl.BlockSpec((1, 1, 1, n), lambda s, g, rows: (s, g, 0, 0)),
            pl.BlockSpec((1, 1, hb, p, n),
                         lambda s, g, rows: (layer, rows[s], g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), lambda s, g, rows: (s, g, 0)),
            pl.BlockSpec((1, 1, hb, p, n),
                         lambda s, g, rows: (layer, rows[s], g, 0, 0)),
        ],
    )
    y, pool = pl.pallas_call(
        _kernel,
        **kernel_name(KERNEL_NAME, rename=False),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # flat inputs: rows, x, dt, a, d, b, c, pool -> the pool is
        # output 1, updated in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(rows.astype(jnp.int32), x, dt.astype(jnp.float32)[..., None],
      a.astype(jnp.float32)[:, None], d_skip.astype(jnp.float32)[:, None],
      b[:, :, None], c[:, :, None], pool)
    return y, pool

