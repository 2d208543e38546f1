"""Pallas kernel of power retention's decode step: one token a slot.

    S <- g S + phi(k) v^T        S: [D_run, d] float32 a KV head a slot
    num_i = phi(q_i)^T S         for the r query heads of the KV head

The state pool is `[layers, rows, kv_heads, D_run, d]` float32 and stays
in HBM; a program of the grid takes one slot's KV head (its whole `D_run
x d` block), and the pool's block is chosen by the slot's ROW, a
scalar-prefetch operand: idle and prefilling lanes ride row 0, the null
row (one block of it for all their heads, so it is fetched once, and
they compute nothing). The pool aliases its output, so a step moves every
live slot's state through the chip once (read and write): the kernel is
bound by those bytes, 1.5 FLOPs a byte.

`phi` is never read from memory: it is built from the d values. In the
tiled symmetric form (`ops/retention.py`) the eight state rows `(a, b, i,
0..7)` are one vreg, `w x[8a + i] * x[8b + 0..7]` down its sublanes and
`v` along its lanes. With `X[c, :] = x[c]` (x down the sublanes, the same
in every lane: a transpose of x broadcast over rows), that is row `8a +
i` of X, spread over the sublanes, times rows `8b ..` of X.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .naming import kernel_name

#: the name the device trace shows (`kernel_metadata`), and the
#: benchmark's `retention_decode_roofline` looks for
KERNEL_NAME = "power_retention_decode"
TILE = 8
_SQRT2 = 2.0 ** 0.5


def _kernel(rows_ref, q_ref, k_ref, v_ref, g_ref, s_ref, y_ref, out_ref,
            x_ref, *, d, r):
    from jax.experimental import pallas as pl

    n = d // TILE
    f32 = jnp.float32

    @pl.when(rows_ref[pl.program_id(0)] == 0)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(rows_ref[pl.program_id(0)] != 0)
    def _live():
        # x_ref[0] is k, x_ref[1 + i] query head i: the d values down
        # the sublanes, the same in every lane
        x_ref[0] = jnp.broadcast_to(k_ref[0, 0], (d, d)).T
        for i in range(r):
            x_ref[1 + i] = jnp.broadcast_to(q_ref[0, 0, i:i + 1],
                                            (d, d)).T
        v = v_ref[0, 0]                          # [8, d]: v in every row
        g = g_ref[0, 0]                          # [8, d]: the gate
        acc = tuple(jnp.zeros((TILE, d), f32) for _ in range(r))
        for a in range(n):
            first = (a * n - a * (a - 1) // 2) * TILE * TILE
            xa = [x_ref[h, a * TILE:(a + 1) * TILE] for h in range(1 + r)]

            def pair(b, acc, a=a, first=first, xa=xa):
                w = jnp.where(b == a, 1.0, _SQRT2).astype(f32)
                at = pl.multiple_of(b * TILE, TILE)
                xb = [x_ref[h, pl.ds(at, TILE)] * w for h in range(1 + r)]
                kv = xb[0] * v                   # w k[8b + j] v[lane]
                row = first + (b - a) * TILE * TILE
                part = [jnp.zeros((TILE, d), f32) for _ in range(r)]
                for i in range(TILE):
                    at = pl.multiple_of(row + i * TILE, TILE)
                    s = g * s_ref[0, 0, 0, pl.ds(at, TILE)] \
                        + xa[0][i:i + 1] * kv
                    out_ref[0, 0, 0, pl.ds(at, TILE)] = s
                    for h in range(r):
                        part[h] = part[h] + xa[1 + h][i:i + 1] * s
                return tuple(acc[h] + xb[1 + h] * part[h]
                             for h in range(r))

            acc = jax.lax.fori_loop(a, n, pair, acc)
        for h in range(r):
            y_ref[0, 0, h:h + 1] = jnp.sum(acc[h], axis=0, keepdims=True)


def retention_decode_update(pool, layer, rows, q, k, v, g,
                            interpret=False):
    """pool `[layers, rows, kv_heads, D_run, d]` float32; `rows` `[slots]`
    int32 (0 = the null row); q `[slots, kv_heads, r, d]` float32,
    SCALED; k, v `[slots, kv_heads, d]` float32; g `[slots, kv_heads]`
    float32 (the gate, not its log). -> (`phi(q)^T S` `[slots, kv_heads,
    r, d]` float32, the pool with the slots' rows of `layer` updated)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, r, d = q.shape
    d_run = pool.shape[-2]
    layer = int(layer)
    f32 = jnp.float32
    over_rows = (slots, heads, TILE, d)

    def state_block(s, h, rows):
        # an idle lane's heads all ride ONE block of the null row: the
        # same block twice running is fetched once
        return (layer, rows[s], jnp.where(rows[s] == 0, 0, h), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, heads),
        in_specs=[
            pl.BlockSpec((1, 1, r, d), lambda s, h, rows: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda s, h, rows: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, TILE, d), lambda s, h, rows: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, TILE, d), lambda s, h, rows: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, d_run, d), state_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, r, d), lambda s, h, rows: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, d_run, d), state_block),
        ],
        scratch_shapes=[pltpu.VMEM((1 + r, d, d), f32)],
    )
    block = d_run * d * 4
    y, pool = pl.pallas_call(
        functools.partial(_kernel, d=d, r=r),
        **kernel_name(KERNEL_NAME, rename=False),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # flat inputs: rows, q, k, v, g, pool -> the pool is output 1,
        # updated in place
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state's block in and out, each twice (the pipeline's
            # two buffers), and room for the rest
            vmem_limit_bytes=4 * block + (8 << 20)),
        interpret=interpret,
    )(rows.astype(jnp.int32), q.astype(f32), k.astype(f32)[:, :, None],
      jnp.broadcast_to(v.astype(f32)[:, :, None], over_rows),
      jnp.broadcast_to(g.astype(f32)[:, :, None, None], over_rows), pool)
    return y, pool
