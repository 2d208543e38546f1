"""Pallas kernel of the expert product: a grouped matmul.

The rows of `x` lie sorted by expert, each expert's group padded to whole
tiles of `tile_rows` rows (`distributed/moe.sorted_dispatch`), so a row
tile belongs to ONE expert: `tile_expert[i]`, a scalar-prefetch operand,
picks the expert's weight block for tile `i`. Tiles at and past
`live_tiles` hold no row of anyone: they skip the product, and point at
the last live tile's last weight block, so nothing is fetched for them.

Where an expert's whole `[K, N]` fits `_EXPERT_BLOCK_BYTES` it is ONE
block: the row tiles of one expert follow one another and name the same
block, which the pipeline then does not fetch again, so every touched
expert's weights cross the chip once a call however many tiles it holds.
A larger expert is cut into column blocks walked inside each row tile,
and crosses once a row tile (`column_tile`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .naming import kernel_name

#: the name the device trace shows, and the benchmark's
#: `moe_experts_roofline` looks for
KERNEL_NAME = "moe_grouped_matmul"

#: VMEM an expert's whole weights may take as one block (two are in
#: flight)
_EXPERT_BLOCK_BYTES = 8 * 1024 * 1024
#: VMEM one column block of a larger expert may take (two are in flight)
_WEIGHT_BLOCK_BYTES = 3 * 1024 * 1024


def column_tile(k, n, itemsize):
    """Columns of a weight block: the whole of `n` where `[k, n]` fits
    `_EXPERT_BLOCK_BYTES`, else its largest divisor that is a multiple of
    128 lanes and keeps `[k, tile]` inside `_WEIGHT_BLOCK_BYTES`."""
    if k * n * itemsize <= _EXPERT_BLOCK_BYTES or n % 128:
        return n
    fits = [t for t in range(128, n, 128)
            if n % t == 0 and k * t * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits) if fits else 128


def _kernel(tile_expert_ref, live_ref, x_ref, w_ref, out_ref, *, square):
    del tile_expert_ref
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < live_ref[0])
    def _live():
        acc = jnp.dot(x_ref[...], w_ref[0],
                      preferred_element_type=jnp.float32)
        if square:                               # relu(.)^2, in float32
            acc = jnp.square(jnp.maximum(acc, 0.0))
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(pl.program_id(0) >= live_ref[0])
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def moe_grouped_matmul(x, w, tile_expert, live_tiles, tile_rows,
                       relu_squared=False, interpret=False):
    """x `[M, K]`, rows sorted by expert and padded to `tile_rows`;
    w `[experts, K, N]`; tile_expert `[M / tile_rows]` int32;
    live_tiles `[1]` int32. -> `[M, N]` in x's dtype: row tile i times
    `w[tile_expert[i]]` (then relu(.)^2 if asked), nought in dead
    tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = w.shape[2]
    tn = column_tile(k, n, w.dtype.itemsize)
    last = n // tn - 1

    def weight_block(i, j, te, live):
        return te[i], 0, jnp.where(i < live[0], j, last)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // tile_rows, n // tn),
        in_specs=[
            pl.BlockSpec((tile_rows, k), lambda i, j, te, live: (i, 0)),
            pl.BlockSpec((1, k, tn), weight_block),
        ],
        out_specs=pl.BlockSpec((tile_rows, tn),
                               lambda i, j, te, live: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, square=relu_squared),
        **kernel_name(KERNEL_NAME, rename=False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the weight block twice (the pipeline's two buffers), and
            # room for the row tiles and the product
            vmem_limit_bytes=2 * k * tn * w.dtype.itemsize + (16 << 20)),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), live_tiles.astype(jnp.int32), x, w)
