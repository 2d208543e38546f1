"""Pallas kernels of power retention: the decode step (one token a slot,
`retention_decode_update`) and a prefill chunk (one slot's rows,
`retention_chunk`, at the end of this file).

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
import numpy as np

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


# -- a prefill chunk of one slot ----------------------------------------------
#
# A program a (KV head, sub-chunk), the sub-chunks in order: the head's
# state `[D_run, d]` and normaliser stay in VMEM across them (the same
# block index: fetched once, written once). `phi` never reaches HBM: a
# step of 8 tile pairs builds `phi(x)^T` `[512, N]` in VMEM from x `[d,
# N]` down the sublanes (the decode kernel's trick, no lane moves), and
# the product contracts over those 512 state rows — `phi(q)^T S` for the
# r readers' rows at once (N = r C), and the update `phi(k)^T (tail v)`.
# The normaliser is read and updated as the `[d, d]` matrix of the
# products (`phi(q) . z = q^T (W Z) q`, `Z += W (tail k)^T k`): one
# small product each, where `z` as a column would double the products'
# width. Float32 operands, products at `highest`, as the XLA form.
# The steps are a `fori_loop`, a pair's tiles read from two scalar
# tables: unrolled, the kernel took 13.5 s to trace and lower for the 8
# layers of the cell's chunk program, paid in every process's set-up
# before the compile cache is asked (`setup_s` +16%); rolled, 2.6 s.
# Microbenchmarked at the cell's geometry (my chip runs, PR 36): 1.15 ms
# a layer call rolled, 1.22 unrolled, against 1.89 for the XLA form; 2,
# 4 or 8 pairs a step, the state (not `phi`) as the transposed operand,
# two `phi` buffers, or rows loaded from the ref: all 1.22-1.23 ms
# unrolled, so the plain form stays.

#: the name `breakdown.device_ops` shows for the chunk's kernel
CHUNK_KERNEL_NAME = "power_retention_chunk"
#: tile pairs a product: 512 rows of the state
STEP_PAIRS = 8


def _phi_rows(x_ref, pair_a, pair_b, first, count, out_ref, width):
    """`phi(x)^T` of the `count` pairs from `first` on into out_ref `[64
    count, N]`: x_ref `[d, N]` holds the d values down the sublanes, so a
    tile `(a, b, i)` of eight state rows is row `8a + i` spread over the
    sublanes times rows `8b ..` — no lane moves. The pairs' tiles are
    read from the scalar tables `pair_a`, `pair_b` (the state's row
    order), so a loop over the steps traces one step."""
    from jax.experimental import pallas as pl

    for j in range(count):
        a, b = pair_a[first + j], pair_b[first + j]
        xa = x_ref[pl.ds(pl.multiple_of(a * TILE, TILE), TILE), :width]
        xb = x_ref[pl.ds(pl.multiple_of(b * TILE, TILE), TILE), :width] \
            * jnp.where(a == b, 1.0, _SQRT2).astype(jnp.float32)
        for i in range(TILE):
            at = (j * TILE + i) * TILE
            out_ref[at:at + TILE, :width] = xa[i:i + 1] * xb


def _pair_weights_matrix(d):
    """`[d, d]`: w_ab at `(8a + i, 8b + j)` where `a <= b` (1 on the
    diagonal tiles, sqrt 2 above), 0 below."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) // TILE
    cols = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1) // TILE
    return jnp.where(cols > rows, _SQRT2,
                     jnp.where(cols == rows, 1.0, 0.0)).astype(jnp.float32)


def _chunk_kernel(pair_a, pair_b, q_ref, qt_ref, k_ref, kt_ref, v_ref,
                  dec_ref, gq_ref, tail_ref, s_ref, z_ref, y_ref, s_out,
                  z_out, xq_ref, phi_ref, acc_ref, *, r, eps):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    c, d = q_ref.shape[2], q_ref.shape[3]
    steps, rest = divmod(pair_a.shape[0], STEP_PAIRS)
    leading = (((0,), (0,)), ((), ()))         # contract both row axes

    def over_steps(step):
        """`step(first pair, pairs)` for every step of the state's rows."""
        if steps:
            jax.lax.fori_loop(0, steps, lambda g, _: step(
                g * STEP_PAIRS, STEP_PAIRS), None)
        if rest:
            step(steps * STEP_PAIRS, rest)

    def state_rows(first, count):
        at = pl.multiple_of(first * TILE * TILE, TILE * TILE)
        return s_out.at[0, pl.ds(at, count * TILE * TILE)]

    @pl.when(pl.program_id(1) == 0)
    def _load():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    w = _pair_weights_matrix(d)
    k, v, dec, gq = k_ref[0], v_ref[0], dec_ref[0], gq_ref[0]
    for i in range(r):
        xq_ref[:, i * c:(i + 1) * c] = qt_ref[0, i]

    # the carried part, phi(q)^T S, for the r readers' rows at once
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def read(first, count):
        _phi_rows(xq_ref, pair_a, pair_b, first, count, phi_ref, r * c)
        acc_ref[...] += jax.lax.dot_general(
            phi_ref[:count * TILE * TILE], state_rows(first, count)[...],
            leading, precision=hi, preferred_element_type=f32)

    over_steps(read)
    zw = w * z_out[0]
    for i in range(r):
        q = q_ref[0, i]                                   # [C, d]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 precision=hi, preferred_element_type=f32)
        a = sc * sc * dec
        num = jnp.dot(a, v, precision=hi, preferred_element_type=f32) \
            + gq * acc_ref[i * c:(i + 1) * c]
        qz = jnp.dot(q, zw, precision=hi, preferred_element_type=f32)
        den = jnp.sum(a, axis=1, keepdims=True) \
            + gq * jnp.sum(q * qz, axis=1, keepdims=True)
        y_ref[0, i] = num / (den + eps)

    # the update, from the state this sub-chunk read
    last = gq[c - 1:c]                                    # [1, d]
    tail = tail_ref[0]                                    # [C, 1]
    vt = v * tail

    def update(first, count):
        _phi_rows(kt_ref.at[0], pair_a, pair_b, first, count, phi_ref, c)
        rows = state_rows(first, count)
        rows[...] = last * rows[...] + jnp.dot(
            phi_ref[:count * TILE * TILE, :c], vt, precision=hi,
            preferred_element_type=f32)

    over_steps(update)
    z_out[0] = last * z_out[0] + w * jax.lax.dot_general(
        k * tail, k, leading, precision=hi, preferred_element_type=f32)


def retention_chunk(q, qt, k, kt, v, dec, gq, tail, state, zmat, eps,
                    interpret=False):
    """q `[h, r, T, d]` float32 SCALED, qt its `[h, r, d, T]`; k, v `[h,
    T, d]`, kt `[h, d, T]`; dec `[h, T, C]` the gated causal weights
    inside a sub-chunk of C rows; gq `[h, T, d]` a row's decay since the
    sub-chunk began, `exp(la)`, over the lanes (its last row is the
    sub-chunk's whole decay); tail `[h, T, 1]` a key's decay to the
    sub-chunk's end (0 on padding); state `[h, D_run, d]`, zmat `[h, d,
    d]` the normaliser as a matrix (`ops/retention._norm_to_matrix`).
    All float32. -> (y `[h, r, T, d]`, state, zmat), the last two in
    place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, r, t, d = q.shape
    c = dec.shape[-1]
    d_run = state.shape[1]
    f32 = jnp.float32
    step_rows = STEP_PAIRS * TILE * TILE
    block = d_run * d * 4
    n = d // TILE
    pair_a, pair_b = np.triu_indices(n)              # the state's row order
    def per_sub(shape, at):
        """A block of each (KV head, sub-chunk); the tables unused."""
        return pl.BlockSpec(shape, lambda hh, s, *_: at(hh, s))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, t // c),
        in_specs=[
            per_sub((1, r, c, d), lambda hh, s: (hh, 0, s, 0)),
            per_sub((1, r, d, c), lambda hh, s: (hh, 0, 0, s)),
            per_sub((1, c, d), lambda hh, s: (hh, s, 0)),
            per_sub((1, d, c), lambda hh, s: (hh, 0, s)),
            per_sub((1, c, d), lambda hh, s: (hh, s, 0)),
            per_sub((1, c, c), lambda hh, s: (hh, s, 0)),
            per_sub((1, c, d), lambda hh, s: (hh, s, 0)),
            per_sub((1, c, 1), lambda hh, s: (hh, s, 0)),
            per_sub((1, d_run, d), lambda hh, s: (hh, 0, 0)),
            per_sub((1, d, d), lambda hh, s: (hh, 0, 0)),
        ],
        out_specs=[
            per_sub((1, r, c, d), lambda hh, s: (hh, 0, s, 0)),
            per_sub((1, d_run, d), lambda hh, s: (hh, 0, 0)),
            per_sub((1, d, d), lambda hh, s: (hh, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((d, r * c), f32),
                        pltpu.VMEM((step_rows, r * c), f32),
                        pltpu.VMEM((r * c, d), f32)],
    )
    y, state, zmat = pl.pallas_call(
        functools.partial(_chunk_kernel, r=r, eps=eps),
        **kernel_name(CHUNK_KERNEL_NAME, rename=False),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(zmat.shape, f32)],
        # flat inputs: the two tables, then q .. zmat: the state and the
        # normaliser are outputs 1 and 2, updated in place
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the state's block in and out, each twice (the pipeline's
            # two buffers), `phi`'s step and room for the rest
            vmem_limit_bytes=4 * block + (24 << 20)),
        interpret=interpret,
    )(jnp.asarray(pair_a, jnp.int32), jnp.asarray(pair_b, jnp.int32),
      q, qt, k, kt, v, dec, gq, tail, state, zmat)
    return y, state, zmat
