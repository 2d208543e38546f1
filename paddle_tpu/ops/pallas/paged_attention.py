"""Fused TPU paged-attention DECODE kernel — the kernel PR 1's
`ops/paged_attention.py` left a seam for.

One batched decode step against the vLLM-style paged KV pool
(`[layers, num_blocks, block_size, heads, head_dim]`, block 0 = null):
the grid runs one program per decode slot, and each program

- writes the incoming token's k/v row into the pool at
  `(block_table[pos // bs], pos % bs)` (fused KV write: the pool is an
  input/output-aliased operand, so the write is an in-place DMA, not a
  functional copy of the pool);
- walks the slot's block table and STREAMS only the blocks at or below
  its position from HBM into a double-buffered VMEM scratch
  (`make_async_copy`, next block's DMA in flight behind the current
  block's compute) — O(active context) HBM traffic per slot per step,
  where the dense fallback pays O(high-water) and the PR-1 gather paid
  O(max_model_len);
- accumulates FlashAttention-style online softmax in fp32 across the
  streamed blocks and normalizes once at the end.

Null-block semantics are preserved: an idle slot (position 0, all-null
table) writes its garbage row into block 0 and attends only position 0
— a one-element softmax, finite by construction — and live slots never
read a trailing-zero table entry because the walk stops at
`pos // block_size`.

`paged_verify_attention` is the speculative-decoding sibling (PR 7):
the same per-slot grid, block-table walk, and fused-write machinery,
widened from one query per slot to a fixed `[W = K+1]` token window.
Each program fires W write DMAs (live window rows through the table,
dead rows to the null block) before the walk, waits for ALL of them
just before the first block the window writes into is streamed (blocks
below the feed position are write-independent and stream concurrently
with the writes), and carries the online-softmax state per window row
— so a verify step's HBM traffic is one context walk amortized over
K+1 scored positions, which is the whole speculative-decoding win.

Interpret mode (`interpret=True`) runs the same kernels through the
Pallas interpreter, which is how CPU CI tests them token-exactly
against the dense path; the op-tier seam (`ops/paged_attention.py`)
runs them interpreted when the platform is `cpu`, and only then.

Tensor-parallel serving (PR 8): the kernels read `heads` from the
operand shapes, never from model config, so the sharded engine invokes
them PER SHARD inside shard_map with heads/mp-head pools and
projections — one grid program per slot per shard, each walking the
same replicated block table over its own pool plane. No cross-shard
communication exists at this level (attention is per-head); the
interpreter path composes with shard_map the same way, which is how
the virtual-mesh CPU CI proves the sharded kernel token-exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.naming import kernel_name

__all__ = ["paged_decode_attention", "paged_verify_attention"]

_NEG_INF = -1e30


def _heads_first(x):
    """`[rows, heads, D]` <-> `[heads, rows, D]`. The pool streams
    blocks rows-major, but Mosaic only lowers a batched matmul whose
    batch (head) axis leads on BOTH operands — the `hd,khd->hk` form,
    head in the middle of the right operand, is refused by the chip's
    compiler though the interpreter accepts it. One in-VMEM swap per
    streamed block buys the canonical `hqd,hkd->hqk` shape."""
    return jnp.swapaxes(x, 0, 1)


def _decode_kernel(bt_ref, pos_ref, q_ref, knew_ref, vnew_ref,
                   kpool_in, vpool_in, o_ref, kpool_ref, vpool_ref,
                   kbuf, vbuf, copy_sems, write_sems, *,
                   layer, block_size, scale):
    """One program per slot. bt_ref [slots, max_blocks] and pos_ref
    [slots] are scalar-prefetch (SMEM) so DMA indices are computable
    before the body runs. kpool_ref/vpool_ref are the ALIASED output
    refs of the full pools (ANY/HBM memory space); kpool_in/vpool_in
    are the same buffers' input refs and are intentionally unused.
    kbuf/vbuf are [2, block_size, heads, D] VMEM double buffers."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    pos = pos_ref[s]
    last_blk = pos // block_size
    nblk = last_blk + 1

    # fused KV write: this token's row lands in the pool before the
    # LAST block of this slot's walk is streamed (that block reads it
    # back); earlier blocks don't depend on it, so their copies run
    # concurrently with the write instead of behind a write round-trip
    wk = pltpu.make_async_copy(
        knew_ref.at[0],
        kpool_ref.at[layer, bt_ref[s, last_blk], pos % block_size],
        write_sems.at[0])
    wv = pltpu.make_async_copy(
        vnew_ref.at[0],
        vpool_ref.at[layer, bt_ref[s, last_blk], pos % block_size],
        write_sems.at[1])
    wk.start()
    wv.start()

    def kv_copies(j, buf):
        bid = bt_ref[s, j]
        return (pltpu.make_async_copy(kpool_ref.at[layer, bid],
                                      kbuf.at[buf], copy_sems.at[0, buf]),
                pltpu.make_async_copy(vpool_ref.at[layer, bid],
                                      vbuf.at[buf], copy_sems.at[1, buf]))

    def start_copies(j, buf):
        ck, cv = kv_copies(j, buf)
        ck.start()
        cv.start()

    @pl.when(last_blk == 0)
    def _first_is_last():           # 1-block walk: copy needs the write
        wk.wait()
        wv.wait()
        start_copies(0, 0)

    @pl.when(last_blk > 0)
    def _first():                   # block 0 is write-independent
        start_copies(0, 0)

    # inputs stay at the pool dtype through the matmuls (bf16 MXU
    # passes on TPU); accumulation is forced fp32 by
    # preferred_element_type — same numerics policy as the dense path
    q = q_ref[0].astype(kbuf.dtype)[:, None]    # [heads, 1, D]
    heads, _, head_dim = q.shape

    def body(j, carry):
        m, l, acc = carry

        @pl.when(j + 1 < nblk)
        def _prefetch():
            @pl.when(j + 1 == last_blk)
            def _writes_land_first():   # exactly once per program
                wk.wait()
                wv.wait()

            start_copies(j + 1, (j + 1) % 2)

        ck, cv = kv_copies(j, j % 2)
        ck.wait()
        cv.wait()
        k = _heads_first(kbuf[j % 2])           # [heads, bs, D]
        v = _heads_first(vbuf[j % 2])
        sc = jnp.einsum("hqd,hkd->hqk", q, k,
                        preferred_element_type=jnp.float32) * scale
        gpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (heads, 1, block_size), 2)
        sc = jnp.where(gpos <= pos, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)                 # [heads, 1, bs] fp32
        alpha = jnp.exp(m - m_new)              # [heads, 1, 1]
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "hqk,hkd->hqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((heads, 1, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((heads, 1, 1), jnp.float32)
    acc0 = jnp.zeros((heads, 1, head_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30))[:, 0].astype(o_ref.dtype)


def _decode_kernel_int8(bt_ref, pos_ref, sref, q_ref, knew_ref,
                        vnew_ref, kpool_in, vpool_in, o_ref, kpool_ref,
                        vpool_ref, kbuf, vbuf, copy_sems, write_sems,
                        *, layer, block_size, scale):
    """int8 edition of `_decode_kernel`: the pools hold int8 codes and
    `sref` is this LAYER's per-block `[num_blocks, 2]` K/V scale plane,
    scalar-prefetched with the block tables. knew/vnew arrive ALREADY
    quantized (the op seam runs quant-on-write: grid grow + requantize
    + scale update happen before the kernel, so the fused write DMA
    below lands the final int8 bytes). Dequant is fused into the
    streamed-block matmuls — int8 codes cast to f32 once in VMEM and
    each block's logits/PV scaled by ITS grid — with the fp32 online
    softmax unchanged; the operation order mirrors `_dense_step_q`
    exactly so both backends agree token-for-token."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    pos = pos_ref[s]
    last_blk = pos // block_size
    nblk = last_blk + 1

    wk = pltpu.make_async_copy(
        knew_ref.at[0],
        kpool_ref.at[layer, bt_ref[s, last_blk], pos % block_size],
        write_sems.at[0])
    wv = pltpu.make_async_copy(
        vnew_ref.at[0],
        vpool_ref.at[layer, bt_ref[s, last_blk], pos % block_size],
        write_sems.at[1])
    wk.start()
    wv.start()

    def kv_copies(j, buf):
        bid = bt_ref[s, j]
        return (pltpu.make_async_copy(kpool_ref.at[layer, bid],
                                      kbuf.at[buf], copy_sems.at[0, buf]),
                pltpu.make_async_copy(vpool_ref.at[layer, bid],
                                      vbuf.at[buf], copy_sems.at[1, buf]))

    def start_copies(j, buf):
        ck, cv = kv_copies(j, buf)
        ck.start()
        cv.start()

    @pl.when(last_blk == 0)
    def _first_is_last():           # 1-block walk: copy needs the write
        wk.wait()
        wv.wait()
        start_copies(0, 0)

    @pl.when(last_blk > 0)
    def _first():                   # block 0 is write-independent
        start_copies(0, 0)

    q = q_ref[0].astype(jnp.float32)[:, None]   # [heads, 1, D]
    heads, _, head_dim = q.shape

    def body(j, carry):
        m, l, acc = carry

        @pl.when(j + 1 < nblk)
        def _prefetch():
            @pl.when(j + 1 == last_blk)
            def _writes_land_first():   # exactly once per program
                wk.wait()
                wv.wait()

            start_copies(j + 1, (j + 1) % 2)

        ck, cv = kv_copies(j, j % 2)
        ck.wait()
        cv.wait()
        bid = bt_ref[s, j]
        ks, vs = sref[bid, 0], sref[bid, 1]     # this block's grid
        k = _heads_first(kbuf[j % 2].astype(jnp.float32))
        v = _heads_first(vbuf[j % 2].astype(jnp.float32))
        sc = jnp.einsum("hqd,hkd->hqk", q, k,
                        preferred_element_type=jnp.float32) * scale
        sc = sc * ks                            # fused dequant (K)
        gpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (heads, 1, block_size), 2)
        sc = jnp.where(gpos <= pos, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)                 # [heads, 1, bs] fp32
        alpha = jnp.exp(m - m_new)              # [heads, 1, 1]
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("hqk,hkd->hqd", p, v,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv * vs

    m0 = jnp.full((heads, 1, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((heads, 1, 1), jnp.float32)
    acc0 = jnp.zeros((heads, 1, head_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30))[:, 0].astype(o_ref.dtype)


def paged_decode_attention(q, knew, vnew, kpool, vpool, layer,
                           block_tables, positions, scale=None,
                           interpret: bool = False, kv_scales=None):
    """Fused paged decode attention over the global pool, one layer.

    q/knew/vnew: `[slots, 1, heads, head_dim]` — this step's
    projections. kpool/vpool: `[layers, num_blocks, block_size, heads,
    head_dim]`. layer: python int (static). block_tables
    `[slots, max_blocks]` int32; positions `[slots]` int32.

    `kv_scales` switches on the int8 path: the pools are int8 codes,
    knew/vnew arrive ALREADY quantized by the op seam, and `kv_scales`
    is this layer's `[num_blocks, 2]` per-block K/V grid, ridden as a
    third scalar-prefetch operand and fused into the streamed-block
    matmuls.

    Returns `(out [slots, 1, heads, head_dim], new_kpool, new_vpool)`
    with the pools updated in place when XLA can alias them (the
    engine's donated decode step) — same contract as the dense
    `paged_attention_step` fallback.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, one, heads, head_dim = q.shape
    assert one == 1, "decode kernel takes one token per slot"
    num_layers, num_blocks, block_size, _, _ = kpool.shape
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)

    q3 = q.reshape(slots, heads, head_dim)
    k3 = knew.reshape(slots, heads, head_dim).astype(kpool.dtype)
    v3 = vnew.reshape(slots, heads, head_dim).astype(vpool.dtype)

    if kv_scales is not None:
        kernel = functools.partial(_decode_kernel_int8,
                                   layer=int(layer),
                                   block_size=block_size, scale=scale)
        prefetch = (block_tables.astype(jnp.int32),
                    positions.astype(jnp.int32),
                    kv_scales.astype(jnp.float32))
    else:
        kernel = functools.partial(_decode_kernel, layer=int(layer),
                                   block_size=block_size, scale=scale)
        prefetch = (block_tables.astype(jnp.int32),
                    positions.astype(jnp.int32))
    row = lambda s, *_: (s, 0, 0)  # noqa: E731 — per-slot [1,heads,D]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # tables, positions[, scales]
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, head_dim), row),
            pl.BlockSpec((1, heads, head_dim), row),
            pl.BlockSpec((1, heads, head_dim), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, head_dim), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_size, heads, head_dim), kpool.dtype),
            pltpu.VMEM((2, block_size, heads, head_dim), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # [k|v, buffer]
            pltpu.SemaphoreType.DMA((2,)),     # [k|v] fused write
        ],
    )
    out, new_kpool, new_vpool = pl.pallas_call(
        kernel,
        # the attribute alone: `name=` would rename the instruction
        # away from `%engine_decode_step.N`, by which traces find it
        **kernel_name("paged_decode_attention", rename=False),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, heads, head_dim), q.dtype),
            jax.ShapeDtypeStruct(kpool.shape, kpool.dtype),
            jax.ShapeDtypeStruct(vpool.shape, vpool.dtype),
        ],
        # flat input order: bt, pos[, scales], q, knew, vnew, kpool,
        # vpool — the pools alias outputs 1/2 so the fused write
        # mutates in place
        input_output_aliases={len(prefetch) + 3: 1,
                              len(prefetch) + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q3, k3, v3, kpool, vpool)
    return out.reshape(slots, 1, heads, head_dim), new_kpool, new_vpool


def _verify_kernel(bt_ref, pos_ref, dlen_ref, q_ref, knew_ref, vnew_ref,
                   kpool_in, vpool_in, o_ref, kpool_ref, vpool_ref,
                   kbuf, vbuf, copy_sems, write_sems, *,
                   layer, block_size, scale, max_blocks):
    """One program per slot, W = K+1 window rows. bt_ref
    [slots, max_blocks], pos_ref [slots] (row-0 absolute position) and
    dlen_ref [slots] (live rows = 0..dlen) are scalar-prefetch (SMEM).
    q/knew/vnew refs are `[1, W, heads, D]` per-slot blocks;
    write_sems is `[2, W]` (one k/v DMA pair per window row)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    pos = pos_ref[s]
    dlen = dlen_ref[s]
    W = q_ref.shape[1]                  # static window width
    first_wb = pos // block_size        # first block the window writes
    last_blk = (pos + dlen) // block_size
    nblk = last_blk + 1

    # fused KV writes, one DMA pair per window row: live rows land
    # through the table (the engine pre-promoted every touched block to
    # private ownership), dead rows (i > dlen) land in the null block 0
    writes = []
    for i in range(W):
        wpos = pos + i
        live = i <= dlen
        bid = jnp.where(
            live,
            bt_ref[s, jnp.minimum(wpos // block_size, max_blocks - 1)],
            0)
        off = wpos % block_size
        wk = pltpu.make_async_copy(knew_ref.at[0, i],
                                   kpool_ref.at[layer, bid, off],
                                   write_sems.at[0, i])
        wv = pltpu.make_async_copy(vnew_ref.at[0, i],
                                   vpool_ref.at[layer, bid, off],
                                   write_sems.at[1, i])
        wk.start()
        wv.start()
        writes.append((wk, wv))

    def wait_writes():
        for wk, wv in writes:
            wk.wait()
            wv.wait()

    def kv_copies(j, buf):
        bid = bt_ref[s, j]
        return (pltpu.make_async_copy(kpool_ref.at[layer, bid],
                                      kbuf.at[buf], copy_sems.at[0, buf]),
                pltpu.make_async_copy(vpool_ref.at[layer, bid],
                                      vbuf.at[buf], copy_sems.at[1, buf]))

    def start_copies(j, buf):
        ck, cv = kv_copies(j, buf)
        ck.start()
        cv.start()

    @pl.when(first_wb == 0)
    def _writes_cover_first():      # window touches block 0: land first
        wait_writes()
        start_copies(0, 0)

    @pl.when(first_wb > 0)
    def _first():                   # block 0 is write-independent
        start_copies(0, 0)

    # inputs stay at the pool dtype through the matmuls; accumulation
    # is forced fp32 — the same policy as decode and the dense paths
    q = _heads_first(q_ref[0].astype(kbuf.dtype))   # [heads, W, D]
    heads, _, head_dim = q.shape

    def body(j, carry):
        m, l, acc = carry

        @pl.when(j + 1 < nblk)
        def _prefetch():
            @pl.when(j + 1 == first_wb)
            def _writes_land_first():   # at most once per program
                wait_writes()

            start_copies(j + 1, (j + 1) % 2)

        ck, cv = kv_copies(j, j % 2)
        ck.wait()
        cv.wait()
        k = _heads_first(kbuf[j % 2])           # [heads, bs, D]
        v = _heads_first(vbuf[j % 2])
        sc = jnp.einsum("hwd,hkd->hwk", q, k,
                        preferred_element_type=jnp.float32) * scale
        # causal per window row over absolute positions
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (W, block_size), 1)
        qpos = pos + jax.lax.broadcasted_iota(
            jnp.int32, (W, block_size), 0)
        sc = jnp.where((kpos <= qpos)[None], sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)                 # [heads, W, bs] fp32
        alpha = jnp.exp(m - m_new)              # [heads, W, 1]
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "hwk,hkd->hwd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((heads, W, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((heads, W, 1), jnp.float32)
    acc0 = jnp.zeros((heads, W, head_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, acc0))
    o_ref[0] = _heads_first(acc / jnp.maximum(l, 1e-30)) \
        .astype(o_ref.dtype)


def _verify_kernel_int8(bt_ref, pos_ref, dlen_ref, sref, q_ref,
                        knew_ref, vnew_ref, kpool_in, vpool_in, o_ref,
                        kpool_ref, vpool_ref, kbuf, vbuf, copy_sems,
                        write_sems, *, layer, block_size, scale,
                        max_blocks):
    """int8 edition of `_verify_kernel`: `sref` is this layer's
    per-block `[num_blocks, 2]` K/V grid (4th scalar-prefetch operand)
    and knew/vnew arrive already quantized by the op seam's window
    quant-on-write. Same write/stream choreography; dequant fused into
    the streamed-block matmuls in `_dense_verify_q`'s exact operation
    order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    pos = pos_ref[s]
    dlen = dlen_ref[s]
    W = q_ref.shape[1]                  # static window width
    first_wb = pos // block_size        # first block the window writes
    last_blk = (pos + dlen) // block_size
    nblk = last_blk + 1

    writes = []
    for i in range(W):
        wpos = pos + i
        live = i <= dlen
        bid = jnp.where(
            live,
            bt_ref[s, jnp.minimum(wpos // block_size, max_blocks - 1)],
            0)
        off = wpos % block_size
        wk = pltpu.make_async_copy(knew_ref.at[0, i],
                                   kpool_ref.at[layer, bid, off],
                                   write_sems.at[0, i])
        wv = pltpu.make_async_copy(vnew_ref.at[0, i],
                                   vpool_ref.at[layer, bid, off],
                                   write_sems.at[1, i])
        wk.start()
        wv.start()
        writes.append((wk, wv))

    def wait_writes():
        for wk, wv in writes:
            wk.wait()
            wv.wait()

    def kv_copies(j, buf):
        bid = bt_ref[s, j]
        return (pltpu.make_async_copy(kpool_ref.at[layer, bid],
                                      kbuf.at[buf], copy_sems.at[0, buf]),
                pltpu.make_async_copy(vpool_ref.at[layer, bid],
                                      vbuf.at[buf], copy_sems.at[1, buf]))

    def start_copies(j, buf):
        ck, cv = kv_copies(j, buf)
        ck.start()
        cv.start()

    @pl.when(first_wb == 0)
    def _writes_cover_first():      # window touches block 0: land first
        wait_writes()
        start_copies(0, 0)

    @pl.when(first_wb > 0)
    def _first():                   # block 0 is write-independent
        start_copies(0, 0)

    q = _heads_first(q_ref[0].astype(jnp.float32))  # [heads, W, D]
    heads, _, head_dim = q.shape

    def body(j, carry):
        m, l, acc = carry

        @pl.when(j + 1 < nblk)
        def _prefetch():
            @pl.when(j + 1 == first_wb)
            def _writes_land_first():   # at most once per program
                wait_writes()

            start_copies(j + 1, (j + 1) % 2)

        ck, cv = kv_copies(j, j % 2)
        ck.wait()
        cv.wait()
        bid = bt_ref[s, j]
        ks, vs = sref[bid, 0], sref[bid, 1]     # this block's grid
        k = _heads_first(kbuf[j % 2].astype(jnp.float32))
        v = _heads_first(vbuf[j % 2].astype(jnp.float32))
        sc = jnp.einsum("hwd,hkd->hwk", q, k,
                        preferred_element_type=jnp.float32) * scale
        sc = sc * ks                            # fused dequant (K)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (W, block_size), 1)
        qpos = pos + jax.lax.broadcasted_iota(
            jnp.int32, (W, block_size), 0)
        sc = jnp.where((kpos <= qpos)[None], sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)                 # [heads, W, bs] fp32
        alpha = jnp.exp(m - m_new)              # [heads, W, 1]
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("hwk,hkd->hwd", p, v,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv * vs

    m0 = jnp.full((heads, W, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((heads, W, 1), jnp.float32)
    acc0 = jnp.zeros((heads, W, head_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, acc0))
    o_ref[0] = _heads_first(acc / jnp.maximum(l, 1e-30)) \
        .astype(o_ref.dtype)


def paged_verify_attention(q, knew, vnew, kpool, vpool, layer,
                           block_tables, positions, draft_lens,
                           scale=None, interpret: bool = False,
                           kv_scales=None):
    """Fused speculative-verify attention over the global pool, one
    layer.

    q/knew/vnew: `[slots, W, heads, head_dim]` — the K-token verify
    window's projections (W = K+1). kpool/vpool:
    `[layers, num_blocks, block_size, heads, head_dim]`. layer: python
    int (static). block_tables `[slots, max_blocks]` int32; positions
    `[slots]` int32 (window row 0's absolute position); draft_lens
    `[slots]` int32 — rows past a slot's draft length write the null
    block and produce garbage the engine discards.

    Returns `(out [slots, W, heads, head_dim], new_kpool, new_vpool)`
    with the pools updated in place when XLA can alias them — the same
    contract as `paged_decode_attention`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, W, heads, head_dim = q.shape
    assert W >= 2, "verify window needs at least one draft row (W >= 2)"
    num_layers, num_blocks, block_size, _, _ = kpool.shape
    max_blocks = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)

    k4 = knew.astype(kpool.dtype)
    v4 = vnew.astype(vpool.dtype)

    if kv_scales is not None:
        kernel = functools.partial(_verify_kernel_int8,
                                   layer=int(layer),
                                   block_size=block_size, scale=scale,
                                   max_blocks=max_blocks)
        prefetch = (block_tables.astype(jnp.int32),
                    positions.astype(jnp.int32),
                    draft_lens.astype(jnp.int32),
                    kv_scales.astype(jnp.float32))
    else:
        kernel = functools.partial(_verify_kernel, layer=int(layer),
                                   block_size=block_size, scale=scale,
                                   max_blocks=max_blocks)
        prefetch = (block_tables.astype(jnp.int32),
                    positions.astype(jnp.int32),
                    draft_lens.astype(jnp.int32))
    row = lambda s, *_: (s, 0, 0, 0)  # noqa: E731 — [1, W, heads, D]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # bt, pos, dlen[, scales]
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, W, heads, head_dim), row),
            pl.BlockSpec((1, W, heads, head_dim), row),
            pl.BlockSpec((1, W, heads, head_dim), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, W, heads, head_dim), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_size, heads, head_dim), kpool.dtype),
            pltpu.VMEM((2, block_size, heads, head_dim), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # [k|v, stream buffer]
            pltpu.SemaphoreType.DMA((2, W)),   # [k|v, window row] write
        ],
    )
    out, new_kpool, new_vpool = pl.pallas_call(
        kernel,
        **kernel_name("paged_verify_attention", rename=False),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, W, heads, head_dim), q.dtype),
            jax.ShapeDtypeStruct(kpool.shape, kpool.dtype),
            jax.ShapeDtypeStruct(vpool.shape, vpool.dtype),
        ],
        # flat input order: bt, pos, dlen[, scales], q, knew, vnew,
        # kpool, vpool — the pools alias outputs 1/2 so writes mutate
        # in place
        input_output_aliases={len(prefetch) + 3: 1,
                              len(prefetch) + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q, k4, v4, kpool, vpool)
    return out, new_kpool, new_vpool
