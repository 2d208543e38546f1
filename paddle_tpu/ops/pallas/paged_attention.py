"""Fused TPU paged-attention DECODE kernel — the kernel PR 1's
`ops/paged_attention.py` left a seam for.

One batched decode step against the vLLM-style paged KV pool
(`[layers, num_blocks, block_size, heads, head_dim]`, block 0 = null):
the grid runs one program per decode slot, and each program

- writes the incoming token's k/v row into the pool at
  `(block_table[pos // bs], pos % bs)` (fused KV write: the pool is an
  input/output-aliased operand, so the write is an in-place DMA, not a
  functional copy of the pool);
- walks the slot's block table and STREAMS only the pages at or below
  its position from HBM into two VMEM step buffers — O(active context)
  HBM traffic per slot per step, where the dense fallback pays
  O(high-water) and the PR-1 gather paid O(max_model_len). A compute
  step gathers `pages_per_step(...)` pages (8 of 16 tokens = 128 keys
  at the GPT-1.3B shape: 1 MB of K and V, one `make_async_copy` a live
  page, none for a page past the position), and the next step's
  copies — in a slot's last step the NEXT SLOT's first — are in flight
  behind it, so 1-2 MB are always on their way and the copy queue
  does not drain between programs;
- scores a step's keys as they lie, `[keys, heads, D]` read as
  `[keys * heads, D]` with no head swap: one matmul of the `[heads, D]`
  query against all rows, a mask that keeps each head's own rows, and
  one PV matmul in which the masked probabilities are zero (PR 27; on
  a v5e the walk with its compute taken out runs 0.41 ms a call at 96
  slots of 344 tokens and the compute with its copies taken out 0.23
  ms, so the copies bound it, at the rate the chip's DMA reaches);
- accumulates FlashAttention-style online softmax in fp32 across the
  steps and normalizes once at the end.

Null-block semantics are preserved: an idle slot (position 0, all-null
table) writes its garbage row into block 0, fetches that one page and
attends only position 0 — a one-element softmax, finite by
construction — and live slots never read a trailing-zero table entry
because the walk stops at `pos // block_size`. The token a step writes
is never read back from the pool: its row goes from `knew`/`vnew` into
the step buffer, so no copy waits for the write.

The int8 decode kernel and the two verify kernels keep the older walk:
one page a compute step, double-buffered, the written rows read back.

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
import math

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.naming import kernel_name

__all__ = ["paged_decode_attention", "paged_verify_attention",
           "pages_per_step", "mla_paged_decode", "latent_pages_per_step",
           "mla_paged_prefill", "latent_prefill_heads",
           "latent_prefill_pages_per_step"]

_NEG_INF = -1e30


def _heads_first(x):
    """`[rows, heads, D]` <-> `[heads, rows, D]`. The pool streams
    blocks rows-major, but Mosaic only lowers a batched matmul whose
    batch (head) axis leads on BOTH operands — the `hd,khd->hk` form,
    head in the middle of the right operand, is refused by the chip's
    compiler though the interpreter accepts it. One in-VMEM swap per
    streamed block buys the canonical `hqd,hkd->hqk` shape (the int8
    and verify kernels; `_decode_kernel` scores the rows as they lie)."""
    return jnp.swapaxes(x, 0, 1)


#: VMEM the decode walk's two K and two V step buffers may take together
_WALK_VMEM_BYTES = 4 * 1024 * 1024
#: keys one compute step scores: a full 128-lane register row per head
_WALK_KEYS = 128


def pages_per_step(block_size, heads, head_dim, dtype):
    """Pages of a slot's table that ONE compute step of `_decode_kernel`
    fetches and scores. A function of what the kernel sees and of
    nothing else: enough pages for `_WALK_KEYS` keys a step, as far as
    two step buffers of K and two of V fit `_WALK_VMEM_BYTES` (8 pages
    = 128 keys = 2 MB of scratch at block 16, 16 heads x 128, bf16).
    The engine publishes the same number as
    `engine_paged_decode_pages_per_step`."""
    page = block_size * heads * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(_WALK_KEYS // block_size,
                      _WALK_VMEM_BYTES // (4 * page)))


def _decode_kernel(bt_ref, pos_ref, q_ref, knew_ref, vnew_ref,
                   kpool_in, vpool_in, o_ref, kpool_ref, vpool_ref,
                   kbuf, vbuf, copy_sems, write_sems, buf_ref, *,
                   layer, block_size, scale):
    """One program per slot. bt_ref [slots, max_blocks] and pos_ref
    [slots] are scalar-prefetch (SMEM) so DMA indices are computable
    before the body runs. kpool_ref/vpool_ref are the ALIASED output
    refs of the full pools (ANY/HBM memory space); kpool_in/vpool_in
    are the same buffers' input refs and are intentionally unused.
    kbuf/vbuf are [2, pages * block_size, heads, D] VMEM step buffers
    and buf_ref [1] (SMEM) says which of the two holds this program's
    first step: the program before started those copies.

    The walk moves `pages` pages a compute step: one copy descriptor a
    live page (none for a page past `pos // block_size`), all of a
    step's K copies on one semaphore and its V copies on another, the
    next step's — or, in the last step, the NEXT SLOT's first step's —
    in flight behind this step's compute, so the copy queue never
    drains between programs.

    A step scores its `pages * block_size` keys as they lie. The tile
    `[keys, heads, D]` is read as `[keys * heads, D]` (no head swap),
    one matmul gives every head's query against every (key, head) row,
    and the mask keeps the entries whose two heads agree; the same
    mask zeroes the probabilities, so the PV matmul over all rows sums
    each head's own keys only. The MXU does `heads` times the needed
    products and is still idle most of the step; the softmax state is
    whole 128-lane rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # positions and table entries are never negative: lax.div/rem, for
    # the floor forms trace and lower several ops each, 24 layers over
    div, rem = jax.lax.div, jax.lax.rem
    s = pl.program_id(0)
    pos = pos_ref[s]
    last_blk = div(pos, block_size)
    step_keys, heads, head_dim = kbuf.shape[1:]
    pages = step_keys // block_size
    nsteps = div(last_blk, pages) + 1
    rows = step_keys * heads

    # fused KV write: in flight for the whole walk, waited for at the
    # end. Nothing reads it back: the last step takes this token's row
    # from knew/vnew (below), so no copy has to queue behind the write
    written = (layer, bt_ref[s, last_blk], rem(pos, block_size))
    wk = pltpu.make_async_copy(knew_ref.at[0], kpool_ref.at[written],
                               write_sems.at[0])
    wv = pltpu.make_async_copy(vnew_ref.at[0], vpool_ref.at[written],
                               write_sems.at[1])
    wk.start()
    wv.start()

    def step_copies(slot, c, buf, start):
        """Start, or wait for, the copies of step `c` of `slot`: its
        live pages only, so the tail costs what it holds."""
        first = c * pages
        live = jnp.minimum(div(pos_ref[slot], block_size) + 1 - first,
                           pages)

        def page(i, _):
            bid = bt_ref[slot, first + i]
            at = pl.ds(pl.multiple_of(i * block_size, block_size),
                       block_size)
            for pool, buffer, sem in ((kpool_ref, kbuf, copy_sems.at[0, buf]),
                                      (vpool_ref, vbuf, copy_sems.at[1, buf])):
                copy = pltpu.make_async_copy(pool.at[layer, bid],
                                             buffer.at[buf, at], sem)
                copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, live, page, None)

    @pl.when(s == 0)
    def _cold():
        # rows no copy ever fills meet probability 0 in the PV matmul:
        # they must be finite, and stay so (only pool pages land here)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        buf_ref[0] = 0
        step_copies(s, 0, 0, start=True)

    buf0 = buf_ref[0]
    # inputs stay at the pool dtype through the matmuls (bf16 MXU
    # passes on TPU); accumulation is forced fp32 by
    # preferred_element_type — same numerics policy as the dense path
    q = q_ref[0].astype(kbuf.dtype)             # [heads, D]
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    own_head = rem(col, heads) == jax.lax.broadcasted_iota(
        jnp.int32, (heads, rows), 0)
    key = div(col, heads)                       # key of the step's tile

    def body(c, carry):
        m, l, acc = carry
        buf = rem(buf0 + c, 2)
        more = c + 1 < nsteps

        # behind this step's compute: this slot's next step or, in its
        # last step, the next slot's first
        @pl.when(jnp.logical_or(more, s + 1 < pl.num_programs(0)))
        def _prefetch():
            step_copies(jnp.where(more, s, s + 1),
                        jnp.where(more, c + 1, 0), 1 - buf, start=True)

        step_copies(s, c, buf, start=False)

        @pl.when(jnp.logical_not(more))
        def _this_token():      # the row in flight to the pool
            at = pl.ds(pos - c * step_keys, 1)
            kbuf[buf, at] = knew_ref[...]
            vbuf[buf, at] = vnew_ref[...]

        k = kbuf[buf].reshape(rows, head_dim)
        v = vbuf[buf].reshape(rows, head_dim)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [heads, rows]
        live = jnp.logical_and(own_head, key <= pos - c * step_keys)
        sc = jnp.where(live, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)                 # [heads, rows] fp32
        alpha = jnp.exp(m - m_new)              # [heads, 1]
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((heads, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    acc0 = jnp.zeros((heads, head_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nsteps, body, (m0, l0, acc0))
    buf_ref[0] = rem(buf0 + nsteps, 2)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    wk.wait()
    wv.wait()


def _decode_kernel_int8(bt_ref, pos_ref, sref, q_ref, knew_ref,
                        vnew_ref, kpool_in, vpool_in, o_ref, kpool_ref,
                        vpool_ref, kbuf, vbuf, copy_sems, write_sems,
                        *, layer, block_size, scale):
    """int8 decode, on the one-page walk `_decode_kernel` had before
    PR 27 (kbuf/vbuf `[2, block_size, heads, D]`, the next page's copy
    behind this page's compute, the written row read back with the
    last page): the pools hold int8 codes and
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
        step_keys, walk_state = block_size, []  # one page a step
    else:
        kernel = functools.partial(_decode_kernel, layer=int(layer),
                                   block_size=block_size, scale=scale)
        prefetch = (block_tables.astype(jnp.int32),
                    positions.astype(jnp.int32))
        step_keys = block_size * pages_per_step(
            block_size, heads, head_dim, kpool.dtype)
        # which step buffer the slot before has filled for this one
        walk_state = [pltpu.SMEM((1,), jnp.int32)]
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
            pltpu.VMEM((2, step_keys, heads, head_dim), kpool.dtype),
            pltpu.VMEM((2, step_keys, heads, head_dim), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # [k|v, buffer]
            pltpu.SemaphoreType.DMA((2,)),     # [k|v] fused write
            *walk_state,
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


# ---------------------------------------------------------------------------
# latent attention (`ops/paged_attention.paged_latent_decode`)
# ---------------------------------------------------------------------------

#: the name the device trace shows, and the benchmark's
#: `mla_decode_roofline` looks for
MLA_KERNEL_NAME = "mla_paged_decode"
#: keys one compute step of the latent walk scores. A row is read ONCE
#: for all the heads, so a step's two products are `heads x keys x
#: (row_width + value_width)` each way: at 128 heads a step of 512 keys
#: is 0.14 GFLOP against 0.59 MB, both about 0.7 us on a v5e
_LATENT_WALK_KEYS = 512


def latent_pages_per_step(block_size, row_width, dtype,
                          keys=_LATENT_WALK_KEYS):
    """Pages of a slot's table that one compute step of
    `_mla_decode_kernel` fetches and scores: `_LATENT_WALK_KEYS` keys, as
    far as its two step buffers fit `_WALK_VMEM_BYTES` (8 pages of 64
    rows x 576 bf16 = 1.2 MB of scratch). A function of shapes alone.
    The prefill kernel asks with its own `keys`."""
    lanes = -(-row_width // 128) * 128             # as VMEM tiles it
    page = block_size * lanes * jnp.dtype(dtype).itemsize
    return max(1, min(keys // block_size,
                      _WALK_VMEM_BYTES // (2 * page)))


def _mla_decode_kernel(bt_ref, pos_ref, q_ref, new_ref, pool_ref, o_ref,
                       buf, copy_sems, buf_ref, *, layer, block_size,
                       value_width, scale):
    """One program a slot; `_decode_kernel`'s walk over ONE pool whose
    rows have no head axis. q_ref `[1, heads, width]` holds every head's
    absorbed query, new_ref `[1, 1, width]` this token's row, buf
    `[2, pages * block_size, width]` the two step buffers; pool_ref is
    the whole pool (HBM), only read.

    The token's own row is not read from the pool: the softmax state
    STARTS from it (`m = q . new`, `l = 1`, `acc = new`'s value part),
    and the walk covers the rows strictly below the position, so a
    masked key weighs exactly 0 and the kernel does not care whether the
    row has reached the pool. (It is written beside the kernel, by XLA in
    place: one row of a packed bf16 tile is half a sublane, which the
    chip's copy engine does not address — "Slice shape along dimension 1
    must be aligned to tiling (2), but is 1".)
    A step scores its keys as they lie: `[heads, width] x [keys,
    width]^T`, then `[heads, keys] x [keys, value_width]` — the rows are
    read once for all the heads, and both products fill the MXU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    div, rem = jax.lax.div, jax.lax.rem
    s = pl.program_id(0)
    pos = pos_ref[s]
    step_keys = buf.shape[1]
    pages = step_keys // block_size

    def blocks_below(slot):     # blocks that hold a row under the position
        return div(pos_ref[slot] + block_size - 1, block_size)

    nsteps = jnp.maximum(div(blocks_below(s) + pages - 1, pages), 1)

    def step_copies(slot, c, b, start):
        first = c * pages
        live = jnp.clip(blocks_below(slot) - first, 0, pages)

        def page(i, _):
            copy = pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[slot, first + i]],
                buf.at[b, pl.ds(pl.multiple_of(i * block_size,
                                               block_size), block_size)],
                copy_sems.at[b])
            copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, live, page, None)

    @pl.when(s == 0)
    def _cold():
        # rows no copy ever fills meet probability 0 in the second
        # product: they must be finite
        buf[...] = jnp.zeros_like(buf)
        buf_ref[0] = 0
        step_copies(s, 0, 0, start=True)

    buf0 = buf_ref[0]
    q = q_ref[0].astype(buf.dtype)                 # [heads, width]
    heads = q.shape[0]
    new = new_ref[0].astype(jnp.float32)           # [1, width]
    key = jax.lax.broadcasted_iota(jnp.int32, (heads, step_keys), 1)

    def body(c, carry):
        m, l, acc = carry
        b = rem(buf0 + c, 2)
        more = c + 1 < nsteps

        @pl.when(jnp.logical_or(more, s + 1 < pl.num_programs(0)))
        def _prefetch():
            step_copies(jnp.where(more, s, s + 1),
                        jnp.where(more, c + 1, 0), 1 - b, start=True)

        step_copies(s, c, b, start=False)
        rows = buf[b]                              # [keys, width]
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        sc = jnp.where(key < pos - c * step_keys, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)                    # [heads, keys] fp32
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :value_width],
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.sum(q.astype(jnp.float32) * new, axis=-1,
                 keepdims=True) * scale            # [heads, 1]
    l0 = jnp.ones((heads, 1), jnp.float32)
    acc0 = jnp.broadcast_to(new[:, :value_width], (heads, value_width))
    _, l, acc = jax.lax.fori_loop(0, nsteps, body, (m0, l0, acc0))
    buf_ref[0] = rem(buf0 + nsteps, 2)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def mla_paged_decode(q, new_rows, pool, layer, block_tables, positions,
                     value_width, scale, interpret: bool = False):
    """Fused latent-attention decode over the one pool, one layer: see
    `ops/paged_attention.paged_latent_decode` for the operands.
    -> (`[slots, heads, value_width]`, the pool with the step's rows
    written: an XLA scatter, in place under the engine's donation)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, width = q.shape
    block_size = pool.shape[2]
    step_keys = block_size * latent_pages_per_step(block_size, width,
                                                   pool.dtype)
    prefetch = (block_tables.astype(jnp.int32),
                positions.astype(jnp.int32))
    new_rows = new_rows.astype(pool.dtype)
    written = jnp.take_along_axis(
        prefetch[0], (prefetch[1] // block_size)[:, None], axis=1)[:, 0]
    pool = pool.at[layer, written, prefetch[1] % block_size].set(new_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec((1, 1, width), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, value_width),
                               lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, step_keys, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),         # a step buffer each
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, layer=int(layer),
                          block_size=block_size,
                          value_width=int(value_width), scale=scale),
        **kernel_name(MLA_KERNEL_NAME, rename=False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, value_width),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q, new_rows[:, None], pool)
    return out, pool


#: the name the device trace shows for the prefill chunk's kernel
#: (`mla_decode_roofline`'s pattern matches none of it)
MLA_PREFILL_KERNEL_NAME = "mla_paged_prefill"
#: (chunk row, key) pairs of one head that one compute step of the prefill
#: kernel scores: 1,024 keys at a chunk of 256 rows (1 MB of float32
#: scores a head). On a v5e a step of 512 keys took 3.02 ms a layer call
#: at 256 rows x 128 heads x 6.1k keys and one of 1,024 keys 2.54: a
#: head's chain of products and softmax pays its latencies once a step
_LATENT_PREFILL_PAIRS = 256 * 1024


def latent_prefill_heads(heads):
    """Heads ONE program of `_mla_prefill_kernel` attends. A step's rows
    are copied once a program, so 8 heads a program fetch a slot's rows
    `heads / 8` times a layer (124 MB for 6k rows at 128 heads: 0.4 ms of
    copies behind 2.5 ms of products), and their weights and softmax
    state (2 MB + 1 MB at a chunk of 256) fit beside the step buffers."""
    return math.gcd(heads, 8)


def latent_prefill_pages_per_step(chunk, block_size, row_width, dtype):
    """Pages one compute step of `_mla_prefill_kernel` fetches and every
    head of the program scores: `_LATENT_PREFILL_PAIRS / chunk` keys (at
    least 128, at most 1,024: a wider chunk takes fewer keys a step, so
    a head's scores stay 1 MB), as far as the two step buffers fit
    `_WALK_VMEM_BYTES`. A function of shapes alone."""
    keys = min(1024, max(128, _LATENT_PREFILL_PAIRS // chunk))
    return latent_pages_per_step(block_size, row_width, dtype, keys)


def _mla_prefill_kernel(row_ref, lim_ref, qn_ref, qr_ref, w_ref, pool_ref,
                        o_ref, buf, wbuf, m_ref, l_ref, acc_ref,
                        copy_sems, w_sem, *, layer, block_size, rank, dn,
                        scale):
    """One program a group of heads; the chunk's own rows are in the pool
    already (XLA's scatter beside the kernel, as the decode step's row).
    row_ref `[max_blocks]` and lim_ref `[start, end]` are scalar
    prefetch; qn_ref `[G, C, dn]` and qr_ref `[G, C, row_width - rank]`
    (the rotated part, in the lanes of the row's tail) this group's
    queries; w_ref `[rank, heads * (dn + dv)]` (HBM) every head's `W_UK`
    then `W_UV` side by side, this group's copied to wbuf `[G, rank,
    dn + dv]` once; pool_ref the whole pool (HBM), only read; buf
    `[2, pages * block_size, row_width]` the two step buffers.

    A step's rows are copied ONCE (the next step's behind this step's
    products) and EXPANDED in VMEM a head at a time: `c x W_h` gives the
    head's keys and values of the step, then `q_nope . k`, `q_rope .
    k_rope` (the row's tail as it lies: its padding lanes meet the
    query's padding, both nought), the float32 online softmax and
    `p x v`. Scores, probabilities and the running state of the G heads
    never leave VMEM. Every row of the chunk is computed; a key is seen
    by the rows at or past its position. Measured on a v5e against the
    rows scored as they lie (absorbed queries, 640- and 512-wide
    products): 2.45 ms a layer call against 3.27.

    Two heads a trip of the (rolled) head loop: the two chains of
    products and softmax are independent, so one's exponentials run
    under the other's products (2.70 ms against 3.02 at 512 keys)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    div, rem = jax.lax.div, jax.lax.rem
    g = pl.program_id(0)
    start, end = lim_ref[0], lim_ref[1]
    group, chunk, _ = qn_ref.shape
    step_keys = buf.shape[1]
    pages = step_keys // block_size
    kvw = wbuf.shape[2]
    blocks = div(jnp.maximum(end - 1, 0), block_size) + 1
    nsteps = div(blocks + pages - 1, pages)

    def w_copy(h):
        return pltpu.make_async_copy(
            w_ref.at[:, pl.ds(pl.multiple_of((g * group + h) * kvw, kvw),
                              kvw)],
            wbuf.at[h], w_sem)

    def step_copies(c, b, start_it):
        first = c * pages
        live = jnp.clip(blocks - first, 0, pages)

        def page(i, _):
            copy = pltpu.make_async_copy(
                pool_ref.at[layer, row_ref[first + i]],
                buf.at[b, pl.ds(pl.multiple_of(i * block_size,
                                               block_size), block_size)],
                copy_sems.at[b])
            copy.start() if start_it else copy.wait()

        jax.lax.fori_loop(0, live, page, None)

    @pl.when(g == 0)
    def _cold():
        # rows no copy ever fills meet probability 0 in the value
        # product: they must be finite
        buf[...] = jnp.zeros_like(buf)

    for h in range(group):
        w_copy(h).start()
    step_copies(0, 0, True)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for h in range(group):
        w_copy(h).wait()

    row_pos = start + jax.lax.broadcasted_iota(
        jnp.int32, (chunk, step_keys), 0)
    key = jax.lax.broadcasted_iota(jnp.int32, (chunk, step_keys), 1)
    nt = (((1,), (1,)), ((), ()))
    together = 1 if group % 2 else 2

    def step(c, _):
        b = rem(c, 2)

        @pl.when(c + 1 < nsteps)
        def _prefetch():
            step_copies(c + 1, 1 - b, True)

        step_copies(c, b, False)
        rows = buf[b]                              # [keys, row_width]
        c_kv, tail = rows[:, :rank], rows[:, rank:]
        seen = key + c * step_keys <= row_pos

        def head(h):
            kv = jnp.dot(c_kv, wbuf[h],
                         preferred_element_type=jnp.float32
                         ).astype(rows.dtype)      # [keys, dn + dv]
            sc = (jax.lax.dot_general(
                qn_ref[h], kv[:, :dn], nt,
                preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr_ref[h], tail, nt,
                    preferred_element_type=jnp.float32)) * scale
            sc = jnp.where(seen, sc, _NEG_INF)     # [C, keys] fp32
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(rows.dtype), kv[:, dn:],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        def heads(i, _):
            for j in range(together):
                head(i * together + j)

        jax.lax.fori_loop(0, group // together, heads, None)

    jax.lax.fori_loop(0, nsteps, step, None)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def mla_paged_prefill(q_nope, q_rope, w_kvb, pool, layer, block_row, start,
                      plen, scale, interpret: bool = False):
    """The latent prefill chunk's attention over the one pool, one layer,
    the chunk's own rows already written to it: see
    `ops/paged_attention.paged_latent_prefill_chunk` for the operands.
    -> `[C, heads, value_dim]` in q_nope's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk, heads, dn = q_nope.shape
    rank, _, kvw = w_kvb.shape
    dv = kvw - dn
    block_size, width = pool.shape[2:]
    dt = pool.dtype
    group = latent_prefill_heads(heads)
    tail = width - rank
    step_keys = block_size * latent_prefill_pages_per_step(
        chunk, block_size, width, dt)
    # heads lead (Mosaic's products take no head axis in the middle); the
    # rotated part in the lanes of the row's tail, its padding nought
    qn = jnp.swapaxes(q_nope.astype(dt), 0, 1)
    qr = jnp.swapaxes(jnp.pad(
        q_rope.astype(dt),
        ((0, 0), (0, 0), (0, tail - q_rope.shape[2]))), 0, 1)
    lims = jnp.stack([start, jnp.minimum(start + chunk, plen)]
                     ).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(heads // group,),
        in_specs=[
            pl.BlockSpec((group, chunk, dn), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec((group, chunk, tail), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((group, chunk, dv),
                               lambda g, *_: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, step_keys, width), dt),
            pltpu.VMEM((group, rank, kvw), dt),
            pltpu.VMEM((group, chunk, 1), jnp.float32),
            pltpu.VMEM((group, chunk, 1), jnp.float32),
            pltpu.VMEM((group, chunk, dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),         # a step buffer each
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, layer=int(layer),
                          block_size=block_size, rank=rank, dn=dn,
                          scale=scale),
        **kernel_name(MLA_PREFILL_KERNEL_NAME, rename=False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, chunk, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_row.astype(jnp.int32), lims, qn, qr,
      w_kvb.astype(dt).reshape(rank, heads * kvw), pool)
    return jnp.swapaxes(out, 0, 1)
