"""Paged KV-cache attention helpers — the op tier under the
continuous-batching generation engine (paddle_tpu/inference/engine.py).

vLLM-PagedAttention-style layout, XLA edition: each layer's KV cache is
a global pool `[num_layers, num_blocks, block_size, heads, head_dim]`
shared by every in-flight request; a per-slot block table maps logical
token positions to pool blocks, so requests of different lengths share
HBM without per-request max-seq allocation. Block 0 is reserved as the
NULL block: idle decode slots and padded prefill positions write there,
and no allocator ever hands it out, so garbage writes can never alias a
live request's context.

`paged_attention_step` is a backend-dispatching seam:

- `"pallas"`: the fused TPU kernel (`ops/pallas/paged_attention.py`) —
  one program per slot walks the block table and streams only the
  blocks at or below that slot's position from HBM into VMEM.
  O(active context) HBM traffic per slot per step. Off-TPU it runs
  through the Pallas interpreter (CPU CI tests it token-exactly).
- `"dense"`: an XLA fallback that online-softmaxes over a
  `lax.fori_loop` bounded by the BATCH's high-water block count
  (`max(positions) // block_size + 1`) — O(high-water) work per step
  instead of the O(max_model_len) full-table gather PR 1 shipped. The
  trip count is a traced scalar, so one compiled program serves every
  context depth (the engine's decode-traces == 1 contract holds).
- `"auto"`: resolves per `resolve_backend` — pallas on TPU at
  serving-scale shapes, dense otherwise (see DESIGN_DECISIONS:
  "Paged-attention backend crossover").

Numerics (both backends): logits and the online-softmax state are
fp32; the PV product accumulates in fp32 (`preferred_element_type`)
and the output is cast to q.dtype ONCE at the end — a bf16 pool loses
only the matmul-input rounding, not the accumulation.

Prefix-cache sharing (PR 6): with the engine's prefix cache on, several
slots' block tables may point at the SAME pool block (a shared system
prompt computed once). Both decode backends tolerate that by
construction — context blocks are only ever READ through the table, and
the step's single write lands at the slot's own feed position, which
the engine guarantees sits in an exclusively-owned block (copy-on-write
promotes a shared block to a private copy via `copy_pool_block` before
any write could touch it). `paged_prefill_chunk` is the incremental
prefill step that makes tail-only prefill possible: it writes one
fixed-shape chunk of prompt KV and attends the chunk's queries over
everything the slot's table covers so far — including read-only shared
prefix blocks another request prefilled.

Speculative decoding (PR 7): `paged_verify_window` is the K-token
verify step's attention — a fixed `[slots, K+1]` window per decode
lane (the feed token plus up to K drafted tokens), per-row base
positions and draft lengths both traced, so ONE compiled program
serves every acceptance outcome. Window row `i` of slot `b` lives at
absolute position `positions[b] + i` and is LIVE iff
`i <= draft_lens[b]`; live rows write their k/v through the slot's
block table (the engine COW-promotes every block the window touches
first), dead rows (draft shorter than K, idle lanes) write the null
block. Each window query attends causally over the slot's context up
to its own position — so the target model scores all K+1 positions in
one pass, and rejected tokens need no cleanup: the engine simply does
not advance the slot position past them, and position-bounded masking
makes their stale KV rows unreachable until overwritten. Dispatches
through the same backend seam (`dense` fori-loop fallback /
`pallas` fused kernel, interpreter-run off-TPU).

Tensor-parallel serving (PR 8): every op here is HEAD-COUNT AGNOSTIC —
the head axis is read from the arrays, never from model config — so
the sharded engine runs the SAME ops per shard inside its shard_map
steps with per-shard pools `[L, blocks, bs, heads/mp, D]` and q/k/v
carrying heads/mp heads. Attention is independent per head, so no
collectives appear at this tier; the block tables and positions arrive
replicated (one logical allocator on the host), which is why a block
id means the same row range on every shard.

Implementation notes:
- functional `.at[].set` / aliased-pool writes chain through the layer
  stack; under the engine's donated compiled step XLA aliases them in
  place, so the pool is updated in HBM, not copied per layer.
- scatter/gather indices are per-slot vectors: one program serves any
  mix of slot positions (shape-stable steady-state decode — no
  per-request recompiles).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.trace.contracts import TraceContract, \
    register_contract
from paddle_tpu.core.device import on_tpu, pallas_interpret
from paddle_tpu.jit import introspect

from .dispatch import apply, as_tensor

__all__ = ["paged_attention_step", "paged_verify_window",
           "paged_prefill_chunk",
           "paged_latent_decode", "paged_latent_prefill_chunk",
           "resolve_latent_backend", "latent_chunk_form",
           "LATENT_PATH_STATS",
           "copy_pool_block", "export_pool_block", "ingest_pool_block",
           "dense_gather_reference",
           "resolve_backend", "PAGED_BACKENDS", "PAGED_PATH_STATS",
           "KV_QUANT_EPS"]

PAGED_BACKENDS = ("auto", "dense", "pallas")

#: Scale floor of the int8 per-block-quantized KV cache. Freshly
#: allocated blocks have their scale rows reset here (PagedKVCache
#: .allocate), so a stale previous owner's scale can never poison a
#: new tenant's quantization grid; a first write whose absmax is below
#: 127*EPS quantizes against the floor (absolute error <= ~1e-6).
KV_QUANT_EPS = 1e-8

# which backend paged_attention_step dispatched to, incremented per
# call (so per TRACE under jit — the engine's compiled decode bumps it
# once per layer at compile time, never per step). Tests read it to
# prove the requested kernel actually engaged; the engine's
# kernel-backend gauge is set separately from resolve_backend() at
# construction. flash_attention.PATH_STATS precedent: never a silent
# fallback.
PAGED_PATH_STATS = {"dense": 0, "pallas": 0}


def reset_paged_path_stats():
    PAGED_PATH_STATS["dense"] = 0
    PAGED_PATH_STATS["pallas"] = 0


def resolve_backend(backend, head_dim, block_size, num_heads):
    """Resolve `auto`/`dense`/`pallas` to the backend a step will run.

    `auto` picks the fused kernel only on TPU and only at the
    geometries the chip's compiler accepts (compiled for a v5e, PR 23;
    `tests/test_chip_compile.py`): head_dim a multiple of the 128-lane
    tile, `num_heads` — the heads ONE program sees, so heads/mp under
    tensor parallel — 4 or a multiple of 8 (the per-slot `[heads, D]`
    rows are sliced out of tiled VMEM blocks; 1, 2, 3 or 12 heads, or
    64-wide heads, are refused as "Slice shape ... must be aligned to
    tiling"), and block_size >= 8. The decode walk gathers
    `pallas.paged_attention.pages_per_step` pages a compute step (8 at
    block 16, PR 27) and compiles at every geometry admitted here —
    4, 8, 16 and 32 heads, bf16 and float32 pools — so none of them
    had to be narrowed to dense; it still issues one copy descriptor a
    page, and a page under 8 rows has never been compiled for the
    chip. Everything else stays dense. Explicit `dense`/`pallas`
    always wins (off-TPU, `pallas` runs the interpreter — the CPU CI
    path)."""
    if backend not in PAGED_BACKENDS:
        raise ValueError(f"backend must be one of {PAGED_BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    if on_tpu() and head_dim % 128 == 0 and block_size >= 8 and \
            (num_heads == 4 or num_heads % 8 == 0):
        return "pallas"
    return "dense"


def paged_attention_step(q, k, v, kpool, vpool, layer, block_tables,
                         positions, scale=None, backend="auto",
                         scales=None, mp_axis=None):
    """One batched decode step against the paged cache, for one layer.

    With `scales` (the int8 engine's `[layers, num_blocks, 2]`
    per-block K/V scale array) the pools are int8: the step
    quantizes-on-write (growing + requantizing the written blocks'
    grids), dequantizes the streamed blocks inside the matmuls, and
    returns a FOUR-tuple `(out, new_kpool, new_vpool, new_scales)`.
    `mp_axis` names the mesh axis whose shards must agree on the
    per-block grid (one lax.pmax per layer); None off-mesh. Without
    `scales` the fp path below is bit-identical to pre-int8 behavior.

    q/k/v: `[slots, 1, heads, head_dim]` — this step's projections.
    kpool/vpool: `[layers, num_blocks, block_size, heads, head_dim]`.
    layer: python int (static) — which layer's pool plane to use.
    block_tables: `[slots, max_blocks]` int32 pool-block ids per slot.
    positions: `[slots]` int32 — the incoming token's absolute position
    per slot (its write address; attention covers positions <= it).
    backend: `auto` | `dense` | `pallas` (see module docstring).

    Writes k/v at `(block_tables[s, pos//bs], pos%bs)` per slot, then
    attends q over the slot's context. Idle slots are encoded by the
    caller as (position 0, all-null table): they write into the null
    block and attend only their own garbage row, and the engine
    discards their token. Decode-only op: gradients are not defined
    through it. Returns `(out [slots,1,heads,head_dim], new_kpool,
    new_vpool)`.
    """
    q, k, v = as_tensor(q), as_tensor(k), as_tensor(v)
    kpool, vpool = as_tensor(kpool), as_tensor(vpool)
    block_tables, positions = as_tensor(block_tables), as_tensor(positions)

    resolved = resolve_backend(backend, head_dim=q.shape[3],
                               block_size=kpool.shape[2],
                               num_heads=q.shape[2])
    PAGED_PATH_STATS[resolved] += 1
    if scales is not None:
        scales = as_tensor(scales)
        if resolved == "pallas":
            from .pallas.paged_attention import paged_decode_attention

            interpret = pallas_interpret()

            def fn(qa, ka, va, kp, vp, sc, bt, pos):
                kp, vp, sc, kq, vq = _quant_write_decode(
                    kp, vp, sc, ka, va, bt, pos, layer, mp_axis)
                out, kp, vp = paged_decode_attention(
                    qa, kq[:, None], vq[:, None], kp, vp, layer, bt,
                    pos, scale=scale, interpret=interpret,
                    kv_scales=sc[layer])
                return out, kp, vp, sc
        else:
            def fn(qa, ka, va, kp, vp, sc, bt, pos):
                return _dense_step_q(qa, ka, va, kp, vp, sc, layer,
                                     bt, pos, scale, mp_axis)

        return apply("paged_attention_step", fn, q, k, v, kpool,
                     vpool, scales, block_tables, positions)
    if resolved == "pallas":
        from .pallas.paged_attention import paged_decode_attention

        interpret = pallas_interpret()

        def fn(qa, ka, va, kp, vp, bt, pos):
            return paged_decode_attention(qa, ka, va, kp, vp, layer,
                                          bt, pos, scale=scale,
                                          interpret=interpret)
    else:
        def fn(qa, ka, va, kp, vp, bt, pos):
            return _dense_step(qa, ka, va, kp, vp, layer, bt, pos,
                               scale)

    return apply("paged_attention_step", fn, q, k, v, kpool, vpool,
                 block_tables, positions)


def _dense_step(qa, ka, va, kp, vp, layer, bt, pos, scale):
    """XLA fallback: per-block online softmax over a fori_loop bounded
    by the batch high-water block count. Work per step is
    O(max(positions)) — the live-context high-water mark — not
    O(max_model_len) like a full-table gather; the traced trip count
    keeps the program shape-stable (no recompiles as context grows)."""
    B = qa.shape[0]
    heads, d = qa.shape[2], qa.shape[3]
    bs, kvh = kp.shape[2], kp.shape[3]
    g = heads // kvh           # query heads a KV head (1: plain heads)
    bid_w = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    kp = kp.at[layer, bid_w, off].set(ka[:, 0])
    vp = vp.at[layer, bid_w, off].set(va[:, 0])
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    # QK inputs stay at the pool dtype (bf16 MXU pass on TPU) with
    # fp32 accumulation — the SAME policy as the pallas kernel, so the
    # two backends see identical logits rounding and the cross-backend
    # token-exact contract holds at bf16, not just fp32
    qf = qa[:, 0].astype(kp.dtype)                 # [B, heads, d]
    hw_blocks = jnp.max(pos) // bs + 1             # traced scalar

    def body(j, carry):
        m, l, acc = carry
        bid = jax.lax.dynamic_index_in_dim(bt, j, axis=1,
                                           keepdims=False)   # [B]
        keys = kp[layer, bid]                      # [B, bs, kvh, d]
        vals = vp[layer, bid]
        # g query heads read one KV head
        logits = jnp.einsum(
            "bngd,bknd->bngk", qf.reshape(B, kvh, g, d), keys,
            preferred_element_type=jnp.float32).reshape(B, heads, bs) * s
        allowed = (j * bs + jnp.arange(bs))[None, :] <= pos[:, None]
        logits = jnp.where(allowed[:, None, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)                # [B, heads, bs] f32
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        # PV accumulates in fp32 (preferred_element_type): probs enter
        # the matmul at the pool dtype (bf16 MXU pass on TPU) but the
        # product never rounds to bf16 mid-accumulation
        pv = jnp.einsum(
            "bngk,bknd->bngd",
            p.astype(vals.dtype).reshape(B, kvh, g, bs), vals,
            preferred_element_type=jnp.float32).reshape(B, heads, d)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((B, heads, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, heads, 1), jnp.float32)
    acc0 = jnp.zeros((B, heads, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, hw_blocks, body, (m0, l0, acc0))
    out = (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype)  # cast ONCE
    return out[:, None], kp, vp


# ---------------------------------------------------------------------------
# int8 per-block-scaled KV quantization (PR 11)
#
# Layout: int8 pools + ONE f32 scale array `[layers, num_blocks, 2]`
# (column 0 = K scale, column 1 = V scale) riding the compiled steps
# alongside the pools. Policy, shared verbatim by every write path so
# cold/warm/chunked runs quantize byte-identically:
#
# - symmetric absmax, clip to +/-127 (-128 unused);
# - per-block scales are MONOTONE: a write whose row absmax exceeds
#   the block's current grid grows the scale and REQUANTIZES the
#   written block's existing rows (round(q * s_old/s_new) — factor
#   <= 1, so no clipping) before the new rows land. Only the written
#   (engine-guaranteed private) blocks are touched, so shared /
#   prefix-cached blocks and their scales are never mutated by a
#   borrower — the COW/prefix sharing story is unchanged;
# - under tensor parallel the pools are head-sharded but the scales
#   are per-(layer, block) GLOBAL: one lax.pmax over the mp axis per
#   layer write folds the shards' absmax, so mp=N quantizes on the
#   same grid as mp=1 (token-identical int8 serving across mesh
#   shapes; the budget lives in GPT_SERVING_COLLECTIVES);
# - dequant is fused into the streamed-block matmuls: logits and PV
#   are computed over the int8 values cast to f32 and scaled ONCE per
#   block (linearity: q . (K*s) == (q . K) * s), fp32 online softmax
#   unchanged. Both backends use the identical operation order so the
#   dense fallback and the Pallas kernel agree token-for-token.
# ---------------------------------------------------------------------------

def _requant_grow(blk, factor):
    """Rescale a written block's existing int8 rows onto a grown grid:
    factor = s_old/s_new <= 1, so round() never needs a clip."""
    return jnp.round(blk.astype(jnp.float32) * factor).astype(jnp.int8)


def _quant_rows(rows, s):
    """Quantize fp rows onto the block grid `s` (broadcast f32)."""
    return jnp.clip(jnp.round(rows.astype(jnp.float32) / s),
                    -127, 127).astype(jnp.int8)


def _fold_amax(amax, mp_axis):
    """Per-block scale candidates must cover ALL heads; under a
    head-sharded mesh each shard sees only its own, so fold with one
    cross-shard max (exact — max is associative/commutative)."""
    if mp_axis is None:
        return amax
    return jax.lax.pmax(amax, mp_axis)


def _quant_write_decode(kp, vp, sc, ka, va, bt, pos, layer, mp_axis):
    """Quant-on-write bookkeeping for one decode row per slot: grow +
    requantize each slot's write block, update its scale row, and
    return the QUANTIZED new rows (not yet written — each backend
    lands them its own way: the dense path scatters, the Pallas
    kernel DMAs). Returns (kp, vp, sc, kq [B,heads,D], vq)."""
    bs = kp.shape[2]
    bid_w = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
    ak = jnp.max(jnp.abs(ka[:, 0].astype(jnp.float32)), axis=(1, 2))
    av = jnp.max(jnp.abs(va[:, 0].astype(jnp.float32)), axis=(1, 2))
    amax = _fold_amax(jnp.stack([ak, av], axis=-1) / 127.0, mp_axis)
    s_old = sc[layer, bid_w]                             # [B, 2]
    s_new = jnp.maximum(jnp.maximum(s_old, amax), KV_QUANT_EPS)
    fac = s_old / s_new
    kp = kp.at[layer, bid_w].set(
        _requant_grow(kp[layer, bid_w], fac[:, 0][:, None, None, None]))
    vp = vp.at[layer, bid_w].set(
        _requant_grow(vp[layer, bid_w], fac[:, 1][:, None, None, None]))
    sc = sc.at[layer, bid_w].set(s_new)
    kq = _quant_rows(ka[:, 0], s_new[:, 0][:, None, None])
    vq = _quant_rows(va[:, 0], s_new[:, 1][:, None, None])
    return kp, vp, sc, kq, vq


def _quant_write_window(kp, vp, sc, ka, va, bt, pos, dlen, layer,
                        mp_axis):
    """Window edition of `_quant_write_decode`: W contiguous write
    positions per slot (the speculative verify window). The window
    spans a STATIC number of candidate table slots, so the grow +
    requantize pass gathers just those blocks. Dead rows (i > dlen)
    are excluded from the absmax and quantize to garbage the engine
    never reads. Returns (kp, vp, sc, kq [B,W,heads,D], vq)."""
    B, W = ka.shape[0], ka.shape[1]
    bs = kp.shape[2]
    maxb = bt.shape[1]
    nb = (W - 1) // bs + 2                 # static candidate count
    wpos = pos[:, None] + jnp.arange(W)[None, :]         # [B, W]
    live = jnp.arange(W)[None, :] <= dlen[:, None]       # [B, W]
    first = pos // bs                                    # [B]
    seg = jnp.clip(wpos // bs - first[:, None], 0, nb - 1)
    # candidates past the table route to the NULL block — a clamped
    # index must never scatter-race the real last block's grid
    cand = first[:, None] + jnp.arange(nb)[None, :]      # [B, nb]
    ti = jnp.minimum(cand, maxb - 1)
    bids = jnp.where(cand <= maxb - 1,
                     jnp.take_along_axis(bt, ti, axis=1), 0)
    rk = jnp.max(jnp.abs(ka.astype(jnp.float32)), axis=(2, 3))
    rv = jnp.max(jnp.abs(va.astype(jnp.float32)), axis=(2, 3))
    zero = jnp.zeros((B, nb), jnp.float32)
    need_k = zero.at[jnp.arange(B)[:, None], seg].max(
        jnp.where(live, rk, 0.0))
    need_v = zero.at[jnp.arange(B)[:, None], seg].max(
        jnp.where(live, rv, 0.0))
    amax = _fold_amax(jnp.stack([need_k, need_v], axis=-1) / 127.0,
                      mp_axis)                           # [B, nb, 2]
    s_old = sc[layer, bids]                              # [B, nb, 2]
    s_new = jnp.maximum(jnp.maximum(s_old, amax), KV_QUANT_EPS)
    fac = s_old / s_new
    kp = kp.at[layer, bids].set(
        _requant_grow(kp[layer, bids],
                      fac[..., 0][..., None, None, None]))
    vp = vp.at[layer, bids].set(
        _requant_grow(vp[layer, bids],
                      fac[..., 1][..., None, None, None]))
    sc = sc.at[layer, bids].set(s_new)
    s_row = jnp.take_along_axis(s_new, seg[..., None], axis=1)  # [B,W,2]
    kq = _quant_rows(ka, s_row[..., 0][..., None, None])
    vq = _quant_rows(va, s_row[..., 1][..., None, None])
    return kp, vp, sc, kq, vq


def _dense_step_q(qa, ka, va, kp, vp, sc, layer, bt, pos, scale,
                  mp_axis):
    """int8 edition of `_dense_step`: quant-on-write, then the SAME
    fori_loop online softmax with dequant fused into the per-block
    matmuls (one scale multiply per streamed block; fp32 logits,
    softmax state and PV accumulation unchanged)."""
    B = qa.shape[0]
    heads, d = qa.shape[2], qa.shape[3]
    bs = kp.shape[2]
    kp, vp, sc, kq, vq = _quant_write_decode(kp, vp, sc, ka, va, bt,
                                             pos, layer, mp_axis)
    bid_w = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    kp = kp.at[layer, bid_w, off].set(kq)
    vp = vp.at[layer, bid_w, off].set(vq)
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = qa[:, 0].astype(jnp.float32)              # [B, heads, d]
    hw_blocks = jnp.max(pos) // bs + 1             # traced scalar

    def body(j, carry):
        m, l, acc = carry
        bid = jax.lax.dynamic_index_in_dim(bt, j, axis=1,
                                           keepdims=False)   # [B]
        keys = kp[layer, bid].astype(jnp.float32)  # [B, bs, heads, d]
        vals = vp[layer, bid].astype(jnp.float32)
        ks, vs = sc[layer, bid, 0], sc[layer, bid, 1]        # [B]
        logits = jnp.einsum("bhd,bkhd->bhk", qf, keys,
                            preferred_element_type=jnp.float32) * s
        logits = logits * ks[:, None, None]        # fused dequant (K)
        allowed = (j * bs + jnp.arange(bs))[None, :] <= pos[:, None]
        logits = jnp.where(allowed[:, None, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)                # [B, heads, bs] f32
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhk,bkhd->bhd", p, vals,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv * vs[:, None, None]

    m0 = jnp.full((B, heads, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, heads, 1), jnp.float32)
    acc0 = jnp.zeros((B, heads, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, hw_blocks, body, (m0, l0, acc0))
    out = (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype)  # cast ONCE
    return out[:, None], kp, vp, sc


def _dense_verify_q(qa, ka, va, kp, vp, sc, layer, bt, pos, dlen,
                    scale, mp_axis):
    """int8 edition of `_dense_verify`: window quant-on-write, then
    the W-query online softmax with per-block fused dequant."""
    B, W = qa.shape[0], qa.shape[1]
    heads, d = qa.shape[2], qa.shape[3]
    bs = kp.shape[2]
    maxb = bt.shape[1]
    kp, vp, sc, kq, vq = _quant_write_window(kp, vp, sc, ka, va, bt,
                                             pos, dlen, layer, mp_axis)
    wpos = pos[:, None] + jnp.arange(W)[None, :]       # [B, W] absolute
    live = jnp.arange(W)[None, :] <= dlen[:, None]     # [B, W]
    bid = jnp.where(
        live, jnp.take_along_axis(bt, jnp.minimum(wpos // bs, maxb - 1),
                                  axis=1), 0)
    off = wpos % bs
    kp = kp.at[layer, bid, off].set(kq)                # [B, W, heads, d]
    vp = vp.at[layer, bid, off].set(vq)
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = qa.astype(jnp.float32)                        # [B, W, heads, d]
    hw_blocks = jnp.max(pos + dlen) // bs + 1          # traced scalar

    def body(j, carry):
        m, l, acc = carry
        bidj = jax.lax.dynamic_index_in_dim(bt, j, axis=1,
                                            keepdims=False)    # [B]
        keys = kp[layer, bidj].astype(jnp.float32)  # [B, bs, heads, d]
        vals = vp[layer, bidj].astype(jnp.float32)
        ks, vs = sc[layer, bidj, 0], sc[layer, bidj, 1]        # [B]
        logits = jnp.einsum("bwhd,bkhd->bhwk", qf, keys,
                            preferred_element_type=jnp.float32) * s
        logits = logits * ks[:, None, None, None]   # fused dequant (K)
        allowed = (j * bs + jnp.arange(bs))[None, None, :] \
            <= wpos[:, :, None]                  # [B, W, bs]
        logits = jnp.where(allowed[:, None, :, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)              # [B, heads, W, bs] f32
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhwk,bkhd->bhwd", p, vals,
                        preferred_element_type=jnp.float32)
        return (m_new, l_new,
                acc * alpha + pv * vs[:, None, None, None])

    m0 = jnp.full((B, heads, W, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, heads, W, 1), jnp.float32)
    acc0 = jnp.zeros((B, heads, W, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, hw_blocks, body, (m0, l0, acc0))
    out = (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype)  # cast ONCE
    return out.transpose(0, 2, 1, 3), kp, vp, sc   # [B, W, heads, d]


def paged_verify_window(q, k, v, kpool, vpool, layer, block_tables,
                        positions, draft_lens, scale=None,
                        backend="auto", scales=None, mp_axis=None):
    """Speculative-verify attention over a fixed `[slots, W]` token
    window (W = K+1), for one layer. With `scales` the int8
    quantized-KV contract of `paged_attention_step` applies (window
    edition) and a four-tuple `(out, kpool, vpool, scales)` returns.

    q/k/v: `[slots, W, heads, head_dim]` — the window's projections
    (feed token at row 0, drafted tokens after it).
    kpool/vpool: `[layers, num_blocks, block_size, heads, head_dim]`.
    layer: python int (static).
    block_tables: `[slots, max_blocks]` int32 pool-block ids per slot.
    positions: `[slots]` int32 — absolute position of window row 0
    (the slot's feed position).
    draft_lens: `[slots]` int32 in `[0, W-1]` — row `i` is live iff
    `i <= draft_lens[s]`; dead rows write the null block and their
    outputs are garbage the engine ignores.

    Live rows write k/v at `(table[(pos+i)//bs], (pos+i)%bs)`; every
    query attends causally over context `<= pos+i`. A draft_len of 0
    degenerates to `paged_attention_step` semantics on row 0 (the
    engine's draftless fallback under pool pressure). Idle lanes are
    (position 0, draft_len 0, all-null table), exactly the decode
    contract. Returns `(out [slots, W, heads, head_dim], new_kpool,
    new_vpool)`."""
    q, k, v = as_tensor(q), as_tensor(k), as_tensor(v)
    kpool, vpool = as_tensor(kpool), as_tensor(vpool)
    block_tables = as_tensor(block_tables)
    positions, draft_lens = as_tensor(positions), as_tensor(draft_lens)

    resolved = resolve_backend(backend, head_dim=q.shape[3],
                               block_size=kpool.shape[2],
                               num_heads=q.shape[2])
    PAGED_PATH_STATS[resolved] += 1
    if scales is not None:
        scales = as_tensor(scales)
        if resolved == "pallas":
            from .pallas.paged_attention import paged_verify_attention

            interpret = pallas_interpret()

            def fn(qa, ka, va, kp, vp, sc, bt, pos, dlen):
                kp, vp, sc, kq, vq = _quant_write_window(
                    kp, vp, sc, ka, va, bt, pos, dlen, layer, mp_axis)
                out, kp, vp = paged_verify_attention(
                    qa, kq, vq, kp, vp, layer, bt, pos, dlen,
                    scale=scale, interpret=interpret,
                    kv_scales=sc[layer])
                return out, kp, vp, sc
        else:
            def fn(qa, ka, va, kp, vp, sc, bt, pos, dlen):
                return _dense_verify_q(qa, ka, va, kp, vp, sc, layer,
                                       bt, pos, dlen, scale, mp_axis)

        return apply("paged_verify_window", fn, q, k, v, kpool,
                     vpool, scales, block_tables, positions,
                     draft_lens)
    if resolved == "pallas":
        from .pallas.paged_attention import paged_verify_attention

        interpret = pallas_interpret()

        def fn(qa, ka, va, kp, vp, bt, pos, dlen):
            return paged_verify_attention(qa, ka, va, kp, vp, layer,
                                          bt, pos, dlen, scale=scale,
                                          interpret=interpret)
    else:
        def fn(qa, ka, va, kp, vp, bt, pos, dlen):
            return _dense_verify(qa, ka, va, kp, vp, layer, bt, pos,
                                 dlen, scale)

    return apply("paged_verify_window", fn, q, k, v, kpool, vpool,
                 block_tables, positions, draft_lens)


def _dense_verify(qa, ka, va, kp, vp, layer, bt, pos, dlen, scale):
    """XLA fallback for the verify window: the `_dense_step` online
    softmax widened to W queries per slot. Work per step is
    O(max(pos + dlen)) — the batch high-water mark including the
    window — with the traced trip count keeping one program for every
    (position, draft-length) mix."""
    B, W = qa.shape[0], qa.shape[1]
    heads, d = qa.shape[2], qa.shape[3]
    bs = kp.shape[2]
    maxb = bt.shape[1]
    wpos = pos[:, None] + jnp.arange(W)[None, :]       # [B, W] absolute
    live = jnp.arange(W)[None, :] <= dlen[:, None]     # [B, W]
    # dead rows (and any clamp overflow) land in the null block 0; the
    # table index is clamped so a dead row past the table stays in
    # bounds before the where() routes it to null
    bid = jnp.where(
        live, jnp.take_along_axis(bt, jnp.minimum(wpos // bs, maxb - 1),
                                  axis=1), 0)
    off = wpos % bs
    kp = kp.at[layer, bid, off].set(ka)                # [B, W, heads, d]
    vp = vp.at[layer, bid, off].set(va)
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    # QK at the pool dtype with fp32 accumulation — the _dense_step
    # policy, so verify and plain decode share one rounding story
    qf = qa.astype(kp.dtype)                           # [B, W, heads, d]
    hw_blocks = jnp.max(pos + dlen) // bs + 1          # traced scalar

    def body(j, carry):
        m, l, acc = carry
        bidj = jax.lax.dynamic_index_in_dim(bt, j, axis=1,
                                            keepdims=False)    # [B]
        keys = kp[layer, bidj]                   # [B, bs, heads, d]
        vals = vp[layer, bidj]
        logits = jnp.einsum("bwhd,bkhd->bhwk", qf, keys,
                            preferred_element_type=jnp.float32) * s
        # causal per window row: key j*bs+k visible to window query w
        # iff it sits at or before that query's absolute position
        allowed = (j * bs + jnp.arange(bs))[None, None, :] \
            <= wpos[:, :, None]                  # [B, W, bs]
        logits = jnp.where(allowed[:, None, :, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)              # [B, heads, W, bs] f32
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhwk,bkhd->bhwd", p.astype(vals.dtype), vals,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((B, heads, W, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, heads, W, 1), jnp.float32)
    acc0 = jnp.zeros((B, heads, W, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, hw_blocks, body, (m0, l0, acc0))
    out = (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype)  # cast ONCE
    return out.transpose(0, 2, 1, 3), kp, vp       # [B, W, heads, d]


def paged_prefill_chunk(q, k, v, kpool, vpool, layer, block_row, start,
                        plen, scale=None, scales=None, mp_axis=None):
    """One chunked-prefill step for ONE slot, for one layer: write the
    chunk's k/v into the pool, then attend the chunk's queries over the
    slot's whole context so far (shared prefix blocks + earlier chunks
    + the chunk itself, causally).

    q/k/v: `[1, C, heads, head_dim]` — this chunk's projections; C is
    the FIXED chunk width, so one compiled program serves every prompt
    length (`start` and `plen` are traced scalars).
    block_row: `[max_blocks]` int32 — the slot's block table.
    start: absolute position of the chunk's first token.
    plen: true prompt length. Chunk positions >= plen (tail padding)
    write to the null block 0 and their query outputs are garbage the
    caller ignores.

    Work is O(chunk x context-so-far) via the same traced-trip-count
    `fori_loop` online softmax as the dense decode step — identical
    numerics policy (fp32 logits/softmax state, fp32 PV accumulation,
    one cast at the end). Reads may cross blocks OTHER slots own (the
    prefix cache seats them read-only); writes never do — the chunk's
    write blocks were allocated exclusively to this slot. Returns
    `(out [1, C, heads, head_dim], new_kpool, new_vpool)`."""
    q, k, v = as_tensor(q), as_tensor(k), as_tensor(v)
    kpool, vpool = as_tensor(kpool), as_tensor(vpool)
    block_row = as_tensor(block_row)
    start, plen = as_tensor(start), as_tensor(plen)

    if scales is not None:
        scales = as_tensor(scales)

        def fnq(qa, ka, va, kp, vp, sc, row, s0, n):
            C = qa.shape[1]
            heads, d = qa.shape[2], qa.shape[3]
            bs = kp.shape[2]
            maxb = row.shape[0]
            nb = (C - 1) // bs + 2         # static candidate blocks
            pos = s0 + jnp.arange(C)                       # absolute [C]
            valid = pos < n
            first = s0 // bs
            seg = jnp.clip(pos // bs - first, 0, nb - 1)   # [C]
            # a chunk may finish a block an EARLIER chunk started, so
            # the grid must grow + requantize. Candidates with no valid
            # rows keep their scale (need 0) and requantize by factor 1
            # — exact.
            # Candidates past the table route to the NULL block so a
            # clamped index can never scatter-race the real last block.
            cand = first + jnp.arange(nb)
            ti = jnp.minimum(cand, maxb - 1)
            bids = jnp.where(cand <= maxb - 1, row[ti], 0)  # [nb]
            rk = jnp.max(jnp.abs(ka[0].astype(jnp.float32)),
                         axis=(1, 2))                      # [C]
            rv = jnp.max(jnp.abs(va[0].astype(jnp.float32)),
                         axis=(1, 2))
            zero = jnp.zeros(nb, jnp.float32)
            need_k = zero.at[seg].max(jnp.where(valid, rk, 0.0))
            need_v = zero.at[seg].max(jnp.where(valid, rv, 0.0))
            amax = _fold_amax(
                jnp.stack([need_k, need_v], axis=-1) / 127.0, mp_axis)
            s_old = sc[layer, bids]                        # [nb, 2]
            s_new = jnp.maximum(jnp.maximum(s_old, amax), KV_QUANT_EPS)
            fac = s_old / s_new
            kp = kp.at[layer, bids].set(
                _requant_grow(kp[layer, bids],
                              fac[:, 0][:, None, None, None]))
            vp = vp.at[layer, bids].set(
                _requant_grow(vp[layer, bids],
                              fac[:, 1][:, None, None, None]))
            sc = sc.at[layer, bids].set(s_new)
            s_row = s_new[seg]                             # [C, 2]
            kq = _quant_rows(ka[0], s_row[:, 0][:, None, None])
            vq = _quant_rows(va[0], s_row[:, 1][:, None, None])
            bid = jnp.where(valid,
                            row[jnp.minimum(pos // bs, maxb - 1)], 0)
            off = pos % bs
            kp = kp.at[layer, bid, off].set(kq)            # [C, heads, d]
            vp = vp.at[layer, bid, off].set(vq)
            s = scale if scale is not None else 1.0 / np.sqrt(d)
            qf = qa[0].astype(jnp.float32)                 # [C, heads, d]
            end = jnp.minimum(s0 + C, n)                   # past-last pos
            hw_blocks = jnp.maximum(end - 1, 0) // bs + 1  # traced

            def body(j, carry):
                m, l, acc = carry
                b = row[j]
                keys = kp[layer, b].astype(jnp.float32)  # [bs, heads, d]
                vals = vp[layer, b].astype(jnp.float32)
                ks, vs = sc[layer, b, 0], sc[layer, b, 1]
                logits = jnp.einsum(
                    "chd,khd->hck", qf, keys,
                    preferred_element_type=jnp.float32) * s
                logits = logits * ks           # fused dequant (K)
                allowed = (j * bs + jnp.arange(bs))[None, :] \
                    <= pos[:, None]
                logits = jnp.where(allowed[None, :, :], logits, -1e30)
                m_new = jnp.maximum(m, jnp.max(logits, axis=-1,
                                               keepdims=True))
                p = jnp.exp(logits - m_new)    # [heads, C, bs]
                alpha = jnp.exp(m - m_new)
                l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                pv = jnp.einsum("hck,khd->hcd", p, vals,
                                preferred_element_type=jnp.float32)
                return m_new, l_new, acc * alpha + pv * vs

            m0 = jnp.full((heads, C, 1), -1e30, jnp.float32)
            l0 = jnp.zeros((heads, C, 1), jnp.float32)
            acc0 = jnp.zeros((heads, C, d), jnp.float32)
            _, l, acc = jax.lax.fori_loop(0, hw_blocks, body,
                                          (m0, l0, acc0))
            out = (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype)
            return out.transpose(1, 0, 2)[None], kp, vp, sc

        return apply("paged_prefill_chunk", fnq, q, k, v, kpool,
                     vpool, scales, block_row, start, plen)

    def fn(qa, ka, va, kp, vp, row, s0, n):
        C = qa.shape[1]
        heads, d = qa.shape[2], qa.shape[3]
        bs, kvh = kp.shape[2], kp.shape[3]
        g = heads // kvh       # query heads a KV head (1: plain heads)
        maxb = row.shape[0]
        pos = s0 + jnp.arange(C)                       # absolute [C]
        valid = pos < n
        bid = jnp.where(valid,
                        row[jnp.minimum(pos // bs, maxb - 1)], 0)
        off = pos % bs
        kp = kp.at[layer, bid, off].set(ka[0])         # [C, kvh, d]
        vp = vp.at[layer, bid, off].set(va[0])
        s = scale if scale is not None else 1.0 / np.sqrt(d)
        # QK at pool dtype, fp32 accumulation — the _dense_step policy
        qf = qa[0].astype(kp.dtype)                    # [C, heads, d]
        end = jnp.minimum(s0 + C, n)                   # past-last pos
        hw_blocks = jnp.maximum(end - 1, 0) // bs + 1  # traced scalar

        def body(j, carry):
            m, l, acc = carry
            b = row[j]
            keys = kp[layer, b]                        # [bs, kvh, d]
            vals = vp[layer, b]
            # g query heads read one KV head
            logits = jnp.einsum(
                "cngd,knd->ngck", qf.reshape(C, kvh, g, d), keys,
                preferred_element_type=jnp.float32
            ).reshape(heads, C, bs) * s
            # causal over absolute positions: key j*bs+k visible to
            # query c iff it is at or before the query's position
            allowed = (j * bs + jnp.arange(bs))[None, :] <= pos[:, None]
            logits = jnp.where(allowed[None, :, :], logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1,
                                           keepdims=True))
            p = jnp.exp(logits - m_new)                # [heads, C, bs]
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.einsum(
                "ngck,knd->ngcd",
                p.astype(vals.dtype).reshape(kvh, g, C, bs), vals,
                preferred_element_type=jnp.float32
            ).reshape(heads, C, d)
            return m_new, l_new, acc * alpha + pv

        m0 = jnp.full((heads, C, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((heads, C, 1), jnp.float32)
        acc0 = jnp.zeros((heads, C, d), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, hw_blocks, body, (m0, l0, acc0))
        out = (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype)
        return out.transpose(1, 0, 2)[None], kp, vp    # [1,C,heads,d]

    return apply("paged_prefill_chunk", fn, q, k, v, kpool, vpool,
                 block_row, start, plen)


# tpu-verify contract for the engine's compiled COW step (the op
# right below): donates both pools (introspect is the shared table),
# runs no collectives at any mp (plain jit over the sharded pools —
# the copy is row-local per shard), and must never bake constants or
# call back to host. Declared here because this module owns the step
# body.
register_contract(TraceContract(
    name="engine_cow_copy",
    declared_at="paddle_tpu/ops/paged_attention.py",
    donate_argnums=introspect.ENGINE_COW_DONATE_ARGNUMS))


def copy_pool_block(kpool, vpool, src, dst, scales=None):
    """Copy one block's KV rows across every layer plane: the engine's
    copy-on-write step. `src`/`dst` may be traced scalars, so the
    engine compiles this ONCE and reuses it for every COW promotion
    (donated pools: XLA rewrites the dst rows in place in HBM). With
    `scales` (int8 pools) the block's per-layer K/V scale rows ride
    along — a COW copy of quantized KV without its grid would
    dequantize on the destination's stale scale. Raw jnp arrays
    in/out — this is a compiled-step body, not a user op."""
    srows = jax.lax.dynamic_index_in_dim(kpool, src, axis=1,
                                         keepdims=False)
    kpool = jax.lax.dynamic_update_index_in_dim(kpool, srows, dst,
                                                axis=1)
    if vpool is not None:      # None: the spec keeps one (latent) pool
        srows = jax.lax.dynamic_index_in_dim(vpool, src, axis=1,
                                             keepdims=False)
        vpool = jax.lax.dynamic_update_index_in_dim(vpool, srows, dst,
                                                    axis=1)
    if scales is None:
        return kpool, vpool
    srow = jax.lax.dynamic_index_in_dim(scales, src, axis=1,
                                        keepdims=False)
    scales = jax.lax.dynamic_update_index_in_dim(scales, srow, dst,
                                                 axis=1)
    return kpool, vpool, scales


def export_pool_block(kpool, vpool, src, scales=None):
    """Gather ONE block's KV rows across every layer plane out of a
    pool: the disaggregated-serving transfer unit's READ half. `src`
    is a traced scalar, so the fleet compiles this once per source
    pool shape and reuses it for every handed-off block. Returns
    (`[layers, block_size, heads, head_dim]` k rows, same-shape v
    rows[, the block's `[layers, 2]` scale rows under int8 pools —
    quantized codes without their grid would dequantize wrong on the
    destination]). Pools are READ, never donated: the source replica
    keeps serving from them. Raw jnp arrays in/out — a compiled-step
    body, not a user op."""
    kb = jax.lax.dynamic_index_in_dim(kpool, src, axis=1,
                                      keepdims=False)
    vb = jax.lax.dynamic_index_in_dim(vpool, src, axis=1,
                                      keepdims=False)
    if scales is None:
        return kb, vb
    srow = jax.lax.dynamic_index_in_dim(scales, src, axis=1,
                                        keepdims=False)
    return kb, vb, srow


def ingest_pool_block(kpool, vpool, kblock, vblock, dst, scales=None,
                      scale_row=None):
    """Scatter one exported block's KV rows into pool block `dst`:
    the transfer unit's WRITE half — a prefill replica's finished
    prompt KV lands in a decode replica's pool through this one
    compiled program (traced `dst`, donated destination pools, so the
    handoff is an in-place HBM write, not a pool rebuild). Under int8
    pools the block's `[layers, 2]` scale rows ride along into the
    destination's scale array. The payload is bit-copied, never
    re-quantized — decode over ingested blocks reads exactly the
    bytes the prefill wrote, which is what makes disaggregated output
    token-identical to a colocated engine. Raw jnp arrays in/out."""
    kpool = jax.lax.dynamic_update_index_in_dim(kpool, kblock, dst,
                                                axis=1)
    vpool = jax.lax.dynamic_update_index_in_dim(vpool, vblock, dst,
                                                axis=1)
    if scales is None:
        return kpool, vpool
    scales = jax.lax.dynamic_update_index_in_dim(scales, scale_row,
                                                 dst, axis=1)
    return kpool, vpool, scales


def dense_gather_reference(kpool, vpool, layer, block_row, length,
                           scales=None):
    """Parity probe: reassemble one slot's first `length` cached k/v
    rows from the pools into dense `[length, heads, head_dim]` arrays
    (host-side, concrete values). Tests compare this against the dense
    fixed-buffer cache the single-request decode path carries — and,
    across two engines, against each other (the pallas-vs-dense pool
    parity probe). With `scales` (int8 pools) the rows come back
    DEQUANTIZED to f32 through the per-block grid."""
    kp = np.asarray(as_tensor(kpool)._array)[layer]
    vp = np.asarray(as_tensor(vpool)._array)[layer]
    row = np.asarray(as_tensor(block_row)._array)
    bs = kp.shape[1]
    pos = np.arange(int(length))
    bids = row[pos // bs]
    if scales is not None:
        # int8 pools: reconstruct the fp rows through the per-block
        # grid, so quantized parity probes compare VALUES, not codes
        sc = np.asarray(as_tensor(scales)._array)[layer]
        return (kp[bids, pos % bs].astype(np.float32)
                * sc[bids, 0][:, None, None],
                vp[bids, pos % bs].astype(np.float32)
                * sc[bids, 1][:, None, None])
    return (kp[bids, pos % bs], vp[bids, pos % bs])


# ---------------------------------------------------------------------------
# latent attention: ONE row a cached token a layer, shared by all heads
#
# The pool is `[layers, num_blocks, block_size, row_width]` (no head axis,
# no V pool: `inference/serving_spec.PagedLatent`). A row is
# `[c ; k_rope]`: `value_width` compressed values that every head both
# scores against (through its absorbed query) and sums (the
# probabilities' product), then the rotated key that only enters the
# scores. Decode runs ABSORBED — `heads` queries of `row_width` values
# against the rows as they lie, two products a page; a prefill chunk
# chooses (`latent_chunk_form`). Raw jnp arrays in and out: these are
# compiled-step bodies.
# ---------------------------------------------------------------------------

#: which form of the latent decode walk was traced (per trace, as
#: `PAGED_PATH_STATS`): never a silent fallback
LATENT_PATH_STATS = {"dense": 0, "pallas": 0}
#: which form a prefill chunk took (per trace): the XLA loop's two, and
#: the fused kernel's (`mla_paged_prefill`, which expands in VMEM)
LATENT_CHUNK_STATS = {"expanded": 0, "absorbed": 0, "pallas_expanded": 0}
#: keys one iteration of the chunk's loop gathers and scores
_LATENT_CHUNK_KEYS = 512


def reset_latent_path_stats():
    for stats in (LATENT_PATH_STATS, LATENT_CHUNK_STATS):
        for k in stats:
            stats[k] = 0


def resolve_latent_backend(backend, row_width, value_width, block_size,
                           num_heads):
    """`auto` takes the fused walk on a TPU at the geometry compiled for
    a described v5e (`tests/test_chip_compile.py`): rows and their value
    part of whole 128-lane tiles (the chip's copy engine moves whole
    tiles: a page of 576-value rows is refused, one of 640 lanes is
    not), pages of whole bf16 tiles (16 rows), query heads a multiple of
    8. An explicit choice always wins (off the chip `pallas`
    runs the interpreter)."""
    if backend not in PAGED_BACKENDS:
        raise ValueError(f"backend must be one of {PAGED_BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    if on_tpu() and value_width % 128 == 0 and row_width % 128 == 0 \
            and block_size % 16 == 0 and num_heads % 8 == 0:
        return "pallas"
    return "dense"


def paged_latent_decode(q, new_rows, pool, layer, block_tables,
                        positions, value_width, scale, backend="auto"):
    """One batched decode step of latent attention, one layer.

    q `[slots, heads, row_width]`: each head's ABSORBED query
    (`[W_UK_h q_nope_h ; q_rope_h]`); new_rows `[slots, row_width]`: this
    token's row, written at `(block_tables[s, pos // bs], pos % bs)`;
    pool `[layers, num_blocks, block_size, row_width]`. Every head scores
    the slot's rows at positions `<= positions[s]` (`q . row * scale`,
    float32 online softmax) and sums their leading `value_width` values.
    -> (`[slots, heads, value_width]` in q's dtype, the pool). Idle lanes
    (position 0, all-null table) write the null block and read their own
    row."""
    resolved = resolve_latent_backend(
        backend, q.shape[2], value_width, pool.shape[2], q.shape[1])
    LATENT_PATH_STATS[resolved] += 1
    if resolved == "pallas":
        from .pallas.paged_attention import mla_paged_decode

        return mla_paged_decode(
            q, new_rows, pool, layer, block_tables, positions,
            value_width, scale, interpret=pallas_interpret())
    return _latent_dense_step(q, new_rows, pool, layer, block_tables,
                              positions, value_width, scale)


def _latent_dense_step(qa, new, pool, layer, bt, pos, value_width, scale):
    """The XLA walk, and the kernel's parity probe: `_dense_step`'s loop
    (online softmax a block, trip count the batch's high-water mark) over
    rows without a head axis."""
    B, heads, _ = qa.shape
    bs = pool.shape[2]
    bid_w = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
    pool = pool.at[layer, bid_w, pos % bs].set(new.astype(pool.dtype))
    qf = qa.astype(pool.dtype)
    hw_blocks = jnp.max(pos) // bs + 1             # traced scalar

    def body(j, carry):
        m, l, acc = carry
        bid = jax.lax.dynamic_index_in_dim(bt, j, axis=1, keepdims=False)
        rows = pool[layer, bid]                    # [B, bs, width]
        logits = jnp.einsum("bhr,bkr->bhk", qf, rows,
                            preferred_element_type=jnp.float32) * scale
        allowed = (j * bs + jnp.arange(bs))[None, :] <= pos[:, None]
        logits = jnp.where(allowed[:, None, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhk,bkv->bhv", p.astype(rows.dtype),
                        rows[..., :value_width],
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((B, heads, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, heads, 1), jnp.float32)
    acc0 = jnp.zeros((B, heads, value_width), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, hw_blocks, body, (m0, l0, acc0))
    return (acc / jnp.maximum(l, 1e-30)).astype(qa.dtype), pool


def latent_chunk_form(chunk_rows, nope_dim, rope_dim, value_dim,
                      value_width):
    """Which form a prefill chunk of `chunk_rows` rows takes, from shapes
    alone. EXPANDING a cached row to every head's key and value costs
    `2 x value_width x heads x (nope_dim + value_dim)` FLOPs once a
    chunk; the ABSORBED form scores and sums the row as it lies, which
    costs `2 x heads x (2 x value_width - nope_dim - value_dim)` more a
    (chunk row, cached row) pair. So expanding pays from
    `value_width x (nope_dim + value_dim) / (2 x value_width - nope_dim
    - value_dim)` rows on: 171 at 512 / 128 / 128. The chunk's width is
    static (every row of it is computed, valid or padding), so the choice
    is too. The fused kernel changes neither side of it: its products
    are the expanded form's, and the 64 lanes by which it pads the
    rotated part to a tile the absorbed form's 640-wide product pads
    too (on a v5e, rows scored as they lie in the same kernel took 3.27
    ms a layer call where expanding took 2.45, at 256 rows)."""
    more_a_pair = 2 * value_width - nope_dim - value_dim
    if more_a_pair <= 0:
        return "absorbed"
    crossover = value_width * (nope_dim + value_dim) / more_a_pair
    return "expanded" if chunk_rows >= crossover else "absorbed"


def paged_latent_prefill_chunk(q_nope, q_rope, new_rows, w_kvb, pool,
                               layer, block_row, start, plen, scale,
                               form=None, backend="auto"):
    """One chunk of ONE slot's prompt, one layer: write the chunk's rows,
    then attend its queries over everything the slot's table covers so
    far (shared prefix blocks, earlier chunks, the chunk itself,
    causally).

    q_nope `[C, heads, nope_dim]`, q_rope `[C, heads, rope_dim]` (rotated),
    new_rows `[C, value_width + rope_dim]`, w_kvb `[value_width, heads,
    nope_dim + value_dim]` (a head's `W_UK` then `W_UV`), pool `[layers,
    num_blocks, block_size, row_width]`, block_row `[max_blocks]`;
    `start`, `plen` traced. Rows at and past `plen` write the null block.
    `form` None: `latent_chunk_form` of the shapes. -> (`[C, heads,
    value_dim]`, the pool). Numerics as `paged_prefill_chunk`: operands
    at the pool's dtype, float32 accumulation and softmax state.

    `backend` as `paged_latent_decode`'s (`resolve_latent_backend`: the
    engine hands down its one resolved choice): under `pallas` an
    expanded chunk attends in the fused kernel (`mla_paged_prefill`:
    the same rows, the same numerics, the scores kept in VMEM), and the
    XLA loop below is the off-chip path and the kernel's parity probe. A
    chunk narrow enough to take the absorbed form runs the loop under
    either backend. `LATENT_CHUNK_STATS` says which was traced."""
    C, heads, dn = q_nope.shape
    dr = q_rope.shape[2]
    rank = w_kvb.shape[0]
    dv = w_kvb.shape[2] - dn
    bs, maxb = pool.shape[2], block_row.shape[0]
    if form is None:
        form = latent_chunk_form(C, dn, dr, dv, rank)
    fused = form == "expanded" and resolve_latent_backend(
        backend, pool.shape[3], rank, bs, heads) == "pallas"
    LATENT_CHUNK_STATS["pallas_expanded" if fused else form] += 1
    dt = pool.dtype
    pos = start + jnp.arange(C)
    valid = pos < plen
    bid = jnp.where(valid, block_row[jnp.minimum(pos // bs, maxb - 1)], 0)
    pool = pool.at[layer, bid, pos % bs].set(new_rows.astype(dt))
    if fused:
        from .pallas.paged_attention import mla_paged_prefill

        return mla_paged_prefill(
            q_nope, q_rope, w_kvb, pool, layer, block_row, start, plen,
            scale, interpret=pallas_interpret()), pool
    group = max(1, min(_LATENT_CHUNK_KEYS // bs, maxb))    # pages a trip
    keys = group * bs
    end = jnp.minimum(start + C, plen)
    trips = (jnp.maximum(end - 1, 0) // bs) // group + 1   # traced
    w_kvb = w_kvb.astype(dt)
    qn, qr = q_nope.astype(dt), q_rope.astype(dt)
    if form == "absorbed":
        # each head's query through its W_UK, once a chunk
        qn = jnp.einsum("chd,rhd->chr", qn, w_kvb[..., :dn],
                        preferred_element_type=jnp.float32).astype(dt)
        width = rank
    else:
        width = dv

    def body(j, carry):
        m, l, acc = carry
        at = j * group + jnp.arange(group)
        rows = pool[layer, block_row[jnp.minimum(at, maxb - 1)]] \
            .reshape(keys, -1)                     # [keys, row_width]
        c, k_rope = rows[:, :rank], rows[:, rank:rank + dr]
        if form == "absorbed":
            k_nope, vals = c, c
            nope = jnp.einsum("chr,kr->hck", qn, k_nope,
                              preferred_element_type=jnp.float32)
        else:
            kv = jnp.einsum("kr,rhd->khd", c, w_kvb,
                            preferred_element_type=jnp.float32).astype(dt)
            k_nope, vals = kv[..., :dn], kv[..., dn:]
            nope = jnp.einsum("chd,khd->hck", qn, k_nope,
                              preferred_element_type=jnp.float32)
        logits = (nope + jnp.einsum(
            "chr,kr->hck", qr, k_rope,
            preferred_element_type=jnp.float32)) * scale
        key_pos = j * keys + jnp.arange(keys)
        allowed = key_pos[None, :] <= pos[:, None]
        logits = jnp.where(allowed[None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)                # [heads, C, keys]
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        if form == "absorbed":
            pv = jnp.einsum("hck,kr->hcr", p.astype(dt), vals,
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("hck,khd->hcd", p.astype(dt), vals,
                            preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((heads, C, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((heads, C, 1), jnp.float32)
    acc0 = jnp.zeros((heads, C, width), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, trips, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)
    if form == "absorbed":
        out = jnp.einsum("hcr,rhd->chd", out.astype(dt), w_kvb[..., dn:],
                         preferred_element_type=jnp.float32)
    else:
        out = out.transpose(1, 0, 2)
    return out.astype(q_nope.dtype), pool
