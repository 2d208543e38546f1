"""GPT — the flagship decoder-only LM (the BASELINE.md GPT-1.3B hybrid-
parallel config; analog of the PaddleNLP GPT the reference's fleet tests
train, e.g. hybrid_parallel_pp_transformer.py's tiny transformer).

TPU-native design choices:
- pre-norm residual blocks, bf16-friendly layer norms (fp32 stats);
- fused QKV projection (one MXU matmul instead of three);
- causal attention via ops.scaled_dot_product_attention, which routes to
  the Pallas flash kernel for long sequences;
- weights created through tensor-parallel-aware layers from
  distributed.mp_layers when a model-parallel degree > 1 is configured —
  under SPMD these annotate shardings instead of splitting buffers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.inference.serving_spec import PagedKV, ServingSpec, \
    StepOut
from paddle_tpu.jit import introspect
from paddle_tpu.ops import manipulation as mp


def _mp_degree():
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group

    try:
        return get_hybrid_communicate_group().axis_size("mp")
    except Exception:
        return 1


# Collective budget of ONE tensor-parallel serving step of this model.
# The numbers live in `jit.introspect.GPT_SERVING_AXIS_BUDGET` — ONE
# per-(mesh axis, kind) table carrying counts AND payload-byte bounds,
# consumed by tpu-verify TPU104 (counts) and tpu-shard TPU301/304/305
# (axes + bytes) — and this module keeps the canonical alias because
# the helpers right below (_mp_all_gather / _vocab_parallel_embed) are
# the only places serving collectives come from. The engine's step
# contracts reference it lazily as
# "paddle_tpu.models.gpt:GPT_SERVING_COLLECTIVES".
GPT_SERVING_COLLECTIVES = introspect.GPT_SERVING_AXIS_BUDGET


def _mp_all_gather(t, mp_axis):
    """Concatenate a column-parallel activation's shards along the LAST
    axis inside a shard_map body (tiled all-gather; mesh axis-index
    order IS the engine's head/column order, so the concat reassembles
    the logical layout exactly). Gathering is pure data movement — the
    result is bit-identical to the unsharded activation, which is what
    keeps tensor-parallel serving token-exact vs mp=1."""
    import jax

    from paddle_tpu.ops.dispatch import apply

    def fn(a):
        return jax.lax.all_gather(a, mp_axis, axis=a.ndim - 1,
                                  tiled=True)

    return apply("mp_all_gather", fn, t)


def _vocab_parallel_embed(weight, token_ids, mp_axis):
    """Embedding lookup over a vocab-sharded table inside a shard_map
    body (VocabParallelEmbedding, inference edition): each shard
    gathers the rows it owns (out-of-range ids masked to zero rows),
    one psum assembles the full embedding. Every id hits exactly ONE
    shard, so the psum adds exact zeros — bit-identical to the
    unsharded gather."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.dispatch import apply

    def fn(w, ids):
        r = jax.lax.axis_index(mp_axis)
        vl = w.shape[0]
        loc = ids.astype(jnp.int32) - r * vl
        inb = (loc >= 0) & (loc < vl)
        rows = w[jnp.clip(loc, 0, vl - 1)]
        rows = jnp.where(inb[..., None], rows, jnp.zeros((), w.dtype))
        return jax.lax.psum(rows, mp_axis)

    return apply("vocab_parallel_embed", fn, weight, token_ids)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int = None
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_bias: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @staticmethod
    def gpt_small():
        return GPTConfig(hidden_size=768, num_layers=12, num_heads=12)

    @staticmethod
    def gpt_medium():
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16)

    @staticmethod
    def gpt_1p3b():
        # the BASELINE GPT-3 1.3B config
        return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                         max_seq_len=2048)

    @staticmethod
    def tiny(vocab=128, hidden=64, layers=2, heads=4, seq=64):
        return GPTConfig(vocab_size=vocab, hidden_size=hidden,
                         num_layers=layers, num_heads=heads, max_seq_len=seq)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        init = nn.initializer.Normal(0.0, config.initializer_range)
        bias_attr = None if config.use_bias else False
        # fused qkv: one [h, 3h] matmul
        self.qkv_proj = nn.Linear(config.hidden_size, 3 * config.hidden_size,
                                  weight_attr=nn.ParamAttr(initializer=init),
                                  bias_attr=bias_attr)
        self.out_proj = nn.Linear(config.hidden_size, config.hidden_size,
                                  weight_attr=nn.ParamAttr(initializer=init),
                                  bias_attr=bias_attr)
        self.dropout = config.dropout
        # Megatron tensor-parallel shardings when an mp axis is active:
        # qkv column-parallel, out row-parallel (mp_layers.py pattern)
        from jax.sharding import PartitionSpec as P

        if _mp_degree() > 1 and config.hidden_size % _mp_degree() == 0:
            self.qkv_proj.weight.dist_spec = P(None, "mp")
            if self.qkv_proj.bias is not None:
                self.qkv_proj.bias.dist_spec = P("mp")
            self.out_proj.weight.dist_spec = P("mp", None)

    def forward(self, x, cache=None):
        B, S, H = x.shape
        qkv = self.qkv_proj(x)  # [B,S,3H]
        qkv = mp.reshape(qkv, [B, S, 3, self.num_heads, self.head_dim])
        q, k, v = mp.unbind(qkv, axis=2)
        if cache is not None:
            k = mp.concat([cache[0], k], axis=1)
            v = mp.concat([cache[1], v], axis=1)
            new_cache = (k, v)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=cache is None, dropout_p=self.dropout,
            training=self.training)
        out = mp.reshape(out, [B, S, H])
        out = self.out_proj(out)
        if cache is not None:
            return out, new_cache
        return out

    def _qkv_heads(self, x, mp_axis, lora=None, layer=None):
        """Project to per-head q/k/v `[B, S, heads, D]`. Unsharded:
        the fused `[H, 3H]` matmul (3-major reshape, unchanged).
        Under tensor parallel (`mp_axis` set) the serving engine binds
        this layer's qkv weight HEAD-GROUPED as `[H, heads/mp, 3, D]`
        (bias `[heads/mp, 3, D]`): the same full-length dot products
        produce just this shard's heads — column parallelism, so every
        float op is identical to mp=1 and token parity is exact.
        With `lora` (an `ops.lora.LoraState` — multi-tenant adapter
        serving) each slot's per-tenant low-rank qkv delta is added in
        the projection's own layout before the unbind; adapter id 0
        contributes exact zeros."""
        B, S, H = x.shape
        if mp_axis is None:
            qkv = self.qkv_proj(x)
            qkv = mp.reshape(qkv,
                             [B, S, 3, self.num_heads, self.head_dim])
            if lora is not None:
                qkv = qkv + lora.qkv_delta(x, layer, head_major=False)
            return mp.unbind(qkv, axis=2)
        from paddle_tpu.ops import nn_ops

        w, b = self.qkv_proj.weight, self.qkv_proj.bias
        lh = w.shape[1]                    # heads on this shard
        qkv = nn_ops.linear(
            x, mp.reshape(w, [H, lh * 3 * self.head_dim]),
            None if b is None
            else mp.reshape(b, [lh * 3 * self.head_dim]))
        qkv = mp.reshape(qkv, [B, S, lh, 3, self.head_dim])
        if lora is not None:
            # the B pages are head-sharded exactly like the qkv weight
            # (_tp_plan layout), so the shard's delta covers ITS heads
            qkv = qkv + lora.qkv_delta(x, layer, head_major=True)
        return mp.unbind(qkv, axis=3)

    def _attn_out(self, out, B, S, mp_axis, lora=None, layer=None):
        """Merge heads and apply the output projection. Under tensor
        parallel the shard's heads are all-gathered to the full
        `[B, S, H]` activation first, and out_proj (bound
        column-sharded `[H, H/mp]`) is followed by a second gather —
        full-length dots + exact concats, never a partial-sum psum, so
        the result is bit-identical to mp=1 (see DESIGN_DECISIONS
        "Tensor-parallel sharded serving"). The per-tenant `lora`
        delta adds to the (output-sharded) projection before the final
        gather — same input, same column slice, no extra collective."""
        out = mp.reshape(out, [B, S, -1])
        if mp_axis is not None:
            out = _mp_all_gather(out, mp_axis)
        proj = self.out_proj(out)
        if lora is not None:
            proj = proj + lora.linear_delta("out", out, layer)
        if mp_axis is not None:
            proj = _mp_all_gather(proj, mp_axis)
        return proj

    def forward_prefill(self, x):
        """Causal forward that ALSO returns this layer's k/v for the
        whole (padded) buffer — fills the fixed-size decode cache."""
        B, S, H = x.shape
        q, k, v = self._qkv_heads(x, None)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=0.0, training=False)
        return self._attn_out(out, B, S, None), k, v

    def forward_prefill_chunk(self, x, kpool, vpool, layer_idx,
                              block_row, start, plen, mp_axis=None,
                              kv_scales=None, lora=None):
        """Chunked prefill for ONE slot against the paged pool: write
        this chunk's k/v through the slot's block table and attend the
        chunk's queries over the whole context so far (shared prefix
        blocks included, read-only). x [1,C,H]; start/plen traced
        scalars — one compiled program per chunk WIDTH, not per prompt
        length. Returns (out [1,C,H], new_kpool, new_vpool), plus the
        updated per-block scale array when `kv_scales` rides along
        (int8 KV serving)."""
        from paddle_tpu.ops.paged_attention import paged_prefill_chunk

        B, C, H = x.shape  # B == 1
        q, k, v = self._qkv_heads(x, mp_axis, lora=lora,
                                  layer=layer_idx)
        if kv_scales is not None:
            out, kpool, vpool, kv_scales = paged_prefill_chunk(
                q, k, v, kpool, vpool, layer_idx, block_row, start,
                plen, scales=kv_scales, mp_axis=mp_axis)
            return (self._attn_out(out, B, C, mp_axis, lora=lora,
                                   layer=layer_idx), kpool, vpool,
                    kv_scales)
        out, kpool, vpool = paged_prefill_chunk(
            q, k, v, kpool, vpool, layer_idx, block_row, start, plen)
        return self._attn_out(out, B, C, mp_axis, lora=lora,
                              layer=layer_idx), kpool, vpool

    def forward_decode(self, x, kcache, vcache, pos):
        """One-token decode against a FIXED-size cache (the jit-friendly
        KV cache: no growing concat). x [B,1,H]; kcache/vcache
        [B,L,heads,D]; pos may be a traced scalar — or a [B] vector of
        per-row positions (the continuous-batching shape: each slot sits
        at its own depth). Writes this token's k/v at `pos`, attends
        over positions <= pos (additive mask), returns
        (out [B,1,H], new_kcache, new_vcache)."""
        import paddle_tpu as paddle

        B, S, H = x.shape  # S == 1
        L = kcache.shape[1]
        qkv = self.qkv_proj(x)
        qkv = mp.reshape(qkv, [B, 1, 3, self.num_heads, self.head_dim])
        q, k, v = mp.unbind(qkv, axis=2)        # [B,1,heads,D]
        per_row = getattr(pos, "ndim", 0) == 1  # [B] vector of positions
        posv = mp.reshape(pos, [B, 1]) if per_row else pos
        slot = (paddle.arange(L).unsqueeze(0) == posv).reshape(
            [-1, L, 1, 1])                      # [B or 1, L, 1, 1]
        kcache = paddle.where(slot, k, kcache)
        vcache = paddle.where(slot, v, vcache)
        # additive mask over the buffer: future slots (and the padded
        # tail) are -inf
        allowed = (paddle.arange(L).unsqueeze(0) <= posv)  # [B or 1, L]
        attn_mask = paddle.where(
            allowed, paddle.zeros([1, L]),
            paddle.full([1, L], -1e30)).reshape([-1, 1, 1, L])
        out = F.scaled_dot_product_attention(
            q, kcache, vcache, attn_mask=attn_mask, dropout_p=0.0,
            training=False)
        return (self.out_proj(mp.reshape(out, [B, 1, H])), kcache,
                vcache)

    def forward_decode_paged(self, x, kpool, vpool, layer_idx,
                             block_tables, positions, backend="auto",
                             mp_axis=None, kv_scales=None, lora=None):
        """Batched one-token decode against the GLOBAL paged KV pool
        (the continuous-batching engine's layer step). x [slots,1,H];
        kpool/vpool [layers, num_blocks, block_size, heads, D];
        positions [slots] per-slot absolute positions; block_tables
        [slots, max_blocks]; backend is the paged-attention kernel
        selector (`auto`/`dense`/`pallas` — ops/paged_attention.py).
        With `mp_axis` set (inside the engine's shard_map step) the
        pools and q/k/v carry heads/mp heads; the attention op is
        head-count agnostic, so both backends run per-shard unchanged.
        With `kv_scales` (int8 KV serving) the pools are int8 and the
        updated `[L, blocks, 2]` scale array returns as a 4th output.
        With `lora` (multi-tenant adapter serving) each slot's tenant
        delta fuses into the qkv and out projections.
        Returns (out, new_kpool, new_vpool[, new_kv_scales])."""
        from paddle_tpu.ops.paged_attention import paged_attention_step

        B, S, H = x.shape  # S == 1
        q, k, v = self._qkv_heads(x, mp_axis, lora=lora,
                                  layer=layer_idx)
        if kv_scales is not None:
            out, kpool, vpool, kv_scales = paged_attention_step(
                q, k, v, kpool, vpool, layer_idx, block_tables,
                positions, backend=backend, scales=kv_scales,
                mp_axis=mp_axis)
            return (self._attn_out(out, B, 1, mp_axis, lora=lora,
                                   layer=layer_idx), kpool, vpool,
                    kv_scales)
        out, kpool, vpool = paged_attention_step(
            q, k, v, kpool, vpool, layer_idx, block_tables, positions,
            backend=backend)
        return self._attn_out(out, B, 1, mp_axis, lora=lora,
                              layer=layer_idx), kpool, vpool

    def forward_verify_paged(self, x, kpool, vpool, layer_idx,
                             block_tables, positions, draft_lens,
                             backend="auto", mp_axis=None,
                             kv_scales=None, lora=None):
        """Speculative K-token verify over the GLOBAL paged pool: one
        fixed `[slots, W]` window per lane (W = K+1: the feed token
        plus the drafts). x [slots,W,H]; positions [slots] absolute
        position of window row 0 per slot; draft_lens [slots] live-row
        count minus one (rows past it write the null block). Writes
        every live row's k/v through the table and attends each window
        query causally up to its own position — the target model
        scores all W candidate positions in one pass. Returns
        (out [slots,W,H], new_kpool, new_vpool), plus the updated
        scale array under int8 KV serving (`kv_scales`). `lora` fuses
        each slot's tenant delta into the projections, same as the
        decode step — the verify window scores under the ADAPTED
        model, so speculative acceptance stays exact per tenant."""
        from paddle_tpu.ops.paged_attention import paged_verify_window

        B, W, H = x.shape
        q, k, v = self._qkv_heads(x, mp_axis, lora=lora,
                                  layer=layer_idx)
        if kv_scales is not None:
            out, kpool, vpool, kv_scales = paged_verify_window(
                q, k, v, kpool, vpool, layer_idx, block_tables,
                positions, draft_lens, backend=backend,
                scales=kv_scales, mp_axis=mp_axis)
            return (self._attn_out(out, B, W, mp_axis, lora=lora,
                                   layer=layer_idx), kpool, vpool,
                    kv_scales)
        out, kpool, vpool = paged_verify_window(
            q, k, v, kpool, vpool, layer_idx, block_tables, positions,
            draft_lens, backend=backend)
        return self._attn_out(out, B, W, mp_axis, lora=lora,
                              layer=layer_idx), kpool, vpool


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, config.initializer_range)
        out_init = nn.initializer.Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers))
        bias_attr = None if config.use_bias else False
        self.fc1 = nn.Linear(config.hidden_size, config.intermediate_size,
                             weight_attr=nn.ParamAttr(initializer=init),
                             bias_attr=bias_attr)
        self.fc2 = nn.Linear(config.intermediate_size, config.hidden_size,
                             weight_attr=nn.ParamAttr(initializer=out_init),
                             bias_attr=bias_attr)
        self.dropout = nn.Dropout(config.dropout)
        from jax.sharding import PartitionSpec as P

        if _mp_degree() > 1 and config.intermediate_size % _mp_degree() == 0:
            self.fc1.weight.dist_spec = P(None, "mp")
            if self.fc1.bias is not None:
                self.fc1.bias.dist_spec = P("mp")
            self.fc2.weight.dist_spec = P("mp", None)

    def forward(self, x, mp_axis=None, lora=None, layer=None):
        """Under tensor parallel (`mp_axis` set, serving engine's
        shard_map step) fc1 AND fc2 are bound column-sharded
        (`[H, I/mp]` / `[I, H/mp]`): each shard's outputs are
        full-length dots over the gathered input, concatenated by a
        tiled all-gather — exact column parallelism both times, never
        a partial-sum psum, so mp=N output is bit-identical to mp=1.
        The per-tenant `lora` deltas add to the (output-sharded) fc1
        pre-activation and fc2 output — same inputs, same column
        slices, no extra collective (adapter id 0 adds exact zeros)."""
        pre = self.fc1(x)
        if lora is not None:
            pre = pre + lora.linear_delta("fc1", x, layer)
        h = F.gelu(pre, approximate=True)
        if mp_axis is not None:
            h = _mp_all_gather(h, mp_axis)
        out = self.fc2(h)
        if lora is not None:
            out = out + lora.linear_delta("fc2", h, layer)
        if mp_axis is not None:
            out = _mp_all_gather(out, mp_axis)
        return self.dropout(out)


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)

    def forward(self, x, cache=None):
        if cache is not None:
            a, new_cache = self.attn(self.ln1(x), cache=cache)
            x = x + a
            x = x + self.mlp(self.ln2(x))
            return x, new_cache
        x = x + self.attn(self.ln1(x))
        x = x + self.mlp(self.ln2(x))
        return x

    def forward_prefill(self, x):
        a, k, v = self.attn.forward_prefill(self.ln1(x))
        x = x + a
        return x + self.mlp(self.ln2(x)), k, v

    def forward_prefill_chunk(self, x, kpool, vpool, layer_idx,
                              block_row, start, plen, mp_axis=None,
                              kv_scales=None, lora=None):
        if kv_scales is not None:
            a, kpool, vpool, kv_scales = self.attn.forward_prefill_chunk(
                self.ln1(x), kpool, vpool, layer_idx, block_row,
                start, plen, mp_axis=mp_axis, kv_scales=kv_scales,
                lora=lora)
            x = x + a
            return (x + self.mlp(self.ln2(x), mp_axis=mp_axis,
                                 lora=lora, layer=layer_idx), kpool,
                    vpool, kv_scales)
        a, kpool, vpool = self.attn.forward_prefill_chunk(
            self.ln1(x), kpool, vpool, layer_idx, block_row, start,
            plen, mp_axis=mp_axis, lora=lora)
        x = x + a
        return (x + self.mlp(self.ln2(x), mp_axis=mp_axis, lora=lora,
                             layer=layer_idx), kpool,
                vpool)

    def forward_decode(self, x, kcache, vcache, pos):
        a, kcache, vcache = self.attn.forward_decode(self.ln1(x),
                                                     kcache, vcache,
                                                     pos)
        x = x + a
        return x + self.mlp(self.ln2(x)), kcache, vcache

    def forward_decode_paged(self, x, kpool, vpool, layer_idx,
                             block_tables, positions, backend="auto",
                             mp_axis=None, kv_scales=None, lora=None):
        if kv_scales is not None:
            a, kpool, vpool, kv_scales = self.attn.forward_decode_paged(
                self.ln1(x), kpool, vpool, layer_idx, block_tables,
                positions, backend=backend, mp_axis=mp_axis,
                kv_scales=kv_scales, lora=lora)
            x = x + a
            return (x + self.mlp(self.ln2(x), mp_axis=mp_axis,
                                 lora=lora, layer=layer_idx), kpool,
                    vpool, kv_scales)
        a, kpool, vpool = self.attn.forward_decode_paged(
            self.ln1(x), kpool, vpool, layer_idx, block_tables,
            positions, backend=backend, mp_axis=mp_axis, lora=lora)
        x = x + a
        return (x + self.mlp(self.ln2(x), mp_axis=mp_axis, lora=lora,
                             layer=layer_idx), kpool,
                vpool)

    def forward_verify_paged(self, x, kpool, vpool, layer_idx,
                             block_tables, positions, draft_lens,
                             backend="auto", mp_axis=None,
                             kv_scales=None, lora=None):
        if kv_scales is not None:
            a, kpool, vpool, kv_scales = self.attn.forward_verify_paged(
                self.ln1(x), kpool, vpool, layer_idx, block_tables,
                positions, draft_lens, backend=backend,
                mp_axis=mp_axis, kv_scales=kv_scales, lora=lora)
            x = x + a
            return (x + self.mlp(self.ln2(x), mp_axis=mp_axis,
                                 lora=lora, layer=layer_idx), kpool,
                    vpool, kv_scales)
        a, kpool, vpool = self.attn.forward_verify_paged(
            self.ln1(x), kpool, vpool, layer_idx, block_tables,
            positions, draft_lens, backend=backend, mp_axis=mp_axis,
            lora=lora)
        x = x + a
        return (x + self.mlp(self.ln2(x), mp_axis=mp_axis, lora=lora,
                             layer=layer_idx), kpool,
                vpool)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(config.dropout)
        self.blocks = nn.LayerList([GPTBlock(config)
                                    for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None):
        B, S = input_ids.shape
        if position_ids is None:
            position_ids = paddle.arange(S, dtype="int32")
        h = self.wte(input_ids) + self.wpe(position_ids)
        h = self.drop(h)
        for blk in self.blocks:
            h = blk(h)
        return self.ln_f(h)

    def _embed(self, token_ids, mp_axis):
        """Token embedding; under tensor parallel the wte table is
        bound vocab-sharded `[V/mp, H]` and the lookup goes through the
        masked-gather + psum (exact) vocab-parallel path."""
        if mp_axis is None:
            return self.wte(token_ids)
        return _vocab_parallel_embed(self.wte.weight, token_ids,
                                     mp_axis)

    def forward_prefill(self, input_ids):
        """Fill the decode caches: causal forward over the (padded)
        buffer, collecting per-layer k/v stacked on a leading layer
        axis (single Tensors, so a compiled decode loop carries them)."""
        B, S = input_ids.shape
        h = self._embed(input_ids, None) + self.wpe(
            paddle.arange(S, dtype="int32"))
        ks, vs = [], []
        for blk in self.blocks:
            h, k, v = blk.forward_prefill(h)
            ks.append(k)
            vs.append(v)
        return self.ln_f(h), mp.stack(ks, axis=0), mp.stack(vs, axis=0)

    def forward_prefill_chunk(self, token_ids, start, kpool, vpool,
                              block_row, plen, mp_axis=None,
                              kv_scales=None, lora=None):
        """Chunked paged prefill (the engine's incremental admission
        path): token_ids [1,C] — chunk `[start, start+C)` of one
        slot's prompt, padded past `plen`; kpool/vpool the global
        paged pools; block_row [max_blocks] the slot's table. Writes
        the chunk's per-layer KV through the table and returns
        (hidden [1,C,H], new_kpool, new_vpool). `start`/`plen` are
        traced — ONE compiled program serves every chunk of every
        prompt, so prefill trace count is bounded by the chunk shape,
        not a bucket ladder."""
        B, C = token_ids.shape
        pos_t = start.astype("int32") if hasattr(start, "astype") \
            else paddle.to_tensor(start, dtype="int32")
        # clamp padded-tail positions into the wpe table: their rows
        # are garbage the engine ignores, but the gather must stay in
        # bounds for any (start, chunk) combination
        pos_vec = paddle.clip(pos_t + paddle.arange(C, dtype="int32"),
                              0, self.config.max_seq_len - 1)
        h = self._embed(token_ids, mp_axis) \
            + self.wpe(pos_vec).unsqueeze(0)
        if kv_scales is not None:
            for i, blk in enumerate(self.blocks):
                h, kpool, vpool, kv_scales = blk.forward_prefill_chunk(
                    h, kpool, vpool, i, block_row, pos_t, plen,
                    mp_axis=mp_axis, kv_scales=kv_scales, lora=lora)
            return self.ln_f(h), kpool, vpool, kv_scales
        for i, blk in enumerate(self.blocks):
            h, kpool, vpool = blk.forward_prefill_chunk(
                h, kpool, vpool, i, block_row, pos_t, plen,
                mp_axis=mp_axis, lora=lora)
        return self.ln_f(h), kpool, vpool

    def forward_decode(self, token_ids, pos, kstack, vstack):
        """One decode step: token_ids [B,1], pos scalar (may be traced)
        or [B] per-row positions, kstack/vstack
        [num_layers, B, L, heads, D]. Returns
        (hidden [B,1,H], new_kstack, new_vstack)."""
        pos_t = pos.astype("int32") if hasattr(pos, "astype") \
            else paddle.to_tensor(pos, dtype="int32")
        if getattr(pos_t, "ndim", 0) == 1:      # per-row: [B] -> [B,1,H]
            pemb = self.wpe(pos_t).unsqueeze(1)
        else:
            pemb = self.wpe(mp.reshape(pos_t, [1]))
        h = self.wte(token_ids) + pemb
        nks, nvs = [], []
        for i, blk in enumerate(self.blocks):
            h, nk, nv = blk.forward_decode(h, kstack[i], vstack[i], pos)
            nks.append(nk)
            nvs.append(nv)
        return (self.ln_f(h), mp.stack(nks, axis=0),
                mp.stack(nvs, axis=0))

    def forward_decode_paged(self, token_ids, positions, kpool, vpool,
                             block_tables, backend="auto",
                             mp_axis=None, kv_scales=None, lora=None):
        """Batched decode step over the paged pool (continuous-batching
        engine path): token_ids [slots,1], positions [slots] int32
        per-slot absolute positions, kpool/vpool
        [num_layers, num_blocks, block_size, heads, D], block_tables
        [slots, max_blocks], backend the paged-attention kernel
        selector (`auto`/`dense`/`pallas`, resolved per layer step in
        ops/paged_attention.py). Returns (hidden [slots,1,H],
        new_kpool, new_vpool) — pool updates chain functionally through
        the layers and alias in place under the engine's donated
        compiled step."""
        pos_t = positions.astype("int32") if hasattr(positions, "astype") \
            else paddle.to_tensor(positions, dtype="int32")
        h = self._embed(token_ids, mp_axis) \
            + self.wpe(pos_t).unsqueeze(1)
        if kv_scales is not None:
            for i, blk in enumerate(self.blocks):
                h, kpool, vpool, kv_scales = blk.forward_decode_paged(
                    h, kpool, vpool, i, block_tables, pos_t,
                    backend=backend, mp_axis=mp_axis,
                    kv_scales=kv_scales, lora=lora)
            return self.ln_f(h), kpool, vpool, kv_scales
        for i, blk in enumerate(self.blocks):
            h, kpool, vpool = blk.forward_decode_paged(
                h, kpool, vpool, i, block_tables, pos_t,
                backend=backend, mp_axis=mp_axis, lora=lora)
        return self.ln_f(h), kpool, vpool

    def forward_verify_paged(self, token_ids, positions, draft_lens,
                             kpool, vpool, block_tables,
                             backend="auto", mp_axis=None,
                             kv_scales=None, lora=None):
        """Speculative verify step over the paged pool (the engine's
        K-token decode): token_ids [slots, W] — the feed token plus up
        to W-1 drafted tokens per lane, positions [slots] int32 row-0
        absolute positions, draft_lens [slots] int32 live-row bounds
        (both traced — ONE compiled program per (backend, W) serves
        every draft/acceptance mix), kpool/vpool the global pools,
        block_tables [slots, max_blocks]. Returns
        (hidden [slots, W, H], new_kpool, new_vpool) — the hidden at
        every window row, so the caller argmaxes all W candidate
        continuations from one pass."""
        B, W = token_ids.shape
        pos_t = positions.astype("int32") \
            if hasattr(positions, "astype") \
            else paddle.to_tensor(positions, dtype="int32")
        dlen_t = draft_lens.astype("int32") \
            if hasattr(draft_lens, "astype") \
            else paddle.to_tensor(draft_lens, dtype="int32")
        # absolute position per window row, clipped into the wpe table:
        # dead rows past a slot's draft length may run beyond the
        # model's positions — their rows are garbage the engine
        # ignores, but the gather must stay in bounds
        wpos = paddle.clip(
            pos_t.unsqueeze(1)
            + paddle.arange(W, dtype="int32").unsqueeze(0),
            0, self.config.max_seq_len - 1)            # [B, W]
        h = self._embed(token_ids, mp_axis) + self.wpe(wpos)
        if kv_scales is not None:
            for i, blk in enumerate(self.blocks):
                h, kpool, vpool, kv_scales = blk.forward_verify_paged(
                    h, kpool, vpool, i, block_tables, pos_t, dlen_t,
                    backend=backend, mp_axis=mp_axis,
                    kv_scales=kv_scales, lora=lora)
            return self.ln_f(h), kpool, vpool, kv_scales
        for i, blk in enumerate(self.blocks):
            h, kpool, vpool = blk.forward_verify_paged(
                h, kpool, vpool, i, block_tables, pos_t, dlen_t,
                backend=backend, mp_axis=mp_axis, lora=lora)
        return self.ln_f(h), kpool, vpool


def _transformed_method(cls, name):
    """Lazily dy2static-transform an unbound method ONCE per class (the
    transform is source-level; callers get a cached converted function
    whose tensor-`while` loops run as lax.while_loop under any trace)."""
    cache_name = f"_{name}_jst"
    fn = cls.__dict__.get(cache_name)
    if fn is None:
        from paddle_tpu.jit.dy2static import transform_function

        fn = transform_function(getattr(cls, name))
        setattr(cls, cache_name, staticmethod(fn))
    return fn


class GPTForCausalLM(nn.Layer):
    """LM head ties to wte (SharedLayerDesc analog, pp_layers.py:77)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def forward(self, input_ids, labels=None):
        h = self.gpt(input_ids)
        # tied embedding projection: [B,S,H] @ [V,H]^T
        logits = paddle.matmul(h, self.gpt.wte.weight, transpose_y=True)
        if labels is not None:
            loss = F.cross_entropy(
                mp.reshape(logits, [-1, self.config.vocab_size]),
                mp.reshape(labels, [-1]))
            return loss
        return logits

    def loss_fn(self, logits, labels):
        return F.cross_entropy(
            mp.reshape(logits, [-1, self.config.vocab_size]),
            mp.reshape(labels, [-1]))

    def generate(self, input_ids, max_length=None, eos_token_id=None,
                 use_cache=False):
        """Greedy decode (generation_utils GenerationMixin.greedy_search
        analog). Written as a data-dependent `while` over a fixed-size
        token buffer so that under @to_static the WHOLE decode compiles
        to ONE program with a lax.while_loop inside (dy2static
        convert_while_loop — the run-to-completion decode loop); eager
        calls run the same code as a python loop.

        use_cache=False re-runs the causal forward over the buffer per
        token (correctness-first); use_cache=True is the fixed-buffer
        KV-cache path (forward_prefill + per-token forward_decode — the
        layer caches are stacked Tensors so the compiled loop carries
        them; O(prefix) per token instead of O(prefix^2)). Compiling
        the cached loop for a very deep model is a significant one-time
        cost through remote-compile setups (the whole 24-layer step is
        one program); small/medium configs compile in seconds.

        input_ids [B, S0] -> tokens [B, max_length] (positions past an
        early EOS keep repeating EOS because `done` rows freeze).

        Generation is an eval-mode operation: with use_cache=True and
        active dropout the cached path (which never applies dropout)
        would diverge from the plain path, so it refuses."""
        max_length = max_length or self.config.max_seq_len
        B, S0 = input_ids.shape
        if max_length < S0:
            raise ValueError(f"max_length={max_length} < prompt {S0}")
        if use_cache and self.training and self.config.dropout > 0:
            raise ValueError(
                "generate(use_cache=True) is deterministic (no dropout) "
                "— call model.eval() first")
        # route through dy2static-transformed bodies so the decode
        # while converts to lax.while_loop even when generate is CALLED
        # from inside a larger traced function (not itself the
        # to_static entry point)
        impl = _transformed_method(
            type(self),
            "_generate_cached" if use_cache else "_generate_plain")
        return impl(self, input_ids, max_length, eos_token_id)

    def _generate_plain(self, input_ids, max_length, eos_token_id):
        import paddle_tpu as paddle

        B, S0 = input_ids.shape
        pad = paddle.zeros([B, max_length - S0], dtype=input_ids.dtype)
        tokens = mp.concat([input_ids, pad], axis=1)      # [B, L] static
        positions = paddle.arange(max_length)             # [L]
        # `done` derives from the (possibly traced) input so the loop
        # condition is tensor-dependent from the first evaluation
        done = (input_ids.sum(axis=1) * 0).astype("bool")  # [B] False
        pos = S0
        while paddle.logical_and(paddle.logical_not(done.all()),
                                 paddle.to_tensor(pos < max_length)):
            logits = self.forward(tokens)                 # [B, L, V]
            # logits at pos-1 decide the token at pos (one-hot reduce:
            # index `pos` is a traced scalar inside the compiled loop)
            sel = (positions == (pos - 1)).astype(logits.dtype)
            step_logits = (logits * sel.unsqueeze(0).unsqueeze(-1)) \
                .sum(axis=1)                              # [B, V]
            nxt = step_logits.argmax(axis=-1).astype(input_ids.dtype)
            if eos_token_id is not None:
                eos = paddle.full([1], eos_token_id, input_ids.dtype)
                nxt = paddle.where(done, eos.expand([B]), nxt)
                done = paddle.logical_or(done, nxt == eos_token_id)
            write = (positions == pos).unsqueeze(0)       # [1, L]
            tokens = paddle.where(write, nxt.unsqueeze(-1), tokens)
            pos = pos + 1
        return tokens

    def serving_spec(self):
        """What the generation engine asks of a model
        (`inference/serving_spec.py`)."""
        return GPTServing(self)

    def _logits_of(self, hidden, mp_axis=None):
        """Tied-embedding logits. Under tensor parallel the wte table
        is bound vocab-sharded, so each shard computes its `[.., V/mp]`
        logit columns with full-length dots; ONE tiled all-gather
        assembles the full logits (replicated on every shard) for the
        host's greedy argmax / speculative acceptance — exact, where a
        sharded-argmax psum would save bandwidth but lose the simple
        "full logits on host" contract (DESIGN_DECISIONS r12)."""
        logits = paddle.matmul(hidden, self.gpt.wte.weight,
                               transpose_y=True)
        if mp_axis is not None:
            logits = _mp_all_gather(logits, mp_axis)
        return logits

    def _generate_cached(self, input_ids, max_length, eos_token_id):
        import paddle_tpu as paddle

        B, S0 = input_ids.shape
        L = max_length
        pad = paddle.zeros([B, L - S0], dtype=input_ids.dtype)
        tokens = mp.concat([input_ids, pad], axis=1)
        positions = paddle.arange(L)
        # prefill over the PROMPT only (O(S0^2) attention, not O(L^2));
        # cache buffers zero-pad to L — every slot >= S0 is overwritten
        # before it is ever attended (the decode mask is <= pos)
        hidden, kstack, vstack = self.gpt.forward_prefill(input_ids)
        def pad_cache(c):
            z = paddle.zeros(list(c.shape[:2]) + [L - S0] +
                             list(c.shape[3:]), dtype=c.dtype)
            return mp.concat([c, z], axis=2)

        kstack = pad_cache(kstack)
        vstack = pad_cache(vstack)
        # only the last prompt position's logits matter: reduce hidden
        # to [B,H] BEFORE the vocab projection (1/L the matmul)
        first_logits = self._logits_of(hidden[:, S0 - 1])
        cur = first_logits.argmax(axis=-1).astype(input_ids.dtype)
        done = (input_ids.sum(axis=1) * 0).astype("bool")
        if eos_token_id is not None:
            done = paddle.logical_or(done, cur == eos_token_id)
        tokens = paddle.where((positions == S0).unsqueeze(0),
                              cur.unsqueeze(-1), tokens)
        pos = S0
        # decode: token at `pos` goes in, token at pos+1 comes out
        # (h_step is a fresh name: the prefill `hidden` is [B,L,H] and
        # must not be carried against the loop's [B,1,H] activations)
        while paddle.logical_and(paddle.logical_not(done.all()),
                                 paddle.to_tensor(pos < L - 1)):
            h_step, kstack, vstack = self.gpt.forward_decode(
                cur.unsqueeze(-1), pos, kstack, vstack)
            nxt = self._logits_of(h_step)[:, 0].argmax(axis=-1) \
                .astype(tokens.dtype)
            if eos_token_id is not None:
                eos = paddle.full([1], eos_token_id, tokens.dtype)
                nxt = paddle.where(done, eos.expand([B]), nxt)
                done = paddle.logical_or(done, nxt == eos_token_id)
            tokens = paddle.where((positions == pos + 1).unsqueeze(0),
                                  nxt.unsqueeze(-1), tokens)
            cur = nxt
            pos = pos + 1
        return tokens

    def num_params(self):
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None):
        """Approximate training FLOPs/token (6ND + attention)."""
        c = self.config
        n = self.num_params()
        s = seq_len or c.max_seq_len
        return 6 * n + 12 * c.num_layers * c.hidden_size * s


class GPTServing(ServingSpec):
    """The GPT-2-style decoder as the engine sees it: every layer keeps
    paged K and V of `num_heads` heads, no state of fixed size, and every
    option is served (tensor parallel, int8 weights and KV, adapters,
    prefix reuse, forks, speculative windows)."""

    def __init__(self, model):
        cfg = model.config
        super().__init__(
            model, cfg.vocab_size, cfg.max_seq_len,
            model.gpt.wte.weight._array.dtype,
            PagedKV(cfg.num_layers, cfg.num_heads,
                    cfg.hidden_size // cfg.num_heads, cfg.num_heads),
            dropout=cfg.dropout)
        self.config = cfg

    def check_mesh(self, mp_degree, devices):
        """The Megatron divisibility constraints, up front: fail HERE
        with the shape story, not deep in a per-shard reshape."""
        from paddle_tpu.distributed.topology import serving_mesh

        cfg = self.config
        serving_mesh(mp_degree, num_heads=cfg.num_heads,
                     vocab_size=cfg.vocab_size, devices=devices)
        if cfg.intermediate_size % mp_degree:
            raise ValueError(
                f"intermediate_size={cfg.intermediate_size} is not "
                f"divisible by mp degree {mp_degree} — cannot "
                "column-shard the MLP")

    def adapter_geometry(self):
        cfg = self.config
        return {"num_layers": cfg.num_layers,
                "hidden_size": cfg.hidden_size,
                "intermediate_size": cfg.intermediate_size,
                "num_heads": cfg.num_heads}

    def weight_quant_plan(self):
        """id(state tensor) -> (scale_transform, scale PartitionSpec)
        for every weight served int8: the attention qkv/out and MLP
        fc1/fc2 matmuls (the per-step weight-read floor), per-OUTPUT-
        channel absmax scales via quantization.quantize_absmax(axis=1).
        Embeddings/norms/biases stay fp — the logit head's quality is
        the tolerance budget's scarcest resource. The scale transform
        mirrors `tp_plan`'s qkv head-grouping so scales shard exactly
        like their weights."""
        from jax.sharding import PartitionSpec as P

        D = self.paged_kv.head_dim

        def qkv_s(s):                  # [1, 3H] -> [1, heads, 3, D]
            return s.reshape(1, 3, -1, D).transpose(0, 2, 1, 3)

        plan = {}
        for blk in self.model.gpt.blocks:
            attn, mlp = blk.attn, blk.mlp
            plan[id(attn.qkv_proj.weight)] = (qkv_s,
                                              P(None, "mp", None, None))
            for lin in (attn.out_proj, mlp.fc1, mlp.fc2):
                plan[id(lin.weight)] = (None, P(None, "mp"))
        return plan

    def tp_plan(self):
        """id(state tensor) -> (transform, PartitionSpec): the Megatron
        column-parallel serving layout. qkv weights are re-grouped
        head-major (`[H, heads, 3, D]`) so a contiguous heads-axis
        shard holds complete (q, k, v) triples for ITS heads;
        out_proj/fc1/fc2 shard their OUTPUT columns (full-length dots,
        all-gathered activations — bit-exact vs mp=1, see
        DESIGN_DECISIONS r12); wte shards vocab rows. Everything else
        (layer norms, wpe) replicates."""
        from jax.sharding import PartitionSpec as P

        D = self.paged_kv.head_dim

        def qkv_w(w):
            return w.reshape(w.shape[0], 3, -1, D).transpose(0, 2, 1, 3)

        def qkv_b(b):
            return b.reshape(3, -1, D).transpose(1, 0, 2)

        plan = {}
        gpt = self.model.gpt
        plan[id(gpt.wte.weight)] = (None, P("mp", None))
        for blk in gpt.blocks:
            attn, mlp = blk.attn, blk.mlp
            plan[id(attn.qkv_proj.weight)] = (qkv_w,
                                              P(None, "mp", None, None))
            if attn.qkv_proj.bias is not None:
                plan[id(attn.qkv_proj.bias)] = (qkv_b,
                                                P("mp", None, None))
            for lin in (attn.out_proj, mlp.fc1, mlp.fc2):
                plan[id(lin.weight)] = (None, P(None, "mp"))
                if lin.bias is not None:
                    plan[id(lin.bias)] = (None, P("mp"))
        return plan

    # -- the step functions: GPTModel's, by name -------------------------
    def logits(self, hidden, mp_axis=None):
        return self.model._logits_of(hidden, mp_axis=mp_axis)

    @staticmethod
    def _out(r, kv_scales):
        if kv_scales is None:
            return StepOut(*r)
        return StepOut(r[0], r[1], r[2], kv_scales=r[3])

    def prefill_chunk(self, tokens, start, kpool, vpool, block_row, plen,
                      backend="auto", mp_axis=None, kv_scales=None,
                      lora=None):
        return self._out(self.model.gpt.forward_prefill_chunk(
            tokens, start, kpool, vpool, block_row, plen,
            mp_axis=mp_axis, kv_scales=kv_scales, lora=lora), kv_scales)

    def decode(self, tokens, positions, kpool, vpool, block_tables,
               backend="auto", mp_axis=None, kv_scales=None, lora=None):
        return self._out(self.model.gpt.forward_decode_paged(
            tokens, positions, kpool, vpool, block_tables,
            backend=backend, mp_axis=mp_axis, kv_scales=kv_scales,
            lora=lora), kv_scales)

    def verify(self, tokens, positions, draft_lens, kpool, vpool,
               block_tables, backend="auto", mp_axis=None,
               kv_scales=None, lora=None):
        return self._out(self.model.gpt.forward_verify_paged(
            tokens, positions, draft_lens, kpool, vpool, block_tables,
            backend=backend, mp_axis=mp_axis, kv_scales=kv_scales,
            lora=lora), kv_scales)


class GPTEmbeddingPipe(nn.Layer):
    """Embedding stage for the pipelined GPT (pp_layers.py SharedLayerDesc
    pattern: the same instance serves as the tied LM head)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(config.dropout)
        from jax.sharding import PartitionSpec as P

        if _mp_degree() > 1 and config.vocab_size % _mp_degree() == 0:
            # vocab-parallel embedding (VocabParallelEmbedding analog)
            self.wte.weight.dist_spec = P("mp", None)

    def forward(self, input_ids):
        S = input_ids.shape[1]
        pos = paddle.arange(S, dtype="int32")
        return self.drop(self.wte(input_ids) + self.wpe(pos))


def _gpt_head_fwd(embed_layer: "GPTEmbeddingPipe", x):
    # tied projection: [B,S,H] @ wte^T
    return paddle.matmul(x, embed_layer.wte.weight, transpose_y=True)


class GPTFinalNorm(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, x):
        return self.ln_f(x)


def build_pipeline_gpt(config: GPTConfig, num_stages: int,
                       num_microbatches: int = None,
                       recompute_interval: int = 0):
    """GPT as a PipelineLayer: tied embedding/head via SharedLayerDesc,
    the block stack stage-stacked over the 'pp' mesh axis. The analog of
    the reference's GPTForPretrainingPipe-style models driven by
    hybrid_parallel_pp_transformer.py tests."""
    from paddle_tpu.distributed import (LayerDesc, PipelineLayer,
                                        SharedLayerDesc)

    descs = [
        SharedLayerDesc("gpt_embed", GPTEmbeddingPipe, None, "wte.weight",
                        config),
        *[LayerDesc(GPTBlock, config) for _ in range(config.num_layers)],
        LayerDesc(GPTFinalNorm, config),
        SharedLayerDesc("gpt_embed", GPTEmbeddingPipe, _gpt_head_fwd,
                        "wte.weight", config),
    ]
    return PipelineLayer(descs, num_stages=num_stages,
                         num_microbatches=num_microbatches,
                         recompute_interval=recompute_interval)
