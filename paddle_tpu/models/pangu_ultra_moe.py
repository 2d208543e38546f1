"""`pangu_ultra_moe` — openPangu-Ultra-MoE: a decoder of latent-attention
layers with sandwich norms, dense SwiGLU MLPs in the leading layers and
sigmoid-routed SwiGLU experts (plus a shared one) after.

A layer, four RMSNorms (`sandwich_norm`):

    a  = h + RMSNorm_post_attn(MLA(RMSNorm_in(h)))
    h' = a + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(a)))

`MLA(u)` at position t: `c_q = RMSNorm(u W_qa)`, `[q_nope_h ; q_r_h] =
c_q W_qb` a head; `[c ; k_r] = u W_kva`, `c_kv = RMSNorm(c)`; the rotary
turns `q_r_h` and `k_r` (ONE rotated key for all the heads; pairs are the
two halves of the 64 values). **What is cached is `[c_kv ; k_rope]`, one
row a token a layer.** Expanded, `[k_nope_h ; v_h] = c_kv W_kvb` and the
heads attend as usual over keys `[k_nope_h ; k_rope]` at scale
`1 / sqrt(nope + rope)`. Absorbed, the same numbers without ever making a
key or a value: `q~_h = W_UK_h q_nope_h`, scores `q~_h . c_kv + q_rope_h .
k_rope`, `o^_h = sum p c_kv`, `o_h = W_UV_h^T o^_h`. Whole sequences and
long prefill chunks expand; decode is absorbed over the engine's latent
pool (`ops/paged_attention.paged_latent_decode`).

Experts: `s = sigmoid(float32(u) W_r)` over ALL the router's experts, the
`num_experts_per_tok` largest, `w = s / (sum s + 1e-20) x
routed_scaling_factor`; the experts HELD HERE (`n_routed_experts` of
`router_experts`, from `expert_offset`) are computed dropless by
`distributed/moe.expert_share(..., activation="swiglu")`; the shared
expert sees every token.

Final RMSNorm, untied head, no bias anywhere. Parameters are created in
the run dtype (`config.dtype`); `init="zeros"` skips the random draw for a
caller that binds every leaf itself.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference.serving_spec import PagedLatent, ServingSpec, \
    StepOut
from paddle_tpu.models._blocks import Leaves as _Leaves, dot as _dot, \
    gated_mlp as _gated_mlp, rms_norm as _rms_norm, rotary

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


@dataclass
class PanguUltraMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256        # experts HELD here
    router_experts: int = None         # the router's width (all experts)
    expert_offset: int = 0             # index of the first expert held
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    initializer_range: float = 0.02
    # per-leaf scales a caller may widen so that a comparison can see the
    # attention and the routing at all (see the benchmark's configuration)
    attn_query_init_std: float = None
    attn_key_init_std: float = None
    router_init_std: float = None
    dtype: str = "float32"
    init: str = "normal"               # or "zeros": leaves bound later
    dropout: float = 0.0

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        for name in ("attn_query_init_std", "attn_key_init_std",
                     "router_init_std"):
            if getattr(self, name) is None:
                setattr(self, name, self.initializer_range)
        if self.expert_offset + self.n_routed_experts \
                > self.router_experts:
            raise ValueError("the experts held lie outside the router")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary turns pairs of values")

    @property
    def row_values(self):
        """Values a cached row holds: `[c_kv ; k_rope]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_width(self):
        """Lanes of a pool row. A row wider than one 128-lane tile is
        padded to whole tiles: the chip lays the pool out in them anyway
        (a `[.., 576]` bf16 array IS `[.., 640]` in its memory) and its
        copy engine moves whole tiles only, so the kernel can fetch a
        page of 640 and cannot fetch one of 576. The padding lanes hold
        nought and score nought."""
        w = self.row_values
        return w if w <= 128 else -(-w // 128) * 128

    @staticmethod
    def tiny(layers=3, dense=1, vocab=128, **kw):
        base = dict(
            vocab_size=vocab, hidden_size=64, num_hidden_layers=layers,
            first_k_dense_replace=dense, num_attention_heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=3, max_seq_len=128, rope_theta=10000.0,
            # at 64 wide, N(0, 0.02) would leave the attention's logits
            # and the experts' part under any tolerance
            initializer_range=0.1, attn_query_init_std=0.4,
            attn_key_init_std=0.4, router_init_std=0.5)
        base.update(kw)
        return PanguUltraMoEConfig(**base)


class LatentAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        std, h, heads = cfg.initializer_range, cfg.hidden_size, \
            cfg.num_attention_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        kv = cfg.qk_nope_head_dim + cfg.v_head_dim
        self.q_a = _Leaves(cfg, weight=((h, cfg.q_lora_rank), std))
        self.q_a_norm = _Leaves(cfg, weight=((cfg.q_lora_rank,), std, 1.0))
        self.q_b = _Leaves(cfg, weight=((cfg.q_lora_rank, heads * qk),
                                        cfg.attn_query_init_std))
        self.kv_a = _Leaves(cfg, weight=((h, cfg.row_values),
                                         cfg.attn_key_init_std))
        self.kv_a_norm = _Leaves(cfg, weight=((cfg.kv_lora_rank,), std,
                                              1.0))
        self.kv_b = _Leaves(cfg, weight=((cfg.kv_lora_rank, heads * kv),
                                         std))
        self.o = _Leaves(cfg, weight=((heads * cfg.v_head_dim, h), std))

    def w_kvb(self):
        """`[rank, heads, nope + v]`: a head's `W_UK` then its `W_UV`."""
        cfg = self.cfg
        return self.kv_b.weight._array.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads, -1)

    def project(self, u, positions):
        """u `[.., hidden]` at `positions [..]` -> (q_nope `[.., heads,
        nope]`, q_rope `[.., heads, rope]` turned, row `[.., rank +
        rope]`: the normed compression then the turned key)."""
        cfg = self.cfg
        c_q = _rms_norm(_dot(u, self.q_a.weight._array),
                        self.q_a_norm.weight._array, cfg.rms_norm_eps)
        q = _dot(c_q, self.q_b.weight._array).reshape(
            u.shape[:-1] + (cfg.num_attention_heads, -1))
        dn = cfg.qk_nope_head_dim
        ckr = _dot(u, self.kv_a.weight._array)
        c_kv = _rms_norm(ckr[..., :cfg.kv_lora_rank],
                         self.kv_a_norm.weight._array, cfg.rms_norm_eps)
        k_rope = rotary(ckr[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]
        return (q[..., :dn], rotary(q[..., dn:], positions,
                                    cfg.rope_theta),
                jnp.concatenate([c_kv, k_rope], -1))

    @property
    def scale(self):
        cfg = self.cfg
        return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

    def _padded(self, x):
        """`x [.., row_values]` in the pool's lanes."""
        pad = self.cfg.pool_row_width - x.shape[-1]
        return x if not pad else jnp.pad(
            x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def out(self, o):
        return _dot(o.reshape(o.shape[:-2] + (-1,)), self.o.weight._array)

    def whole(self, u, absorbed=False):
        """Causal attention over whole sequences `[B, S, hidden]`, no
        cache; `absorbed` takes the decode's form of the same numbers."""
        cfg = self.cfg
        b, s, _ = u.shape
        rank, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        q_nope, q_rope, row = self.project(
            u, jnp.broadcast_to(jnp.arange(s), (b, s)))
        c_kv, k_rope = row[..., :rank], row[..., rank:]
        w = self.w_kvb()
        rope = jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope,
                          preferred_element_type=_F32)
        if absorbed:
            q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w[..., :dn],
                               preferred_element_type=_F32).astype(u.dtype)
            nope = jnp.einsum("bqhr,bkr->bhqk", q_abs, c_kv,
                              preferred_element_type=_F32)
        else:
            kv = jnp.einsum("bkr,rhd->bkhd", c_kv, w,
                            preferred_element_type=_F32).astype(u.dtype)
            nope = jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :dn],
                              preferred_element_type=_F32)
        keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(keep, (nope + rope) * self.scale,
                                     -1e30), axis=-1).astype(u.dtype)
        if absorbed:
            o = jnp.einsum("bhqk,bkr->bqhr", p, c_kv,
                           preferred_element_type=_F32).astype(u.dtype)
            o = jnp.einsum("bqhr,rhd->bqhd", o, w[..., dn:],
                           preferred_element_type=_F32)
        else:
            o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:],
                           preferred_element_type=_F32)
        return self.out(o.astype(u.dtype))

    def chunk(self, u, pool, layer, block_row, start, plen, backend):
        """One slot's chunk `u [C, hidden]` at positions `start ..`."""
        q_nope, q_rope, row = self.project(
            u, start + jnp.arange(u.shape[0]))
        o, pool = self._chunk_heads(q_nope, q_rope, row, pool, layer,
                                    block_row, start, plen, backend)
        return self.out(o), pool

    def step(self, u, pool, layer, block_tables, positions, backend):
        """One token a slot, `u [slots, hidden]`, absorbed."""
        q_nope, q_rope, row = self.project(u, positions)
        o, pool = self._step_heads(q_nope, q_rope, row, pool, layer,
                                   block_tables, positions, backend)
        return self.out(o), pool

    def chunk_and_step(self, u, width, pool, layer, block_row, start,
                       plen, tables, positions, backend):
        """`chunk` over the first `width` rows of `u [width + slots,
        hidden]` and `step` over the rest, with ONE pass of every
        projection: the chunk's rows attend in their own form, then the
        decode rows absorbed over the pool the chunk has written."""
        q_nope, q_rope, row = self.project(u, jnp.concatenate(
            [start + jnp.arange(width), positions]))
        o_c, pool = self._chunk_heads(
            q_nope[:width], q_rope[:width], row[:width], pool, layer,
            block_row, start, plen, backend)
        o_s, pool = self._step_heads(
            q_nope[width:], q_rope[width:], row[width:], pool, layer,
            tables, positions, backend)
        return self.out(jnp.concatenate([o_c, o_s])), pool

    def _chunk_heads(self, q_nope, q_rope, row, pool, layer, block_row,
                     start, plen, backend):
        from paddle_tpu.ops.paged_attention import \
            paged_latent_prefill_chunk

        return paged_latent_prefill_chunk(
            q_nope, q_rope, self._padded(row), self.w_kvb(), pool, layer,
            block_row, start, plen, self.scale, backend=backend)

    def _step_heads(self, q_nope, q_rope, row, pool, layer, block_tables,
                    positions, backend):
        from paddle_tpu.ops.paged_attention import paged_latent_decode

        cfg = self.cfg
        dn = cfg.qk_nope_head_dim
        w = self.w_kvb()
        q_abs = jnp.einsum("bhd,rhd->bhr", q_nope, w[..., :dn],
                           preferred_element_type=_F32).astype(q_nope.dtype)
        o, pool = paged_latent_decode(
            self._padded(jnp.concatenate([q_abs, q_rope], -1)),
            self._padded(row), pool, layer, block_tables, positions,
            cfg.kv_lora_rank, self.scale, backend=backend)
        o = jnp.einsum("bhr,rhd->bhd", o, w[..., dn:],
                       preferred_element_type=_F32).astype(q_nope.dtype)
        return o, pool


class DenseMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        std, h, inter = cfg.initializer_range, cfg.hidden_size, \
            cfg.intermediate_size
        self.gate_up = _Leaves(cfg, weight=((h, 2 * inter), std))
        self.down = _Leaves(cfg, weight=((inter, h), std))

    def forward_rows(self, u, live):
        del live
        return _gated_mlp(u, self.gate_up.weight._array,
                          self.down.weight._array), None


class SparseMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        std, h, inter = cfg.initializer_range, cfg.hidden_size, \
            cfg.moe_intermediate_size
        held = cfg.n_routed_experts
        self.router = _Leaves(cfg, weight=((h, cfg.router_experts),
                                           cfg.router_init_std))
        self.shared = _Leaves(cfg, gate_up=((h, 2 * inter), std),
                              down=((inter, h), std))
        self.experts = _Leaves(cfg, w1=((held, h, 2 * inter), std),
                               w2=((held, inter, h), std))

    def route(self, u):
        """-> (ids `[T, k]` among ALL the router's experts, weights
        normalised over the whole chosen set and scaled). The product
        stays float32 at `highest`, as published."""
        cfg = self.cfg
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(_F32), self.router.weight._array.astype(_F32),
            precision=_HIGHEST))
        chosen, ids = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        return ids, cfg.routed_scaling_factor * chosen \
            / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)

    def forward_rows(self, u, live):
        """u `[T, hidden]`; rows where `live` is False (idle lanes, a
        prompt's padding) are routed nowhere. -> (out, counters [4],
        as `distributed/moe.EXPERT_COUNTERS` names them)."""
        from paddle_tpu.distributed.moe import expert_share

        ids, weights = self.route(u)
        ids = jnp.where(live[:, None], ids, -1)
        routed, counters = expert_share(
            u, ids, weights, self.experts.w1._array,
            self.experts.w2._array, self.cfg.expert_offset,
            activation="swiglu")
        return routed.astype(u.dtype) + _gated_mlp(
            u, self.shared.gate_up._array, self.shared.down._array), \
            counters


class PanguUltraMoEBlock(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__(dtype=cfg.dtype)
        gain = ((cfg.hidden_size,), cfg.initializer_range, 1.0)
        self.input_norm = _Leaves(cfg, weight=gain)
        self.attn = LatentAttention(cfg)
        self.post_attn_norm = _Leaves(cfg, weight=gain)
        self.pre_mlp_norm = _Leaves(cfg, weight=gain)
        self.mlp = DenseMLP(cfg) if index < cfg.first_k_dense_replace \
            else SparseMLP(cfg)
        self.post_mlp_norm = _Leaves(cfg, weight=gain)


class PanguUltraMoEForCausalLM(nn.Layer):
    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        std = config.initializer_range
        table = (config.vocab_size, config.hidden_size)
        self.embed = _Leaves(config, weight=(table, std))
        self.layers = nn.LayerList(
            [PanguUltraMoEBlock(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm_f = _Leaves(config, weight=((config.hidden_size,), std,
                                              1.0))
        self.lm_head = _Leaves(config, weight=(table, std))

    def serving_spec(self):
        return PanguUltraMoEServing(self)

    def _head(self, h):
        return jnp.dot(h, self.lm_head.weight._array.T,
                       preferred_element_type=_F32)

    def _walk(self, h, attention, live):
        """The layers in order over rows `h [.., hidden]`;
        `attention(mixer, u, index)` is the caller's (whole, chunk or
        step). -> (final norm'd rows, the expert layers' counters `[4]`,
        folded over the layers by `distributed/moe.fold_expert_counters`)."""
        eps = self.config.rms_norm_eps

        def norm(x, leaves):
            return _rms_norm(x, leaves.weight._array, eps)

        counters = []
        for i, blk in enumerate(self.layers):
            a = attention(blk.attn, norm(h, blk.input_norm), i)
            h = h + norm(a, blk.post_attn_norm)
            u = norm(h, blk.pre_mlp_norm)
            out, c = blk.mlp.forward_rows(u.reshape(-1, u.shape[-1]), live)
            if c is not None:
                counters.append(c)
            h = h + norm(out.reshape(u.shape), blk.post_mlp_norm)
        h = norm(h, self.norm_f)
        if not counters:
            return h, None
        from paddle_tpu.distributed.moe import fold_expert_counters

        return h, fold_expert_counters(jnp.stack(counters))

    def forward(self, input_ids, absorbed=False):
        """Whole sequences, no cache: `[B, S]` ids -> float32 logits
        `[B, S, vocab]`."""
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        h, _ = self._walk(
            self.embed.weight._array[ids],
            lambda mixer, u, i: mixer.whole(u, absorbed),
            jnp.ones(ids.size, bool))
        return Tensor._wrap(self._head(h))


class PanguUltraMoEServing(ServingSpec):
    """What the engine asks of the latent-attention decoder: ONE paged
    pool of latent rows for all the layers (no V pool, no head axis), the
    prefix cache over it, the experts' load and the rows the decode walk
    covered counted a decode step."""

    refuses = {
        "fork": "copy-on-write forks of a latent slot are not tested",
        "spec_decode": "no verify window over the latent pool is built "
                       "(the model's own multi-token-prediction module "
                       "would be the drafter)",
        "handoff": "no export of latent blocks is built",
        "kv_int8": "the latent row is not quantized: its compressed part "
                   "is read by every head, and no grid for it is built",
        "weight_int8": "no int8 plan for the experts and the latent "
                       "projections",
    }

    def __init__(self, model):
        cfg = model.config
        dtype = model.embed.weight._array.dtype
        super().__init__(
            model, cfg.vocab_size, cfg.max_seq_len, dtype,
            PagedLatent(cfg.num_hidden_layers, cfg.pool_row_width,
                        cfg.kv_lora_rank, cfg.num_attention_heads),
            dropout=cfg.dropout)
        from paddle_tpu.distributed.moe import EXPERT_COUNTERS

        self._sparse = cfg.num_hidden_layers > cfg.first_k_dense_replace
        # a decode step's: lanes that decoded; over the expert layers,
        # the experts' counts (`EXPERT_COUNTERS`); the cached rows the
        # lanes' walks covered (one layer's: every layer walks the same
        # rows); whether a prefill chunk's rows rode the step (then the
        # experts' counts are of the ONE product over both)
        self.step_counters = (("decode_live_lanes", "sum"),) \
            + (EXPERT_COUNTERS if self._sparse else ()) \
            + (("mla_context_rows", "sum"),
               ("decode_steps_with_chunk", "sum"))

    @property
    def offers_decode_with_chunk(self):
        """Where one expert product over both row sets reads the experts
        less than two (`distributed/moe.one_product_reads_less`: the
        chip's kernel, at the narrower of an expert's gate-and-up
        product's widths, as `expert_share` resolves it); elsewhere, and
        in a model with no expert layer, the two plain steps."""
        from paddle_tpu.distributed.moe import one_product_reads_less

        cfg = self.model.config
        return self._sparse and one_product_reads_less(
            min(cfg.hidden_size, 2 * cfg.moe_intermediate_size))

    def attention_backend(self, requested, block_size, mp_degree):
        from paddle_tpu.ops.paged_attention import resolve_latent_backend

        kv = self.paged_kv
        return resolve_latent_backend(requested, kv.row_width,
                                      kv.value_width, block_size,
                                      kv.query_heads)

    def decode_pages_per_step(self, block_size, mp_degree, pool_dtype):
        from paddle_tpu.ops.pallas.paged_attention import \
            latent_pages_per_step

        return latent_pages_per_step(block_size, self.paged_kv.row_width,
                                     pool_dtype)

    def logits(self, hidden, mp_axis=None):
        return Tensor._wrap(self.model._head(hidden._array))

    def prefill_chunk(self, tokens, start, kpool, vpool, block_row, plen,
                      backend="auto", mp_axis=None, kv_scales=None,
                      lora=None):
        ids = tokens._array                               # [1, C]
        width = ids.shape[1]
        pool = [kpool._array]
        row, s0, n = block_row._array, start._array, plen._array

        def attention(mixer, u, i):
            out, pool[0] = mixer.chunk(u[0], pool[0], i, row, s0, n,
                                       backend)
            return out[None]

        h, _ = self.model._walk(
            self.model.embed.weight._array[ids], attention,
            jnp.arange(width) < jnp.clip(n - s0, 0, width))
        return StepOut(Tensor._wrap(h), Tensor._wrap(pool[0]), None)

    def decode(self, tokens, positions, kpool, vpool, block_tables,
               backend="auto", mp_axis=None, kv_scales=None, lora=None):
        ids = tokens._array                               # [slots, 1]
        pos, tables = positions._array, block_tables._array
        # a lane that decodes holds at least one block; the others ride
        # the all-null table
        live = tables[:, 0] != 0
        pool = [kpool._array]

        def attention(mixer, u, i):
            out, pool[0] = mixer.step(u[:, 0], pool[0], i, tables, pos,
                                      backend)
            return out[:, None]

        h, moe = self.model._walk(
            self.model.embed.weight._array[ids], attention, live)
        return StepOut(Tensor._wrap(h), Tensor._wrap(pool[0]), None,
                       counters=self._counters(live, pos, moe, 0))

    def decode_with_chunk(self, chunk_tokens, start, block_row, plen,
                          tokens, positions, block_tables, kpool, vpool,
                          backend="auto"):
        """`prefill_chunk` and `decode` as one walk over the chunk's
        rows and the decode rows together: every projection, the dense
        layers, the shared expert, the router and the experts' products
        cross their weights once for both row sets; each row set keeps
        its own attention (`LatentAttention.chunk_and_step`)."""
        ids_c, ids_s = chunk_tokens._array[0], tokens._array[:, 0]
        width = ids_c.shape[0]
        row, s0, n = block_row._array, start._array, plen._array
        pos, tables = positions._array, block_tables._array
        live = tables[:, 0] != 0
        pool = [kpool._array]

        def attention(mixer, u, i):
            out, pool[0] = mixer.chunk_and_step(
                u, width, pool[0], i, row, s0, n, tables, pos, backend)
            return out

        h, moe = self.model._walk(
            self.model.embed.weight._array[
                jnp.concatenate([ids_c, ids_s])], attention,
            jnp.concatenate([jnp.arange(width) < jnp.clip(n - s0, 0, width),
                             live]))
        return (StepOut(Tensor._wrap(h[None, :width]),
                        Tensor._wrap(pool[0]), None,
                        counters=self._counters(live, pos, moe, 1)),
                Tensor._wrap(h[width:, None]))

    @staticmethod
    def _counters(live, pos, moe, with_chunk):
        """`step_counters`' values of one decode step over the lanes
        `live` at `pos`."""
        parts = [jnp.sum(live, dtype=jnp.int32).reshape(1)]
        if moe is not None:
            parts.append(moe)
        parts.append(jnp.sum(jnp.where(live, pos + 1, 0),
                             dtype=jnp.int32).reshape(1))
        return jnp.concatenate(parts + [jnp.full(1, with_chunk, jnp.int32)])
