"""`nemotron_h` — a hybrid decoder built from a layer-pattern string
(NVIDIA Nemotron-H / Nemotron-3): every block is
`x <- x + mixer(RMSNorm(x))` with ONE mixer, chosen by the character of
`hybrid_override_pattern`:

- `M` Mamba-2 (state-space): conv window and state matrices carried a
  slot, chunked scan for a prefill chunk, one-step recurrence at decode
  (`ops/ssm.py`);
- `*` attention with grouped KV heads and no position encoding, over the
  engine's paged pool (`ops/paged_attention.py`);
- `E` LatentMoE: a sigmoid router over ALL published experts, the experts
  HELD HERE (`n_routed_experts` of `router_experts`, from
  `expert_offset`) computed dropless by a grouped matmul in a narrow
  latent, plus a shared expert (`distributed/moe.expert_share`).

Final RMSNorm, untied head, no biases but the conv's. Served by
`GenerationEngine(model)` like any model: `serving_spec()` declares the
two kinds of state a slot holds and hands over the step functions.
Parameters are created in the run dtype (`config.dtype`); `init="zeros"`
skips the random draw for a caller that binds every leaf itself.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference.serving_spec import PagedKV, ServingSpec, \
    SlotState, StepOut
from paddle_tpu.models._blocks import Leaves as _Leaves, dot as _dot, \
    leaf as _leaf, rms_norm as _rms_norm

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    hybrid_override_pattern: str = "MEMEMEMEM*E"
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512        # experts HELD here
    router_experts: int = None         # the router's width (all experts)
    expert_offset: int = 0             # index of the first expert held
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    initializer_range: float = 0.02
    conv_init_std: float = None
    dtype: str = "float32"
    init: str = "normal"               # or "zeros": leaves bound later
    dropout: float = 0.0

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if self.conv_init_std is None:
            self.conv_init_std = self.initializer_range
        bad = set(self.hybrid_override_pattern) - set("M*E")
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)} in "
                             "hybrid_override_pattern (M, * and E)")
        if self.expert_offset + self.n_routed_experts \
                > self.router_experts:
            raise ValueError("the experts held lie outside the router")

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def tiny(pattern="ME*E", vocab=128, **kw):
        base = dict(
            vocab_size=vocab, hidden_size=64,
            hybrid_override_pattern=pattern, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16,
            chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, n_routed_experts=8, num_experts_per_tok=3,
            moe_latent_size=32, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=64, max_seq_len=128,
            # at 64 wide, N(0, 0.02) would leave the experts' and the
            # scan's parts under any tolerance: nothing would test them
            initializer_range=0.1, conv_init_std=0.3)
        base.update(kw)
        return NemotronHConfig(**base)


def _relu2(x):
    return jnp.square(jnp.maximum(x.astype(_F32), 0.0)).astype(x.dtype)


class MambaMixer(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        std, heads = cfg.initializer_range, cfg.mamba_num_heads
        self.in_proj = _Leaves(cfg, weight=(
            (cfg.hidden_size, cfg.mamba_inner + cfg.conv_dim + heads),
            std))
        self.conv = _Leaves(
            cfg, weight=((cfg.conv_kernel, cfg.conv_dim),
                         cfg.conv_init_std),
            bias=((cfg.conv_dim,), std))
        self.dt_bias = _leaf(self, cfg, (heads,), std)
        self.A_log = _leaf(self, cfg, (heads,), std)
        self.D = _leaf(self, cfg, (heads,), std, 1.0)
        self.norm = _Leaves(cfg, weight=((cfg.mamba_inner,), std, 1.0))
        self.out_proj = _Leaves(cfg, weight=(
            (cfg.mamba_inner, cfg.hidden_size), std))

    def _split(self, u):
        cfg = self.cfg
        proj = _dot(u, self.in_proj.weight._array)
        inner, conv = cfg.mamba_inner, cfg.conv_dim
        dt = jax.nn.softplus(proj[..., inner + conv:].astype(_F32)
                             + self.dt_bias._array.astype(_F32))
        return proj[..., :inner], proj[..., inner:inner + conv], dt

    def _heads(self, xbc):
        cfg = self.cfg
        lead = xbc.shape[:-1]
        inner, gn = cfg.mamba_inner, cfg.n_groups * cfg.ssm_state_size
        shape = lead + (cfg.n_groups, cfg.ssm_state_size)
        return (xbc[..., :inner].reshape(
                    lead + (cfg.mamba_num_heads, cfg.mamba_head_dim)),
                xbc[..., inner:inner + gn].reshape(shape),
                xbc[..., inner + gn:].reshape(shape))

    def _gate_out(self, y, gate, dtype):
        """RMSNorm within each group of `y * SiLU(z)`, then W_out."""
        cfg = self.cfg
        lead = y.shape[:-2]
        y = y.reshape(lead + (cfg.mamba_inner,)).astype(_F32) \
            * jax.nn.silu(gate.astype(_F32))
        y = _rms_norm(y.reshape(lead + (cfg.n_groups, -1)),
                      self.norm.weight._array.reshape(cfg.n_groups, -1),
                      cfg.norm_eps)
        return _dot(y.reshape(lead + (cfg.mamba_inner,)).astype(dtype),
                    self.out_proj.weight._array)

    def _a(self):
        return -jnp.exp(self.A_log._array.astype(_F32))

    def _scan_chunk(self, xbc, dt, conv_state, ssm_state, n_valid):
        from paddle_tpu.ops import ssm

        xbc, conv_state = ssm.causal_conv_chunk(
            xbc, conv_state, self.conv.weight._array,
            self.conv.bias._array, n_valid)
        x, b, c = self._heads(xbc)
        y, ssm_state = ssm.ssd_chunk_scan(
            x, dt, self._a(), b, c, self.D._array, ssm_state, n_valid,
            self.cfg.chunk_size)
        return y, conv_state, ssm_state

    def _scan_step(self, xbc, dt, conv_pool, ssm_pool, layer, rows):
        from paddle_tpu.ops import ssm

        xbc, carried = ssm.causal_conv_step(
            xbc, conv_pool[layer, rows], self.conv.weight._array,
            self.conv.bias._array)
        conv_pool = conv_pool.at[layer, rows].set(carried)
        x, b, c = self._heads(xbc)
        y, ssm_pool = ssm.ssm_decode_step(
            ssm_pool, layer, rows, x, dt, self._a(),
            self.D._array.astype(_F32), b, c)
        return y, conv_pool, ssm_pool

    def chunk(self, u, conv_state, ssm_state, n_valid):
        """One slot's chunk `u [C, hidden]` from its carried state
        (`conv_state [K-1, D]`, `ssm_state [heads, P, N]`); rows at and
        past `n_valid` are padding. -> (out, conv_state, ssm_state)."""
        gate, xbc, dt = self._split(u)
        y, conv_state, ssm_state = self._scan_chunk(
            xbc, dt, conv_state, ssm_state, n_valid)
        return self._gate_out(y, gate, u.dtype), conv_state, ssm_state

    def step(self, u, conv_pool, ssm_pool, layer, rows):
        """One token a slot, `u [slots, hidden]`, over the state pools
        (`rows [slots]`, 0 = the null row)."""
        gate, xbc, dt = self._split(u)
        y, conv_pool, ssm_pool = self._scan_step(
            xbc, dt, conv_pool, ssm_pool, layer, rows)
        return self._gate_out(y, gate, u.dtype), conv_pool, ssm_pool

    def chunk_and_step(self, u, width, conv_pool, ssm_pool, layer, row,
                       n_valid, rows):
        """`chunk` over the first `width` rows of `u` (one slot's chunk,
        from and to row `row` of the pools) and `step` over the others
        (one token a slot, `rows`), with ONE input and ONE output
        projection over all the rows. The chunk's row is written before
        the step's kernel updates the pool in place: one chain, no copy
        of the pool."""
        gate, xbc, dt = self._split(u)
        y_c, conv_new, ssm_new = self._scan_chunk(
            xbc[:width], dt[:width], conv_pool[layer, row],
            ssm_pool[layer, row], n_valid)
        y_s, conv_pool, ssm_pool = self._scan_step(
            xbc[width:], dt[width:],
            conv_pool.at[layer, row].set(conv_new),
            ssm_pool.at[layer, row].set(ssm_new), layer, rows)
        y = jnp.concatenate([y_c.astype(_F32), y_s.astype(_F32)])
        return self._gate_out(y, gate, u.dtype), conv_pool, ssm_pool


class AttentionMixer(nn.Layer):
    """Grouped-KV attention, no position encoding."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        std, h, d = cfg.initializer_range, cfg.hidden_size, cfg.head_dim
        q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        self.q_proj = _Leaves(cfg, weight=((h, q), std))
        self.k_proj = _Leaves(cfg, weight=((h, kv), std))
        self.v_proj = _Leaves(cfg, weight=((h, kv), std))
        self.o_proj = _Leaves(cfg, weight=((q, h), std))

    def qkv(self, u):
        """u `[B, S, hidden]` -> q `[B,S,heads,D]`, k, v `[B,S,kv,D]`."""
        d = self.cfg.head_dim
        lead = u.shape[:-1]
        return tuple(_dot(u, p.weight._array).reshape(lead + (-1, d))
                     for p in (self.q_proj, self.k_proj, self.v_proj))

    def out(self, o):
        return _dot(o.reshape(o.shape[:-2] + (-1,)),
                    self.o_proj.weight._array)

    def whole(self, u):
        """Causal attention over whole sequences, no cache."""
        q, k, v = self.qkv(u)
        g = q.shape[2] // k.shape[2]
        b, s, kvh, d = k.shape
        qg = q.reshape(b, s, kvh, g, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=_F32) / (d ** 0.5)
        keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(keep, logits, -1e30), axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                       preferred_element_type=_F32).astype(u.dtype)
        return self.out(o.reshape(b, s, kvh * g, d))


class LatentMoE(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        std, h = cfg.initializer_range, cfg.hidden_size
        lat, inter = cfg.moe_latent_size, cfg.moe_intermediate_size
        held = cfg.n_routed_experts
        self.router = _Leaves(cfg, weight=((h, cfg.router_experts), std),
                              bias=((cfg.router_experts,), std))
        self.down = _Leaves(cfg, weight=((h, lat), std))
        self.up = _Leaves(cfg, weight=((lat, h), std))
        shared = cfg.moe_shared_expert_intermediate_size
        self.shared = _Leaves(cfg, w1=((h, shared), std),
                              w2=((shared, h), std))
        self.experts = _Leaves(cfg, w1=((held, lat, inter), std),
                               w2=((held, inter, lat), std))

    def route(self, u):
        """-> (ids `[T, k]` among ALL the router's experts, weights
        normalised over the whole chosen set). The product stays float32
        at `highest`, as published."""
        cfg = self.cfg
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(_F32), self.router.weight._array.astype(_F32),
            precision=_HIGHEST))
        _, ids = jax.lax.top_k(
            scores + self.router.bias._array.astype(_F32),
            cfg.num_experts_per_tok)
        chosen = jnp.take_along_axis(scores, ids, -1)
        return ids, cfg.routed_scaling_factor * chosen \
            / jnp.sum(chosen, -1, keepdims=True)

    def forward_rows(self, u, live):
        """u `[T, hidden]`; rows where `live` is False (idle lanes, a
        prompt's padding) are routed nowhere. -> (out, counters [4],
        as `distributed/moe.EXPERT_COUNTERS` names them)."""
        from paddle_tpu.distributed.moe import expert_share

        ids, weights = self.route(u)
        ids = jnp.where(live[:, None], ids, -1)
        routed, counters = expert_share(
            _dot(u, self.down.weight._array), ids, weights,
            self.experts.w1._array, self.experts.w2._array,
            self.cfg.expert_offset)
        shared = _dot(_relu2(_dot(u, self.shared.w1._array)),
                      self.shared.w2._array)
        return _dot(routed.astype(u.dtype), self.up.weight._array) \
            + shared, counters


_MIXERS = {"M": MambaMixer, "*": AttentionMixer, "E": LatentMoE}


class NemotronHBlock(nn.Layer):
    def __init__(self, cfg, kind):
        super().__init__(dtype=cfg.dtype)
        self.kind = kind
        self.norm = _Leaves(cfg, weight=((cfg.hidden_size,),
                                         cfg.initializer_range, 1.0))
        self.mixer = _MIXERS[kind](cfg)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        std = config.initializer_range
        table = (config.vocab_size, config.hidden_size)
        self.embed = _Leaves(config, weight=(table, std))
        self.layers = nn.LayerList(
            [NemotronHBlock(config, kind)
             for kind in config.hybrid_override_pattern])
        self.norm_f = _Leaves(config, weight=((config.hidden_size,), std,
                                              1.0))
        self.lm_head = _Leaves(config, weight=(table, std))

    def serving_spec(self):
        return NemotronHServing(self)

    def _head(self, h):
        """Final norm'd rows -> float32 logits over the rows held."""
        return jnp.dot(h, self.lm_head.weight._array.T,
                       preferred_element_type=_F32)

    def forward(self, input_ids):
        """Whole sequences, no cache: `[B, S]` ids -> float32 logits
        `[B, S, vocab]`. Every sequence starts from an empty state."""
        cfg = self.config
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        h = self.embed.weight._array[ids]
        for blk in self.layers:
            u = _rms_norm(h, blk.norm.weight._array, cfg.norm_eps)
            if blk.kind == "M":
                conv0 = jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim),
                                  h.dtype)
                ssm0 = jnp.zeros((cfg.mamba_num_heads, cfg.mamba_head_dim,
                                  cfg.ssm_state_size), _F32)
                out = jax.vmap(lambda row: blk.mixer.chunk(
                    row, conv0, ssm0, s)[0])(u)
            elif blk.kind == "*":
                out = blk.mixer.whole(u)
            else:
                out, _ = blk.mixer.forward_rows(
                    u.reshape(b * s, -1), jnp.ones(b * s, bool))
                out = out.reshape(b, s, -1)
            h = h + out
        return Tensor._wrap(self._head(_rms_norm(
            h, self.norm_f.weight._array, cfg.norm_eps)))


class NemotronHServing(ServingSpec):
    """What the engine asks of the hybrid decoder: paged K and V for the
    `*` layers alone (KV heads, not query heads), a conv window and a
    state matrix a slot for every `M` layer, the experts' load counted a
    decode step. What needs a snapshot of the recurrent state is refused,
    each with its reason."""

    _NEEDS_SNAPSHOT = (
        "a recurrent layer's state is not bounded by a position, so {} "
        "would need a snapshot of it at the block boundary (state "
        "snapshots are not built yet)")
    refuses = {
        "prefix_cache": _NEEDS_SNAPSHOT.format("a prefix hit"),
        "fork": _NEEDS_SNAPSHOT.format("a copy-on-write fork"),
        "spec_decode": _NEEDS_SNAPSHOT.format(
            "rolling back a rejected speculative window"),
        "handoff": "the slot's recurrent state would have to travel with "
                   "its blocks, and no export of it is built yet",
        "kv_int8": "the grouped-KV paged path is not quantized",
        "weight_int8": "no int8 plan for the experts and the scan",
    }

    def __init__(self, model):
        cfg = model.config
        kinds = cfg.hybrid_override_pattern
        dtype = model.embed.weight._array.dtype
        super().__init__(
            model, cfg.vocab_size, cfg.max_seq_len, dtype,
            PagedKV(kinds.count("*"), cfg.num_key_value_heads,
                    cfg.head_dim, cfg.num_attention_heads),
            dropout=cfg.dropout)
        n_m = kinds.count("M")
        self.slot_state = (
            SlotState("conv", n_m, (cfg.conv_kernel - 1, cfg.conv_dim),
                      dtype),
            SlotState("ssm", n_m, (cfg.mamba_num_heads,
                                   cfg.mamba_head_dim,
                                   cfg.ssm_state_size), _F32),
        ) if n_m else ()
        from paddle_tpu.distributed.moe import EXPERT_COUNTERS

        # a decode step's: lanes that decoded; over the E layers, the
        # experts' counts (`EXPERT_COUNTERS`); whether a prefill chunk's
        # rows rode the step (then the experts' counts are of the ONE
        # product over both)
        self.step_counters = (("decode_live_lanes", "sum"),) \
            + (EXPERT_COUNTERS if "E" in kinds else ()) \
            + (("decode_steps_with_chunk", "sum"),)

    def logits(self, hidden, mp_axis=None):
        return Tensor._wrap(self.model._head(hidden._array))

    @property
    def offers_decode_with_chunk(self):
        """Where one expert product over both row sets reads the experts
        less than two (`distributed/moe.one_product_reads_less`: the
        chip's kernel; this model's experts are each one weight block at
        the published widths); elsewhere the two plain steps."""
        from paddle_tpu.distributed.moe import one_product_reads_less

        cfg = self.model.config
        return one_product_reads_less(min(cfg.moe_latent_size,
                                          cfg.moe_intermediate_size))

    def _walk(self, h, mamba, attention, moe_live):
        """The layers in order; `mamba(mixer, u, index)` and
        `attention(mixer, u, index)` are the caller's (chunk, step or
        both). -> (final norm'd rows, the E layers' counters `[4]` or
        None)."""
        model, cfg = self.model, self.model.config
        i_m = i_a = 0
        counters = []
        for blk in model.layers:
            u = _rms_norm(h, blk.norm.weight._array, cfg.norm_eps)
            if blk.kind == "M":
                out = mamba(blk.mixer, u, i_m)
                i_m += 1
            elif blk.kind == "*":
                out = attention(blk.mixer, u, i_a)
                i_a += 1
            else:
                flat = u.reshape(-1, u.shape[-1])
                out, c = blk.mixer.forward_rows(flat, moe_live)
                out = out.reshape(u.shape)
                counters.append(c)
            h = h + out
        h = _rms_norm(h, model.norm_f.weight._array, cfg.norm_eps)
        if not counters:
            return h, None
        from paddle_tpu.distributed.moe import fold_expert_counters

        return h, fold_expert_counters(jnp.stack(counters))

    @staticmethod
    def _counters(lanes_live, moe, with_chunk):
        """`step_counters`' values of one decode step."""
        parts = [jnp.sum(lanes_live, dtype=jnp.int32).reshape(1)]
        if moe is not None:
            parts.append(moe)
        return jnp.concatenate(
            parts + [jnp.full(1, with_chunk, jnp.int32)])

    def prefill_chunk(self, tokens, start, kpool, vpool, block_row, plen,
                      backend="auto", mp_axis=None, kv_scales=None,
                      lora=None, slot_state=(), state_row=None):
        from paddle_tpu.ops.paged_attention import paged_prefill_chunk

        ids = tokens._array                               # [1, C]
        width = ids.shape[1]
        n_valid = jnp.clip(plen._array - start._array, 0, width)
        state = list(slot_state)
        pools = [kpool, vpool]

        def mamba(mixer, u, i):
            conv, ssm = state
            out, c_new, s_new = mixer.chunk(
                u[0], conv[i, state_row], ssm[i, state_row], n_valid)
            state[0] = conv.at[i, state_row].set(c_new)
            state[1] = ssm.at[i, state_row].set(s_new)
            return out[None]

        def attention(mixer, u, i):
            q, k, v = mixer.qkv(u)
            o, pools[0], pools[1] = paged_prefill_chunk(
                q, k, v, pools[0], pools[1], i, block_row, start, plen)
            return mixer.out(o._array)

        # the experts' load is counted a DECODE step: a chunk's is not
        # handed on (its final token alone is waited for)
        h, _ = self._walk(
            self.model.embed.weight._array[ids], mamba, attention,
            jnp.arange(width) < n_valid)
        return StepOut(Tensor._wrap(h), pools[0], pools[1],
                       slot_state=tuple(state))

    def _lanes_live(self, slots, slot_state, state_rows):
        """(the lanes' rows of state, which lanes decode): a lane that
        decodes holds a state row; where the model keeps no such state
        every lane counts."""
        rows = jnp.zeros(slots, jnp.int32) if state_rows is None \
            else state_rows
        return rows, rows > 0 if slot_state else jnp.ones(slots, bool)

    def decode(self, tokens, positions, kpool, vpool, block_tables,
               backend="auto", mp_axis=None, kv_scales=None, lora=None,
               slot_state=(), state_rows=None):
        from paddle_tpu.ops.paged_attention import paged_attention_step

        ids = tokens._array                               # [slots, 1]
        rows, live = self._lanes_live(ids.shape[0], slot_state,
                                      state_rows)
        state = list(slot_state)
        pools = [kpool, vpool]

        def mamba(mixer, u, i):
            out, state[0], state[1] = mixer.step(
                u[:, 0], state[0], state[1], i, rows)
            return out[:, None]

        def attention(mixer, u, i):
            q, k, v = mixer.qkv(u)
            o, pools[0], pools[1] = paged_attention_step(
                q, k, v, pools[0], pools[1], i, block_tables, positions,
                backend=backend)
            return mixer.out(o._array)

        h, moe = self._walk(
            self.model.embed.weight._array[ids], mamba, attention, live)
        return StepOut(Tensor._wrap(h), pools[0], pools[1],
                       slot_state=tuple(state),
                       counters=self._counters(live, moe, 0))

    def decode_with_chunk(self, chunk_tokens, start, block_row, plen,
                          tokens, positions, block_tables, kpool, vpool,
                          backend="auto", slot_state=(), state_row=None,
                          state_rows=None):
        """`prefill_chunk` and `decode` as one walk over the chunk's
        rows and the decode rows together: every weight crosses once —
        above all the experts', which a chunk and a decode step each
        touch nearly all of. The scan and the attention run each row
        set's own form (`mixer.chunk_and_step`, the two paged ops); an
        `E` layer makes ONE product over all the rows."""
        from paddle_tpu.ops.paged_attention import \
            paged_attention_step, paged_prefill_chunk

        ids_c, ids_s = chunk_tokens._array[0], tokens._array[:, 0]
        width = ids_c.shape[0]
        n_valid = jnp.clip(plen._array - start._array, 0, width)
        rows, live = self._lanes_live(ids_s.shape[0], slot_state,
                                      state_rows)
        state = list(slot_state)
        pools = [kpool, vpool]

        def mamba(mixer, u, i):
            out, state[0], state[1] = mixer.chunk_and_step(
                u, width, state[0], state[1], i, state_row, n_valid, rows)
            return out

        def attention(mixer, u, i):
            q, k, v = mixer.qkv(u)                  # [C + slots, h, D]
            o_c, pools[0], pools[1] = paged_prefill_chunk(
                q[None, :width], k[None, :width], v[None, :width],
                pools[0], pools[1], i, block_row, start, plen)
            o_s, pools[0], pools[1] = paged_attention_step(
                q[width:, None], k[width:, None], v[width:, None],
                pools[0], pools[1], i, block_tables, positions,
                backend=backend)
            return mixer.out(jnp.concatenate(
                [o_c._array[0], o_s._array[:, 0]]))

        h, moe = self._walk(
            self.model.embed.weight._array[
                jnp.concatenate([ids_c, ids_s])], mamba, attention,
            jnp.concatenate([jnp.arange(width) < n_valid, live]))
        return (StepOut(Tensor._wrap(h[None, :width]), pools[0], pools[1],
                        slot_state=tuple(state),
                        counters=self._counters(live, moe, 1)),
                Tensor._wrap(h[width:, None]))
