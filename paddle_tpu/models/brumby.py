"""`brumby` — Brumby-14B-Base (Manifest AI): the Qwen3 decoder block with
every softmax attention replaced by POWER RETENTION (`ops/retention.py`).
A layer (u the normed input, `i` a query head of KV head h):

    q_t,i = rot(rmsnorm_head(W_q u_t))    k_t = rot(rmsnorm_head(W_k u_t))
    v_t = W_v u_t                         log g_t = logsigmoid(W_g u_t + b_g)
    a_tj  = (q_t,i . k_j / sqrt(d))^2 * prod_{l=j+1..t} g_l
    y_t,i = sum_j a_tj v_j / (sum_j a_tj + eps)

then `W_o`, the residual, RMSNorm, the SwiGLU MLP, the residual. There is
NO cache of keys and values: the layer is served from a state of fixed
size a slot (`S [kv_heads, D_run, d]` and its normaliser `z [kv_heads,
D_run]`, float32), carried from prefill chunk to prefill chunk and updated
a token at decode. `serving_spec()` declares exactly that: `paged_kv=None`
and two `SlotState`s; the engine then holds no pool and no block table.

Final RMSNorm, untied head, no bias but the gate's. Parameters are created
in the run dtype (`config.dtype`); `init="zeros"` skips the random draw for
a caller that binds every leaf itself.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference.serving_spec import ServingSpec, SlotState, \
    StepOut
from paddle_tpu.models._blocks import Leaves, dot, gated_mlp, rms_norm, \
    rotary
from paddle_tpu.ops import retention

_F32 = jnp.float32


@dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_seq_len: int = 32768
    chunk_size: int = 128             # rows of a prefill sub-chunk
    initializer_range: float = 0.02
    dtype: str = "float32"
    init: str = "normal"              # or "zeros": leaves bound later
    dropout: float = 0.0

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the KV heads")
        if self.head_dim % 2:
            raise ValueError("the rotary turns pairs of values")

    @property
    def state_width(self):
        """`D_run`: rows of a KV head's state (`phi`'s tiled form)."""
        return retention.state_width(self.head_dim)

    @staticmethod
    def tiny(layers=2, vocab=128, **kw):
        base = dict(
            vocab_size=vocab, hidden_size=64, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=96, max_seq_len=128, rope_theta=10000.0,
            chunk_size=8,
            # at 64 wide, N(0, 0.02) would leave the mixer's part under
            # any tolerance
            initializer_range=0.1)
        base.update(kw)
        return BrumbyConfig(**base)


class PowerRetention(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        std, h, d = cfg.initializer_range, cfg.hidden_size, cfg.head_dim
        q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        self.q_proj = Leaves(cfg, weight=((h, q), std))
        self.k_proj = Leaves(cfg, weight=((h, kv), std))
        self.v_proj = Leaves(cfg, weight=((h, kv), std))
        self.g_proj = Leaves(
            cfg, weight=((h, cfg.num_key_value_heads), std),
            bias=((cfg.num_key_value_heads,), std))
        self.q_norm = Leaves(cfg, weight=((d,), std, 1.0))
        self.k_norm = Leaves(cfg, weight=((d,), std, 1.0))
        self.o_proj = Leaves(cfg, weight=((q, h), std))

    def project(self, u, positions):
        """u `[.., hidden]` at `positions [..]` -> (q `[.., kv_heads, r,
        d]`, k, v `[.., kv_heads, d]`, log_g `[.., kv_heads]` float32):
        q and k normed a head and turned."""
        cfg = self.cfg
        d, kvh = cfg.head_dim, cfg.num_key_value_heads
        lead = u.shape[:-1]

        def heads(p):
            return dot(u, p.weight._array).reshape(lead + (-1, d))

        q = rotary(rms_norm(heads(self.q_proj), self.q_norm.weight._array,
                            cfg.rms_norm_eps), positions, cfg.rope_theta)
        k = rotary(rms_norm(heads(self.k_proj), self.k_norm.weight._array,
                            cfg.rms_norm_eps), positions, cfg.rope_theta)
        log_g = jax.nn.log_sigmoid(
            dot(u, self.g_proj.weight._array).astype(_F32)
            + self.g_proj.bias._array.astype(_F32))
        return q.reshape(lead + (kvh, -1, d)), k, heads(self.v_proj), \
            log_g

    def out(self, y, dtype):
        """y `[.., kv_heads, r, d]` float32 -> `[.., hidden]`."""
        return dot(y.reshape(y.shape[:-3] + (-1,)).astype(dtype),
                   self.o_proj.weight._array)

    def chunk(self, u, state, norm, start, n_valid, backend):
        """One slot's chunk `u [C, hidden]` at positions `start ..` from
        its carried `state` and `norm`; rows at and past `n_valid` are
        padding. -> (out, state, norm)."""
        q, k, v, log_g = self.project(u, start + jnp.arange(u.shape[0]))
        # positional: the benchmark's fault seams wrap this call
        y, state, norm = retention.power_retention_chunk(
            q, k, v, log_g, state, norm, n_valid, self.cfg.chunk_size,
            backend)
        return self.out(y, u.dtype), state, norm

    def step(self, u, pool, norm_pool, layer, rows, positions, backend):
        """One token a slot, `u [slots, hidden]`, over the state pools
        (`rows [slots]`, 0 = the null row)."""
        q, k, v, log_g = self.project(u, positions)
        y, pool, norm_pool = retention.power_retention_decode(
            pool, norm_pool, layer, rows, q, k, v, log_g, backend=backend)
        return self.out(y, u.dtype), pool, norm_pool


class BrumbyBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        std, h = cfg.initializer_range, cfg.hidden_size
        gain = ((h,), std, 1.0)
        self.input_norm = Leaves(cfg, weight=gain)
        self.mixer = PowerRetention(cfg)
        self.post_norm = Leaves(cfg, weight=gain)
        self.mlp = Leaves(cfg,
                          gate_up=((h, 2 * cfg.intermediate_size), std),
                          down=((cfg.intermediate_size, h), std))


class BrumbyForCausalLM(nn.Layer):
    def __init__(self, config: BrumbyConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        std = config.initializer_range
        table = (config.vocab_size, config.hidden_size)
        self.embed = Leaves(config, weight=(table, std))
        self.layers = nn.LayerList(
            [BrumbyBlock(config)
             for _ in range(config.num_hidden_layers)])
        self.norm_f = Leaves(config, weight=((config.hidden_size,), std,
                                             1.0))
        self.lm_head = Leaves(config, weight=(table, std))

    def serving_spec(self):
        return BrumbyServing(self)

    def _head(self, h):
        return jnp.dot(h, self.lm_head.weight._array.T,
                       preferred_element_type=_F32)

    def _walk(self, h, mixer):
        """The layers in order over rows `h [.., hidden]`; `mixer(layer's
        PowerRetention, u, index)` is the caller's (chunk or step).
        -> the final norm'd rows."""
        eps = self.config.rms_norm_eps
        for i, blk in enumerate(self.layers):
            h = h + mixer(blk.mixer, rms_norm(
                h, blk.input_norm.weight._array, eps), i)
            h = h + gated_mlp(
                rms_norm(h, blk.post_norm.weight._array, eps),
                blk.mlp.gate_up._array, blk.mlp.down._array)
        return rms_norm(h, self.norm_f.weight._array, eps)

    def forward(self, input_ids):
        """Whole sequences, no state kept: `[B, S]` ids -> float32 logits
        `[B, S, vocab]`. Every sequence starts from an empty state."""
        cfg = self.config
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        s = ids.shape[1]
        kvh, d_run = cfg.num_key_value_heads, cfg.state_width
        state0 = jnp.zeros((kvh, d_run, cfg.head_dim), _F32)
        norm0 = jnp.zeros((kvh, d_run), _F32)

        def mixer(m, u, i):
            return jax.vmap(lambda row: m.chunk(
                row, state0, norm0, 0, s, "xla")[0])(u)

        return Tensor._wrap(self._head(self._walk(
            self.embed.weight._array[ids], mixer)))


class BrumbyServing(ServingSpec):
    """What the engine asks of the retention decoder: NO paged cache
    (`paged_kv=None`: the engine holds no pool and hands the steps None
    for the pools and the block tables), a state matrix and a normaliser
    a slot for every layer. What needs a snapshot of that state, and what
    hangs on blocks that do not exist, is refused, each with its reason."""

    _NEEDS_SNAPSHOT = (
        "a retention layer's state is not bounded by a position, so {} "
        "would need a snapshot of it (33.8 MB a layer a slot; state "
        "snapshots are not built yet)")
    _NO_BLOCKS = "the model keeps no paged cache: there are no blocks {}"
    refuses = {
        "prefix_cache": _NEEDS_SNAPSHOT.format("a prefix hit"),
        "fork": _NEEDS_SNAPSHOT.format("a copy-on-write fork"),
        "spec_decode": _NEEDS_SNAPSHOT.format(
            "rolling back a rejected speculative window"),
        "handoff": _NO_BLOCKS.format(
            "to hand off, and no export of the slot's state is built"),
        "kv_int8": _NO_BLOCKS.format("to quantize"),
        "weight_int8": "no int8 plan for the retention projections",
    }
    #: a decode step's: lanes that decoded (each moves its state through
    #: the chip once a layer)
    step_counters = (("decode_live_lanes", "sum"),)
    #: a prefill chunk's: the prompt rows it computed (padding left out)
    chunk_counters = (("prefill_rows_computed", "sum"),)

    def __init__(self, model):
        cfg = model.config
        super().__init__(model, cfg.vocab_size, cfg.max_seq_len,
                         model.embed.weight._array.dtype, None,
                         dropout=cfg.dropout)
        layers, kvh = cfg.num_hidden_layers, cfg.num_key_value_heads
        self.slot_state = (
            SlotState("ret_state", layers,
                      (kvh, cfg.state_width, cfg.head_dim), _F32),
            SlotState("ret_norm", layers, (kvh, cfg.state_width), _F32))

    def attention_backend(self, requested, block_size, mp_degree):
        """The engine's one backend choice picks the retention's form,
        the decode step's and the prefill chunk's alike: `pallas` the
        kernels, `dense` the XLA forms."""
        if requested not in ("auto", "dense", "pallas"):
            raise ValueError("attention_backend must be auto, dense or "
                             f"pallas, got {requested!r}")
        resolved = retention.resolve_retention_backend(
            "xla" if requested == "dense" else requested,
            self.model.config.head_dim)
        return "dense" if resolved == "xla" else resolved

    def logits(self, hidden, mp_axis=None):
        return Tensor._wrap(self.model._head(hidden._array))

    def prefill_chunk(self, tokens, start, kpool, vpool, block_row, plen,
                      backend="auto", mp_axis=None, kv_scales=None,
                      lora=None, slot_state=(), state_row=None):
        ids = tokens._array                               # [1, C]
        width = ids.shape[1]
        s0 = start._array
        n_valid = jnp.clip(plen._array - s0, 0, width)
        state = list(slot_state)
        form = "xla" if backend == "dense" else backend

        def mixer(m, u, i):
            out, s_new, z_new = m.chunk(
                u[0], state[0][i, state_row], state[1][i, state_row],
                s0, n_valid, form)
            state[0] = state[0].at[i, state_row].set(s_new)
            state[1] = state[1].at[i, state_row].set(z_new)
            return out[None]

        h = self.model._walk(self.model.embed.weight._array[ids], mixer)
        return StepOut(Tensor._wrap(h), None, None,
                       slot_state=tuple(state),
                       counters=n_valid.astype(jnp.int32).reshape(1))

    def decode(self, tokens, positions, kpool, vpool, block_tables,
               backend="auto", mp_axis=None, kv_scales=None, lora=None,
               slot_state=(), state_rows=None):
        ids = tokens._array                               # [slots, 1]
        pos = positions._array
        state = list(slot_state)
        form = "xla" if backend == "dense" else backend

        def mixer(m, u, i):
            out, state[0], state[1] = m.step(
                u[:, 0], state[0], state[1], i, state_rows, pos, form)
            return out[:, None]

        h = self.model._walk(self.model.embed.weight._array[ids], mixer)
        return StepOut(Tensor._wrap(h), None, None,
                       slot_state=tuple(state),
                       counters=jnp.sum(state_rows > 0, dtype=jnp.int32)
                       .reshape(1))
