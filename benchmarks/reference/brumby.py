"""Plain reference of the `brumby` decoder (Manifest AI Brumby-14B-Base:
the Qwen3 block with every softmax attention replaced by power retention,
arXiv:2507.04239), float32 `jax.numpy` at `highest`, in the ATTENTION
FORM: a `[T, T]` score matrix a head, no state, no feature map, no kernel.

A layer (u the normed input; KV head h serves query heads `r h .. r h +
r - 1`):

    q = rot(rmsnorm_head(u W_q))   k = rot(rmsnorm_head(u W_k))   v = u W_v
    log g = logsigmoid(u W_g + b_g)                one gate a KV head
    a_tj = (q_t . k_j / sqrt(d))^2 * exp(sum_{l=j+1..t} log g_l)    j <= t
    y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)
    x <- x + y W_o;   x <- x + (silu(n W_gate) * (n W_up)) W_down

with `n` the RMSNorm of the new x. Final RMSNorm, untied head.

What the catalog's config does not say is taken from the published method
and the release, and listed in the configuration file's `assumed`: the
power 2, the scale inside the power, the gate and its bias, the
normaliser's eps 1e-6, the QK-RMSNorm a head and the rotary (two halves
paired) kept from the Qwen3 block. The program serves the SAME numbers
from a recurrent state (`phi(k) v^T`, decayed by the gate): nothing here
shares that form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .stepwise import Model, Segment

EPS = 1e-6
LAYER_LEAVES = ("input_norm.weight", "mixer.q_proj.weight",
                "mixer.k_proj.weight", "mixer.v_proj.weight",
                "mixer.g_proj.weight", "mixer.g_proj.bias",
                "mixer.q_norm.weight", "mixer.k_norm.weight",
                "mixer.o_proj.weight", "post_norm.weight",
                "mlp.gate_up", "mlp.down")


def param_spec(cfg):
    """`[(name, shape, init)]`, the program's `named_parameters()` names.
    N(0, std) everywhere and gains 1 + N(0, std), but ONE leaf that
    decides whether a comparison can see the recurrence at all: the
    gate's bias, `[gate_bias_init, gate_bias_std]` (a kind of
    `lib/weights.py` and its spread). With N(0, 0.02) every gate is 0.5:
    the state halves a token, and a stale row or a lost carry is gone in
    ten tokens."""
    std = cfg.get("initializer_range", 0.02)
    w, g = ["normal", std], ["ones_normal", std]
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    inter, kvh = cfg["intermediate_size"], cfg["num_key_value_heads"]
    gate_bias = [cfg.get("gate_bias_init", "normal"),
                 cfg.get("gate_bias_std", std)]
    shapes = [((h,), g), ((h, q), w), ((h, kv), w), ((h, kv), w),
              ((h, kvh), w), ((kvh,), gate_bias), ((d,), g), ((d,), g),
              ((q, h), w), ((h,), g), ((h, 2 * inter), w), ((inter, h), w)]
    spec = [("embed.weight", (cfg["vocab_size"], h), w)]
    for i in range(cfg["num_hidden_layers"]):
        spec += [(f"layers.{i}.{leaf}", shape, init)
                 for leaf, (shape, init) in zip(LAYER_LEAVES, shapes)]
    spec += [("norm_f.weight", (h,), g),
             ("lm_head.weight", (cfg["vocab_size"], h), w)]
    return spec


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rotary(x, theta):
    """x `[B, S, n, d]` at positions 0..S-1: value i of the first half
    pairs with value i of the second."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention_weights(q, k, log_g):
    """The attention form's weights `a_tj`, `[B, H, S, S]`: q, k `[B, S,
    H, d]` (each KV head repeated for its query heads), log_g `[B, S,
    H]`."""
    d, s = q.shape[-1], q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=common.HIGHEST) / jnp.sqrt(jnp.float32(d))
    cum = jnp.moveaxis(jnp.cumsum(log_g, axis=1), 1, 2)   # [B, H, S]
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    gap = jnp.where(keep, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    return jnp.square(scores) * jnp.exp(gap)


def power_retention(q, k, v, log_g):
    """v `[B, S, H, d]`, the rest as `retention_weights` takes them."""
    a = retention_weights(q, k, log_g)
    num = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=common.HIGHEST)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), 1, 2)         # [B, S, H]
    return num / (den[..., None] + EPS)


def projections(p, u, cfg, mm):
    """-> q, k, v, log_g of a layer's mixer, K, V and the gate repeated
    for the query heads; `p` the mixer's eight leaves."""
    wq, wk, wv, wg, bg, q_gain, k_gain, _ = p
    b, s, _ = u.shape
    d, kvh = cfg["head_dim"], cfg["num_key_value_heads"]
    rep = cfg["num_attention_heads"] // kvh
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = rotary(rms_norm(mm(u, wq).reshape(b, s, -1, d), q_gain, eps),
               theta)
    k = rotary(rms_norm(mm(u, wk).reshape(b, s, kvh, d), k_gain, eps),
               theta)
    v = mm(u, wv).reshape(b, s, kvh, d)
    log_g = jax.nn.log_sigmoid(mm(u, wg) + bg)            # [B, S, kvh]
    return (q,) + tuple(jnp.repeat(t, rep, axis=2) for t in (k, v, log_g))


def mixer(p, u, cfg, mm):
    y = power_retention(*projections(p, u, cfg, mm))
    return mm(y.reshape(u.shape[:2] + (-1,)), p[-1])


def build(cfg, mm=common.mm_f32):
    eps = cfg["rms_norm_eps"]

    def embed(p, x, batch):
        return p[0].astype(jnp.float32)[batch["input_ids"]]

    def block(p, x, batch):
        p = [a.astype(jnp.float32) for a in p]
        x = x + mixer(p[1:9], rms_norm(x, p[0], eps), cfg, mm)
        n = rms_norm(x, p[9], eps)
        gate_up = mm(n, p[10])
        half = gate_up.shape[-1] // 2
        return x + mm(jax.nn.silu(gate_up[..., :half])
                      * gate_up[..., half:], p[11])

    def logits(p, x, batch):
        gain, head = [a.astype(jnp.float32) for a in p]
        return mm(rms_norm(x, gain, eps), head.T)

    def loss(p, x, batch):
        return common.cross_entropy_mean(logits(p, x, batch),
                                         batch["labels"])

    segs = [Segment(embed, ("embed.weight",))]
    for i in range(cfg["num_hidden_layers"]):
        segs.append(Segment(block, tuple(
            f"layers.{i}.{leaf}" for leaf in LAYER_LEAVES)))
    head = ("norm_f.weight", "lm_head.weight")
    return Model(param_spec(cfg), segs, Segment(loss, head),
                 Segment(logits, head))
