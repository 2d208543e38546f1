"""Plain reference of the GPT-2-style decoder (Radford et al. 2019; the
block Cerebras-GPT publishes, arXiv:2304.03208): learned positions,
pre-LayerNorm residual blocks, biased projections, tanh-GELU MLP, output
head tied to the token table. Departures from the published model are
the configuration file's `assumed` list (padded vocabulary rows, tanh
GELU as the program runs it, no dropout).

`build(cfg, mm)` returns the model as a chain of segments
(`embed`, one per layer, `head`) that `stepwise` walks one at a time so
that float32 at the published widths fits the chip.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from . import common
from .stepwise import Model, Segment


def param_spec(cfg):
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    out_std = std / math.sqrt(2 * cfg["num_layers"])
    w, b, g = ["normal", std], ["normal", std], ["ones_normal", std]
    spec = [("gpt.wte.weight", (cfg["vocab_size_run"], h), w),
            ("gpt.wpe.weight", (cfg["max_position_embeddings"], h), w)]
    for i in range(cfg["num_layers"]):
        p = f"gpt.blocks.{i}."
        spec += [
            (p + "ln1.weight", (h,), g), (p + "ln1.bias", (h,), b),
            (p + "attn.qkv_proj.weight", (h, 3 * h), w, 3),
            (p + "attn.qkv_proj.bias", (3 * h,), b, 3),
            (p + "attn.out_proj.weight", (h, h), w),
            (p + "attn.out_proj.bias", (h,), b),
            (p + "ln2.weight", (h,), g), (p + "ln2.bias", (h,), b),
            (p + "mlp.fc1.weight", (h, inter), w),
            (p + "mlp.fc1.bias", (inter,), b),
            (p + "mlp.fc2.weight", (inter, h), ["normal", out_std]),
            (p + "mlp.fc2.bias", (h,), b),
        ]
    spec += [("gpt.ln_f.weight", (h,), g), ("gpt.ln_f.bias", (h,), b)]
    return spec


LAYER_KEYS = ("ln1.weight", "ln1.bias", "attn.qkv_proj.weight",
              "attn.qkv_proj.bias", "attn.out_proj.weight",
              "attn.out_proj.bias", "ln2.weight", "ln2.bias",
              "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
              "mlp.fc2.bias")


def build(cfg, mm=common.mm_f32):
    heads, eps = cfg["num_heads"], cfg["layer_norm_epsilon"]

    def embed(p, x, batch):
        ids = batch["input_ids"]
        wte, wpe = p
        return wte.astype(jnp.float32)[ids] + \
            wpe.astype(jnp.float32)[jnp.arange(ids.shape[1])]

    def block(p, x, batch):
        (g1, b1, wqkv, bqkv, wo, bo, g2, b2, w1, c1, w2, c2) = \
            [a.astype(jnp.float32) for a in p]
        x = x + common.self_attention(
            common.layer_norm(x, g1, b1, eps), wqkv, bqkv, wo, bo,
            heads, True, mm)
        hid = common.gelu_tanh(mm(common.layer_norm(x, g2, b2, eps), w1)
                               + c1)
        return x + mm(hid, w2) + c2

    def logits(p, x, batch):
        g, b, wte = [a.astype(jnp.float32) for a in p]
        return mm(common.layer_norm(x, g, b, eps), wte.T)

    def loss(p, x, batch):
        return common.cross_entropy_mean(logits(p, x, batch),
                                         batch["labels"])

    segs = [Segment(embed, ("gpt.wte.weight", "gpt.wpe.weight"))]
    for i in range(cfg["num_layers"]):
        segs.append(Segment(
            block, tuple(f"gpt.blocks.{i}.{k}" for k in LAYER_KEYS)))
    head_leaves = ("gpt.ln_f.weight", "gpt.ln_f.bias", "gpt.wte.weight")
    return Model(param_spec(cfg), segs,
                 Segment(loss, head_leaves), Segment(logits, head_leaves))
