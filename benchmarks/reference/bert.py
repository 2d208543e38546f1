"""Plain reference of BERT (Devlin et al. 2018) with the sequence-
classification head used for fine-tuning: token + position + type
embeddings under a LayerNorm, post-LayerNorm encoder blocks, a tanh
pooler over the first position, a linear classifier, cross entropy.
Departures are the configuration file's `assumed` list (tanh GELU as the
program runs it, no dropout, token type 0 throughout).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import common
from .stepwise import Model, Segment


def param_spec(cfg):
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    w, b, g = ["normal", std], ["normal", std], ["ones_normal", std]
    e = "bert.embeddings."
    spec = [(e + "word_embeddings.weight", (cfg["vocab_size"], h), w),
            (e + "position_embeddings.weight",
             (cfg["max_position_embeddings"], h), w),
            (e + "token_type_embeddings.weight",
             (cfg["type_vocab_size"], h), w),
            (e + "layer_norm.weight", (h,), g),
            (e + "layer_norm.bias", (h,), b)]
    for i in range(cfg["num_layers"]):
        p = f"bert.layers.{i}."
        spec += [
            (p + "attention.qkv_proj.weight", (h, 3 * h), w, 3),
            (p + "attention.qkv_proj.bias", (3 * h,), b, 3),
            (p + "attention.out_proj.weight", (h, h), w),
            (p + "attention.out_proj.bias", (h,), b),
            (p + "ln1.weight", (h,), g), (p + "ln1.bias", (h,), b),
            (p + "fc1.weight", (h, inter), w), (p + "fc1.bias", (inter,), b),
            (p + "fc2.weight", (inter, h), w), (p + "fc2.bias", (h,), b),
            (p + "ln2.weight", (h,), g), (p + "ln2.bias", (h,), b),
        ]
    spec += [("bert.pooler.dense.weight", (h, h), w),
             ("bert.pooler.dense.bias", (h,), b),
             ("classifier.weight", (h, cfg["num_labels"]), w),
             ("classifier.bias", (cfg["num_labels"],), b)]
    return spec


EMBED_KEYS = ("word_embeddings.weight", "position_embeddings.weight",
              "token_type_embeddings.weight", "layer_norm.weight",
              "layer_norm.bias")
LAYER_KEYS = ("attention.qkv_proj.weight", "attention.qkv_proj.bias",
              "attention.out_proj.weight", "attention.out_proj.bias",
              "ln1.weight", "ln1.bias", "fc1.weight", "fc1.bias",
              "fc2.weight", "fc2.bias", "ln2.weight", "ln2.bias")
HEAD_KEYS = ("bert.pooler.dense.weight", "bert.pooler.dense.bias",
             "classifier.weight", "classifier.bias")


def build(cfg, mm=common.mm_f32):
    heads, eps = cfg["num_heads"], cfg["layer_norm_epsilon"]

    def embed(p, x, batch):
        ids = batch["input_ids"]
        word, pos, typ, g, b = [a.astype(jnp.float32) for a in p]
        e = word[ids] + pos[jnp.arange(ids.shape[1])] + typ[0]
        return common.layer_norm(e, g, b, eps)

    def block(p, x, batch):
        (wqkv, bqkv, wo, bo, g1, b1, w1, c1, w2, c2, g2, b2) = \
            [a.astype(jnp.float32) for a in p]
        x = common.layer_norm(
            x + common.self_attention(x, wqkv, bqkv, wo, bo, heads,
                                      False, mm), g1, b1, eps)
        hid = common.gelu_tanh(mm(x, w1) + c1)
        return common.layer_norm(x + mm(hid, w2) + c2, g2, b2, eps)

    def logits(p, x, batch):
        wp, bp, wc, bc = [a.astype(jnp.float32) for a in p]
        pooled = jnp.tanh(mm(x[:, 0], wp) + bp)
        return mm(pooled, wc) + bc

    def loss(p, x, batch):
        return common.cross_entropy_mean(logits(p, x, batch),
                                         batch["labels"])

    segs = [Segment(embed, tuple("bert.embeddings." + k
                                 for k in EMBED_KEYS))]
    for i in range(cfg["num_layers"]):
        segs.append(Segment(
            block, tuple(f"bert.layers.{i}.{k}" for k in LAYER_KEYS)))
    return Model(param_spec(cfg), segs, Segment(loss, HEAD_KEYS),
                 Segment(logits, HEAD_KEYS))
