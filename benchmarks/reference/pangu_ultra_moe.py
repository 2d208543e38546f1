"""Plain reference of the `pangu_ultra_moe` decoder (openPangu-Ultra-MoE:
latent attention, sandwich norms, dense SwiGLU MLPs in the leading layers,
sigmoid-routed SwiGLU experts plus a shared one after), float32
`jax.numpy` at `highest`: the EXPANDED attention over the whole sequence
(every head's keys and values made from the compressed rows, no cache, no
absorbed products, no kernels), the experts a loop over the experts held
(no sorting, no grouping).

A layer (four RMSNorms):

    a  = h + RMSNorm_post_attn(MLA(RMSNorm_in(h)))
    h' = a + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(a)))

* `MLA(u)`: `c_q = RMSNorm(u W_qa)`; `[q_nope_h ; q_r_h] = c_q W_qb`;
  `[c ; k_r] = u W_kva`; `c_kv = RMSNorm(c)`; `[k_nope_h ; v_h] =
  c_kv W_kvb`; rotary on `q_r_h` and on `k_r` (one key for all heads);
  `softmax((q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(nope + rope))`
  causal, times `v_h`; heads concatenated, `W_o`.
* `FFN`: `(silu(u W_g) * (u W_u)) W_d` in the first
  `first_k_dense_replace` layers; after them `s = sigmoid(u W_r)` over ALL
  published experts, the `num_experts_per_tok` largest, `w = s / (sum s +
  1e-20) x routed_scaling_factor`, `sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)`.

Departures from the published code (`modeling_openpangu_moe.py`; the
configuration file's `assumed` and `reduced` say the same):

* the chip's share: only experts `expert_offset .. expert_offset +
  n_routed_experts` exist here; the router keeps its published width
  (`router_experts`), its experts a token and its normalisation over the
  whole chosen set, and what the absent experts would add is left out;
* the vocabulary is the slice's rows (`vocab_size`), embedding and head;
* rotary pairs are the two halves of the 64 values (`rotate_half`), no
  scaling of the frequencies; no group limit and no correction bias on
  the router (the config has neither key);
* gate and up projections are stored side by side as one matrix
  `[W_g | W_u]` (the same numbers as two); the multi-token-prediction
  module is not built;
* queries are walked in blocks of `QUERY_BLOCK` so that 128 heads of
  scores over 8k keys fit; the numbers are those of the whole softmax;
* weights are random from the seed (see `param_spec`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .stepwise import Model, Segment

HIGHEST = common.HIGHEST
#: queries a block of the attention's walk: `[B, heads, block, S]` scores
QUERY_BLOCK = 256


def sizes(cfg):
    return {"hidden": cfg["hidden_size"],
            "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "inter": cfg["intermediate_size"],
            "expert_inter": cfg["moe_intermediate_size"],
            "experts": cfg["n_routed_experts"],
            "router": cfg["router_experts"],
            "offset": cfg.get("expert_offset", 0),
            "top_k": cfg["num_experts_per_tok"],
            "scaling": cfg["routed_scaling_factor"],
            "theta": cfg["rope_theta"], "eps": cfg["rms_norm_eps"],
            "vocab": cfg["vocab_size"]}


ATTN_LEAVES = ("q_a.weight", "q_a_norm.weight", "q_b.weight",
               "kv_a.weight", "kv_a_norm.weight", "kv_b.weight",
               "o.weight")
MLP_LEAVES = {"dense": ("gate_up.weight", "down.weight"),
              "sparse": ("router.weight", "shared.gate_up", "shared.down",
                         "experts.w1", "experts.w2")}


def param_spec(cfg):
    """`[(name, shape, init)]`, the program's `named_parameters()` names.
    N(0, `initializer_range`) everywhere and gains 1 + N(0, that), but
    three leaves that decide whether a comparison can see the attention
    and the routing at all (the configuration's `assumed.weights` gives
    the readings): `q_b` and `kv_a` — at 0.02 every attention logit is
    near 0, each softmax uniform, and a wrong position, a skipped page or
    a stale row moves nothing — and the router, whose sigmoid would sit
    at a half for every expert."""
    z = sizes(cfg)
    std = cfg.get("initializer_range", 0.02)
    w, g = ["normal", std], ["ones_normal", std]
    h, heads = z["hidden"], z["heads"]
    attn = [((h, z["q_rank"]), w), ((z["q_rank"],), g),
            ((z["q_rank"], heads * (z["nope"] + z["rope"])),
             ["normal", cfg.get("attn_query_init_std", std)]),
            ((h, z["rank"] + z["rope"]),
             ["normal", cfg.get("attn_key_init_std", std)]),
            ((z["rank"],), g),
            ((z["rank"], heads * (z["nope"] + z["v"])), w),
            ((heads * z["v"], h), w)]
    e = z["expert_inter"]
    mlps = {
        "dense": [((h, 2 * z["inter"]), w), ((z["inter"], h), w)],
        "sparse": [((h, z["router"]),
                    ["normal", cfg.get("router_init_std", std)]),
                   ((h, 2 * e), w), ((e, h), w),
                   ((z["experts"], h, 2 * e), w),
                   ((z["experts"], e, h), w)]}
    spec = [("embed.weight", (z["vocab"], h), w)]
    for i in range(z["layers"]):
        kind = "dense" if i < z["dense"] else "sparse"
        spec.append((f"layers.{i}.input_norm.weight", (h,), g))
        spec += [(f"layers.{i}.attn.{leaf}", shape, init)
                 for leaf, (shape, init) in zip(ATTN_LEAVES, attn)]
        spec += [(f"layers.{i}.post_attn_norm.weight", (h,), g),
                 (f"layers.{i}.pre_mlp_norm.weight", (h,), g)]
        spec += [(f"layers.{i}.mlp.{leaf}", shape, init)
                 for leaf, (shape, init) in zip(MLP_LEAVES[kind],
                                                mlps[kind])]
        spec.append((f"layers.{i}.post_mlp_norm.weight", (h,), g))
    spec += [("norm_f.weight", (h,), g),
             ("lm_head.weight", (z["vocab"], h), w)]
    return spec


def layer_leaves(cfg, i):
    kind = "dense" if i < cfg["first_k_dense_replace"] else "sparse"
    return (f"layers.{i}.input_norm.weight",) \
        + tuple(f"layers.{i}.attn.{leaf}" for leaf in ATTN_LEAVES) \
        + (f"layers.{i}.post_attn_norm.weight",
           f"layers.{i}.pre_mlp_norm.weight") \
        + tuple(f"layers.{i}.mlp.{leaf}" for leaf in MLP_LEAVES[kind]) \
        + (f"layers.{i}.post_mlp_norm.weight",)


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rotary(x, z):
    """x `[B, S, n, rope]` at positions 0..S-1: value i of the first half
    pairs with value i of the second."""
    s, d = x.shape[1], x.shape[-1]
    inv = z["theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(u, w_gate_up, w_down, mm):
    gu = mm(u, w_gate_up)
    half = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], w_down)


def latent_attention(p, u, z, mm, fault=None):
    """u `[B, S, hidden]` (normed) -> `[B, S, hidden]`, expanded.

    `fault` plants what a broken cache would do, for the comparison's
    own test (never in a benchmark run): `no_key_rotation` leaves the
    rotation off the keys; `("skip_keys", a, b)` hides keys `a..b` from
    every query past them (a cached page left out of a walk);
    `("late_keys", n, by)` makes the queries at and past `n` read, for a
    key position `j < n`, the key and value `by` positions earlier (a
    prefix hit mapped one block late)."""
    w_qa, g_q, w_qb, w_kva, g_kv, w_kvb, w_o = p
    b, s, _ = u.shape
    heads, nope, rope, dv = z["heads"], z["nope"], z["rope"], z["v"]
    q = mm(rms_norm(mm(u, w_qa), g_q, z["eps"]), w_qb) \
        .reshape(b, s, heads, nope + rope)
    ckr = mm(u, w_kva)
    kv = mm(rms_norm(ckr[..., :z["rank"]], g_kv, z["eps"]), w_kvb) \
        .reshape(b, s, heads, nope + dv)
    k_rope = ckr[:, :, None, z["rank"]:]
    if fault != "no_key_rotation":
        k_rope = rotary(k_rope, z)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], z)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], -1)
    v = kv[..., nope:]
    late = None
    if isinstance(fault, tuple) and fault[0] == "late_keys":
        _, n, by = fault
        src = jnp.where(jnp.arange(s) < n,
                        jnp.maximum(jnp.arange(s) - by, 0), jnp.arange(s))
        late = (n, k[:, src], v[:, src])
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) \
        .reshape(b, blocks, QUERY_BLOCK, heads, nope + rope)

    def one_block(args):
        q_blk, first = args                      # [B, QB, heads, d]
        rows = first + jnp.arange(QUERY_BLOCK)
        keep = jnp.arange(s)[None, :] <= rows[:, None]
        if isinstance(fault, tuple) and fault[0] == "skip_keys":
            _, lo, hi = fault
            hidden = (jnp.arange(s) >= lo) & (jnp.arange(s) < hi)
            keep = keep & ~(hidden[None, :] & (rows[:, None] >= hi))
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k,
                        precision=HIGHEST) * scale
        if late is not None:        # rows at and past `n` read late keys
            after = (rows >= late[0])[None, None, :, None]
            sc = jnp.where(after, jnp.einsum(
                "bqhd,bkhd->bhqk", q_blk, late[1],
                precision=HIGHEST) * scale, sc)
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HIGHEST)
        if late is not None:
            out = jnp.where((rows >= late[0])[None, :, None, None],
                            jnp.einsum("bhqk,bkhd->bqhd", pr, late[2],
                                       precision=HIGHEST), out)
        return out

    o = jax.lax.map(one_block, (jnp.moveaxis(qb, 1, 0),
                                jnp.arange(blocks) * QUERY_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * QUERY_BLOCK,
                                      heads * dv)[:, :s]
    return mm(o, w_o)


def route(u, w_r, z, mm):
    """-> (ids `[.., top_k]` among ALL published experts, their weights,
    normalised over the whole chosen set and scaled)."""
    chosen, ids = jax.lax.top_k(jax.nn.sigmoid(mm(u, w_r)), z["top_k"])
    return ids, z["scaling"] * chosen \
        / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)


def moe_routed_part(p, u, z, mm):
    """What the experts held here add."""
    w_r, _, _, w1, w2 = p
    ids, weights = route(u, w_r, z, mm)

    def one_expert(acc, e):
        w1_e, w2_e, index = e
        w_e = jnp.sum(jnp.where(ids == index, weights, 0.0), -1)
        return acc + w_e[..., None] * swiglu(u, w1_e, w2_e, mm), None

    held = z["offset"] + jnp.arange(z["experts"])
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), (w1, w2, held))
    return routed


def moe_shared_part(p, u, mm):
    return swiglu(u, p[1], p[2], mm)


def ffn(p, u, z, mm):
    if len(p) == 2:
        return swiglu(u, p[0], p[1], mm)
    return moe_routed_part(p, u, z, mm) + moe_shared_part(p, u, mm)


def build(cfg, mm=common.mm_f32, fault=None):
    z = sizes(cfg)

    def embed(p, x, batch):
        return p[0].astype(jnp.float32)[batch["input_ids"]]

    def block(p, x, batch):
        p = [a.astype(jnp.float32) for a in p]
        g_in, attn, g_pa, g_pm = p[0], p[1:8], p[8], p[9]
        mlp, g_po = p[10:-1], p[-1]
        eps = z["eps"]
        a = x + rms_norm(latent_attention(
            attn, rms_norm(x, g_in, eps), z, mm, fault), g_pa, eps)
        return a + rms_norm(ffn(mlp, rms_norm(a, g_pm, eps), z, mm),
                            g_po, eps)

    def logits(p, x, batch):
        gain, head = [a.astype(jnp.float32) for a in p]
        return mm(rms_norm(x, gain, z["eps"]), head.T)

    def loss(p, x, batch):
        return common.cross_entropy_mean(logits(p, x, batch),
                                         batch["labels"])

    segs = [Segment(embed, ("embed.weight",))]
    for i in range(z["layers"]):
        segs.append(Segment(block, layer_leaves(cfg, i)))
    head = ("norm_f.weight", "lm_head.weight")
    return Model(param_spec(cfg), segs, Segment(loss, head),
                 Segment(logits, head))
