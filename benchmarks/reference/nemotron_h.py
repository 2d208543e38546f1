"""Plain reference of the `nemotron_h` hybrid decoder (NVIDIA Nemotron-H /
Nemotron-3: Mamba-2 layers, LatentMoE layers and grouped-KV attention
layers chosen by the characters of `hybrid_override_pattern`), float32
`jax.numpy` at `highest`: the recurrence is a `lax.scan` over tokens (no
chunks, no carried cache), attention is the whole causal softmax, the
experts are a loop over the experts held (no sorting, no grouping).

Every block is `x <- x + mixer(RMSNorm(x))` with ONE mixer:

* `M` Mamba-2: `[z | xBC | dt] = u W_in`; `xBC <- SiLU(conv4(xBC) + b)`
  (causal, depthwise, tap 3 on the current token); `x, B, C` split from
  it; `d = softplus(dt + dt_bias)`, `A = -exp(A_log)` a head;
  `S_t = exp(d A) S_{t-1} + d x_t (x) B_t`, head h reading group
  `h // (heads / groups)`; `y_t = S_t C_t + D x_t`;
  `y <- RMSNorm within each group (y * SiLU(z))`; `out = y W_out`.
* `*` attention: 32 query heads on 2 key/value heads (16 a group),
  causal softmax(q k^T / sqrt(head_dim)) v, no position encoding.
* `E` LatentMoE: `s = sigmoid(u W_r)` over ALL published experts; the
  chosen set is the top `num_experts_per_tok` of `s + b_corr`; weights
  `routed_scaling_factor * s_e / sum_chosen s`; experts work in the
  latent `l = u W_down`: `r = sum_e w_e relu(l W1_e)^2 W2_e`;
  `out = r W_up + relu(u W_s1)^2 W_s2` (the shared expert).

Departures from the published description (the configuration file's
`assumed` and `reduced` say the same):

* the chip's share: only experts `expert_offset .. expert_offset +
  n_routed_experts` exist here; the router keeps its published width
  (`router_experts`), its experts a token and its normalisation over the
  whole chosen set, and what the absent experts would add is left out;
* the vocabulary is the slice's rows (`vocab_size`), embedding and head;
* no rotary: `nemotron_h`'s attention applies no position encoding;
* `time_step_limit` none (d is not clamped); the multi-token-prediction
  module is not part of the base model's logits and is not built;
* weights are random from the seed (see `param_spec`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .stepwise import Model, Segment


def sizes(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"hidden": cfg["hidden_size"], "heads": heads, "p": p,
            "inner": heads * p, "groups": g, "state": n,
            "conv_dim": heads * p + 2 * g * n,
            "conv_kernel": cfg["conv_kernel"],
            "q_heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "experts": cfg["n_routed_experts"],
            "router": cfg["router_experts"],
            "offset": cfg.get("expert_offset", 0),
            "top_k": cfg["num_experts_per_tok"],
            "latent": cfg["moe_latent_size"],
            "expert_inter": cfg["moe_intermediate_size"],
            "shared_inter": cfg["moe_shared_expert_intermediate_size"],
            "scaling": cfg["routed_scaling_factor"],
            "eps": cfg["norm_eps"], "vocab": cfg["vocab_size"]}


MIXER_LEAVES = {
    "M": ("in_proj.weight", "conv.weight", "conv.bias", "dt_bias",
          "A_log", "D", "norm.weight", "out_proj.weight"),
    "*": ("q_proj.weight", "k_proj.weight", "v_proj.weight",
          "o_proj.weight"),
    "E": ("router.weight", "router.bias", "down.weight", "up.weight",
          "shared.w1", "shared.w2", "experts.w1", "experts.w2"),
}


def param_spec(cfg):
    """`[(name, shape, init)]`, the program's `named_parameters()` names.
    N(0, std) everywhere, gains and `D` 1 + N(0, std), but two leaves
    that decide whether a comparison can see the recurrence at all: the
    depthwise conv's four taps N(0, `conv_init_std`) (with 0.02 the
    conv's output, and with it B, C and the whole recurrence, would be
    nought beside the `D x` term), and `A_log` N(0, `a_log_init_std`)
    (with 0.02 every head has `A` = -1 and a step of 0.7: the state
    halves a token and nothing carried over a chunk's edge, or left in
    a row by the slot's last tenant, outlives a few tokens; a spread of
    3 gives a sixth of the heads a memory of 30 tokens and more, as the
    published initialisation's small steps do, and those heads hold the
    largest states)."""
    z = sizes(cfg)
    std = cfg.get("initializer_range", 0.02)
    w, g = ["normal", std], ["ones_normal", std]
    h = z["hidden"]
    shapes = {
        "M": [((h, 2 * z["inner"] + 2 * z["groups"] * z["state"]
                + z["heads"]), w),
              ((z["conv_kernel"], z["conv_dim"]),
               ["normal", cfg.get("conv_init_std", std)]),
              ((z["conv_dim"],), w), ((z["heads"],), w),
              ((z["heads"],), ["normal", cfg.get("a_log_init_std", std)]),
              ((z["heads"],), g),
              ((z["inner"],), g), ((z["inner"], h), w)],
        "*": [((h, z["q_heads"] * z["head_dim"]), w),
              ((h, z["kv_heads"] * z["head_dim"]), w),
              ((h, z["kv_heads"] * z["head_dim"]), w),
              ((z["q_heads"] * z["head_dim"], h), w)],
        "E": [((h, z["router"]), w), ((z["router"],), w),
              ((h, z["latent"]), w), ((z["latent"], h), w),
              ((h, z["shared_inter"]), w), ((z["shared_inter"], h), w),
              ((z["experts"], z["latent"], z["expert_inter"]), w),
              ((z["experts"], z["expert_inter"], z["latent"]), w)],
    }
    spec = [("embed.weight", (z["vocab"], h), w)]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        spec.append((f"layers.{i}.norm.weight", (h,), g))
        for leaf, (shape, init) in zip(MIXER_LEAVES[kind], shapes[kind]):
            spec.append((f"layers.{i}.mixer.{leaf}", shape, init))
    spec += [("norm_f.weight", (h,), g),
             ("lm_head.weight", (z["vocab"], h), w)]
    return spec


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba_mixer(p, u, z, mm):
    """u [B, S, hidden] (normed) -> [B, S, hidden]; the state starts at
    nought and is carried token by token."""
    w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gain, w_out = p
    b, s, _ = u.shape
    heads, hp, g, n = z["heads"], z["p"], z["groups"], z["state"]
    proj = mm(u, w_in)
    gate = proj[..., :z["inner"]]
    xbc = proj[..., z["inner"]:z["inner"] + z["conv_dim"]]
    dt = proj[..., z["inner"] + z["conv_dim"]:]
    k = z["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(conv_w[j] * padded[:, j:j + s] for j in range(k)) + conv_b
    xbc = jax.nn.silu(conv)
    x = xbc[..., :z["inner"]].reshape(b, s, heads, hp)
    bm = xbc[..., z["inner"]:z["inner"] + g * n].reshape(b, s, g, n)
    cm = xbc[..., z["inner"] + g * n:].reshape(b, s, g, n)
    bm, cm = (jnp.repeat(t, heads // g, axis=2) for t in (bm, cm))
    d = jax.nn.softplus(dt + dt_bias)                     # [B, S, heads]
    decay = jnp.exp(d * -jnp.exp(a_log))

    def step(state, t):
        x_t, b_t, c_t, d_t, a_t = t
        state = a_t[..., None, None] * state + \
            (d_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], -1)

    first = jnp.zeros((b, heads, hp, n), jnp.float32)
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, d, decay)))
    y = jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x      # [B,S,heads,P]
    y = y.reshape(b, s, z["inner"]) * jax.nn.silu(gate)
    y = rms_norm(y.reshape(b, s, g, -1), gain.reshape(g, -1), z["eps"])
    return mm(y.reshape(b, s, z["inner"]), w_out)


def attention_mixer(p, u, z, mm):
    wq, wk, wv, wo = p
    b, s, _ = u.shape
    qh, kvh, d = z["q_heads"], z["kv_heads"], z["head_dim"]
    q = mm(u, wq).reshape(b, s, qh, d)
    k = jnp.repeat(mm(u, wk).reshape(b, s, kvh, d), qh // kvh, axis=2)
    v = jnp.repeat(mm(u, wv).reshape(b, s, kvh, d), qh // kvh, axis=2)
    o = common.attention(q, k, v, causal=True)
    return mm(o.reshape(b, s, qh * d), wo)


def route(scores, bias, z):
    """scores [.., router] float32 (sigmoid done) -> (`[.., top_k]` ids
    among ALL published experts, their weights): chosen by score + bias,
    weighted by score alone, normalised over the whole chosen set."""
    _, ids = jax.lax.top_k(scores + bias, z["top_k"])
    chosen = jnp.take_along_axis(scores, ids, -1)
    return ids, z["scaling"] * chosen / jnp.sum(chosen, -1, keepdims=True)


def moe_mixer(p, u, z, mm):
    """The part of the layer that the experts held here give, with the
    shared expert (which every chip of the group computes alike)."""
    return moe_routed_part(p, u, z, mm) + moe_shared_part(p, u, mm)


def moe_shared_part(p, u, mm):
    return mm(relu2(mm(u, p[4])), p[5])


def moe_routed_part(p, u, z, mm):
    w_r, b_corr, w_down, w_up, _, _, w1, w2 = p
    ids, weights = route(jax.nn.sigmoid(mm(u, w_r)), b_corr, z)
    latent = mm(u, w_down)

    def one_expert(acc, e):
        w1_e, w2_e, index = e
        w_e = jnp.sum(jnp.where(ids == index, weights, 0.0), -1)
        out = mm(relu2(mm(latent, w1_e)), w2_e)
        return acc + w_e[..., None] * out, None

    held = z["offset"] + jnp.arange(z["experts"])
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                             (w1, w2, held))
    return mm(routed, w_up)


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": moe_mixer}


def build(cfg, mm=common.mm_f32):
    z = sizes(cfg)

    def embed(p, x, batch):
        return p[0].astype(jnp.float32)[batch["input_ids"]]

    def layer(kind):
        def block(p, x, batch):
            p = [a.astype(jnp.float32) for a in p]
            return x + MIXERS[kind](p[1:], rms_norm(x, p[0], z["eps"]),
                                    z, mm)

        return block

    blocks = {kind: layer(kind) for kind in MIXERS}

    def logits(p, x, batch):
        gain, head = [a.astype(jnp.float32) for a in p]
        return mm(rms_norm(x, gain, z["eps"]), head.T)

    def loss(p, x, batch):
        return common.cross_entropy_mean(logits(p, x, batch),
                                         batch["labels"])

    segs = [Segment(embed, ("embed.weight",))]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        segs.append(Segment(blocks[kind], (f"layers.{i}.norm.weight",)
                            + tuple(f"layers.{i}.mixer.{leaf}"
                                    for leaf in MIXER_LEAVES[kind])))
    head = ("norm_f.weight", "lm_head.weight")
    return Model(param_spec(cfg), segs, Segment(loss, head),
                 Segment(logits, head))
