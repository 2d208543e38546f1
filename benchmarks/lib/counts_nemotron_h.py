"""Operations, bytes and parameters that the `nemotron_h` ARCHITECTURE
requires, from a configuration file's numbers alone (`lib/counts.py` is
the GPT-2 block's). Matmul parameters only where FLOPs are counted; the
parameter totals count every leaf.

A layer, by kind (hidden h, Mamba inner i = heads x head_dim, conv width
c = i + 2 x groups x state, latent l):

    M   in h x (i + c + heads), out i x h, conv 4c + c, 3 x heads, norm i
    *   q h x (q_heads x d), k and v h x (kv_heads x d), out (q_heads x d) x h
    E   router h x experts + experts, down h x l, up l x h,
        shared 2 x h x shared_inter, and 2 x l x inter AN EXPERT

and one gain of h a layer; embedding and head vocab x h each, final gain.
"""
from __future__ import annotations


def _m(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = heads * p
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return heads, p, inner, conv


def layer_matmul_params(cfg, kind, experts_active=None):
    """Matmul parameters one token passes through in a layer of `kind`.
    `experts_active`: routed experts a token is computed by HERE."""
    h = cfg["hidden_size"]
    if kind == "M":
        heads, _, inner, conv = _m(cfg)
        return h * (inner + conv + heads) + inner * h
    if kind == "*":
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, \
            cfg["num_key_value_heads"] * d
        return h * q + 2 * h * kv + q * h
    lat = cfg["moe_latent_size"]
    outside = h * cfg["router_experts"] + 2 * h * lat \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
    return outside + experts_active * expert_params(cfg)


def expert_params(cfg):
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, kind, experts):
    """Every leaf of a layer of `kind` that holds `experts` experts."""
    h = cfg["hidden_size"]
    if kind == "M":
        heads, _, inner, conv = _m(cfg)
        return layer_matmul_params(cfg, "M") \
            + cfg["conv_kernel"] * conv + conv + 3 * heads + inner + h
    if kind == "*":
        return layer_matmul_params(cfg, "*") + h
    return layer_matmul_params(cfg, "E", experts) \
        + cfg["router_experts"] + h


def total_params(cfg, pattern, experts, vocab):
    """A model of `pattern` that holds `experts` experts a layer and
    `vocab` rows (embedding and head untied)."""
    h = cfg["hidden_size"]
    return sum(layer_params(cfg, k, experts) for k in pattern) \
        + 2 * vocab * h + h


def expected_experts_here(cfg):
    """Routed experts of THIS chip that compute a token, on average:
    experts a token x the share of the router's experts held."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def experts_held_all_layers(cfg):
    """Experts this chip holds, summed over the E layers: what one decode
    step's assignments are spread over."""
    return cfg["n_routed_experts"] \
        * cfg["hybrid_override_pattern"].count("E")


def scan_flops_per_token(cfg):
    """One M layer's state update and read-out for one token: a state
    element is decayed (1), takes `d x (x) B` (2) and is read against C
    (2)."""
    heads, p, _, _ = _m(cfg)
    return 5 * heads * p * cfg["ssm_state_size"]


def serve_request_flops(cfg, prompt_len, new_tokens):
    """Forward work one served request requires on this chip: every
    prompt and generated token but the last through the body (the
    experts at the chip's expected share), the scan's update a token a
    layer, causal attention over its own context in the `*` layers (token
    i sees i keys: QK^T and PV are 4 x heads x d a key), the head where a
    token is sampled."""
    pattern = cfg["hybrid_override_pattern"]
    n = prompt_len + new_tokens - 1
    body = sum(layer_matmul_params(cfg, k, expected_experts_here(cfg))
               for k in pattern)
    scan = pattern.count("M") * scan_flops_per_token(cfg)
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * pattern.count("*") * (n * (n + 1) // 2)
    head = 2 * cfg["vocab_size"] * cfg["hidden_size"] * new_tokens
    return (2 * body + scan) * n + attn + head


def ssm_state_bytes_per_slot_layer(cfg, itemsize=4):
    heads, p, _, _ = _m(cfg)
    return heads * p * cfg["ssm_state_size"] * itemsize


def ssm_decode_work(cfg, lane_steps):
    """(FLOPs, bytes) of the scan's decode kernel over `lane_steps` live
    lanes summed over decode steps: every M layer reads and writes the
    lane's state once."""
    layers = cfg["hybrid_override_pattern"].count("M")
    return (lane_steps * layers * scan_flops_per_token(cfg),
            lane_steps * layers * 2 * ssm_state_bytes_per_slot_layer(cfg))


def moe_experts_work(cfg, experts_touched, assignments, itemsize=2):
    """(FLOPs, bytes) of the expert product: the weights of every expert
    touched cross once a layer and step (`experts_touched` is summed over
    both), every assignment's latent goes in and out and its hidden row
    out and in."""
    lat, inter = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    return (2 * assignments * expert_params(cfg),
            itemsize * (experts_touched * expert_params(cfg)
                        + assignments * 2 * (lat + inter)))
