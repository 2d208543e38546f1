"""Operations, bytes and parameters that the `pangu_ultra_moe`
ARCHITECTURE requires, from a configuration file's numbers alone
(`lib/counts.py` is the GPT-2 block's). Matmul parameters only where
FLOPs are counted; the parameter totals count every leaf.

A layer (hidden h, heads H, ranks q and c, head sizes nope / rope / v):

    attention   W_qa h x q, W_qb q x H (nope + rope), W_kva h x (c + rope),
                W_kvb c x H (nope + v), W_o H v x h
    dense MLP   3 x h x intermediate (gate, up, down)
    an expert   3 x h x moe_intermediate; the shared expert is one more
    router      h x router_experts
    norms       4 x h, and the two latent norms q + c

embedding and head vocab x h each, the final gain h. What is CACHED is
one row a token a layer: c + rope values.
"""
from __future__ import annotations


def attention_params(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return h * q + q * heads * (nope + rope) + h * (c + rope) \
        + c * heads * (nope + v) + heads * v * h


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["router_experts"]


def norm_params(cfg):
    return 4 * cfg["hidden_size"] + cfg["q_lora_rank"] \
        + cfg["kv_lora_rank"]


def layer_params(cfg, sparse, experts):
    """Every leaf of a layer that holds `experts` routed experts (and the
    shared one) if `sparse`, the dense MLP if not."""
    mlp = router_params(cfg) + (experts + cfg["n_shared_experts"]) \
        * expert_params(cfg) if sparse else dense_mlp_params(cfg)
    return attention_params(cfg) + norm_params(cfg) + mlp


def total_params(cfg, dense_layers, sparse_layers, experts, vocab):
    h = cfg["hidden_size"]
    return dense_layers * layer_params(cfg, False, 0) \
        + sparse_layers * layer_params(cfg, True, experts) \
        + 2 * vocab * h + h


def held_params(cfg):
    """What this chip holds, as the configuration is run."""
    dense = cfg["first_k_dense_replace"]
    return total_params(cfg, dense, cfg["num_hidden_layers"] - dense,
                        cfg["n_routed_experts"], cfg["vocab_size"])


def published_params(cfg):
    """(the whole published model, what one token passes through)."""
    pub = cfg["published"]
    dense, layers = pub["first_k_dense_replace"], pub["num_hidden_layers"]
    whole = total_params(cfg, dense, layers - dense,
                         pub["n_routed_experts"], pub["vocab_size"])
    active = total_params(cfg, dense, layers - dense,
                          cfg["num_experts_per_tok"], pub["vocab_size"])
    return whole, active


def sparse_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def cache_values_per_token_layer(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes_per_token(cfg, itemsize=2):
    return cfg["num_hidden_layers"] * cache_values_per_token_layer(cfg) \
        * itemsize


def plain_heads_cache_bytes_per_token(cfg, itemsize=2):
    """What keys and values of every head would take."""
    return cfg["num_hidden_layers"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * itemsize


def expected_experts_here(cfg):
    """Routed experts of THIS chip that compute a token, on average."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def experts_held_all_layers(cfg):
    """Experts this chip holds, summed over the expert layers: what one
    decode step's assignments are spread over."""
    return cfg["n_routed_experts"] * sparse_layers(cfg)


def token_matmul_params(cfg):
    """Matmul parameters one token passes through on this chip (the
    routed experts at the chip's expected share)."""
    dense = cfg["first_k_dense_replace"]
    per_sparse = router_params(cfg) + (
        expected_experts_here(cfg) + cfg["n_shared_experts"]) \
        * expert_params(cfg)
    return cfg["num_hidden_layers"] * attention_params(cfg) \
        + dense * dense_mlp_params(cfg) + sparse_layers(cfg) * per_sparse


def attention_pair_flops(cfg):
    """One query against one cached token in one layer, as plain heads
    would compute it (scores over nope + rope, the sum over v): the
    least the mathematics asks, whichever form the program takes."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def serve_request_flops(cfg, prompt_len, new_tokens, cached_tokens=0):
    """Forward work one served request requires on this chip WITH its
    first `cached_tokens` prompt tokens served from cached blocks: the
    other prompt tokens and every generated token but the last through
    the body, causal attention of each of them over its whole context
    (cached rows included: they are read, not recomputed), the head where
    a token is sampled. Work the cache saved is not counted."""
    end = prompt_len + new_tokens - 1          # tokens fed, cached or not
    fed = end - cached_tokens
    pairs = end * (end + 1) // 2 - cached_tokens * (cached_tokens + 1) // 2
    head = 2 * cfg["vocab_size"] * cfg["hidden_size"] * new_tokens
    return 2 * token_matmul_params(cfg) * fed \
        + attention_pair_flops(cfg) * cfg["num_hidden_layers"] * pairs \
        + head


def mla_decode_work(cfg, context_rows, itemsize=2):
    """(FLOPs, bytes) of the latent decode walk over `context_rows`
    cached rows (one layer's count, summed over lanes and steps): every
    layer reads each row once (c + rope values) and makes the two
    products of the absorbed form, `heads x (c + rope)` for the scores
    and `heads x c` for the sum."""
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    row = cache_values_per_token_layer(cfg)
    return (context_rows * layers * 2 * heads
            * (row + cfg["kv_lora_rank"]),
            context_rows * layers * row * itemsize)


def moe_experts_work(cfg, experts_touched, assignments, itemsize=2):
    """(FLOPs, bytes) of the expert product: the weights of every expert
    touched cross once a layer and step (`experts_touched` is summed over
    both), every assignment's hidden row goes in and comes out, its
    gate-and-up row out and its gated row in."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (2 * assignments * expert_params(cfg),
            itemsize * (experts_touched * expert_params(cfg)
                        + assignments * (2 * h + 3 * inter)))
