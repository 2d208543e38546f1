"""Operations, bytes and parameters that the `brumby` ARCHITECTURE (the
Qwen3 block with power retention for attention) requires, from a
configuration file's numbers alone. Matmul parameters only where FLOPs
are counted; the parameter totals count every leaf.

A layer (hidden h, q = heads x d, kv = kv_heads x d, MLP width i):

    q h x q, k and v h x kv, o q x h, gate h x kv_heads + kv_heads,
    MLP 3 x h x i, two gains of h, the QK-norm gains 2 x d

embedding and head vocab x h each, final gain. The retention's state is
`phi`'s D = d (d + 1) / 2 DISTINCT values by d, a KV head: what the
published method requires, whatever the program pads it to.
"""
from __future__ import annotations


def _heads(cfg):
    d = cfg["head_dim"]
    return cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d


def layer_matmul_params(cfg):
    """Matmul parameters one token passes through in a layer."""
    h = cfg["hidden_size"]
    q, kv = _heads(cfg)
    return 2 * h * q + 2 * h * kv + h * cfg["num_key_value_heads"] \
        + 3 * h * cfg["intermediate_size"]


def layer_params(cfg):
    """Every leaf of a layer: the gate's bias and the four gains too."""
    return layer_matmul_params(cfg) + cfg["num_key_value_heads"] \
        + 2 * cfg["hidden_size"] + 2 * cfg["head_dim"]


def total_params(cfg, layers, vocab):
    """A model of `layers` layers that holds `vocab` rows (embedding and
    head untied)."""
    h = cfg["hidden_size"]
    return layers * layer_params(cfg) + 2 * vocab * h + h


def experts_held_all_layers(cfg):
    """No experts: the stateful driver asks."""
    return 0


def state_width(cfg):
    """D: distinct values of the symmetric second-power map."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def retention_flops_per_token(cfg):
    """One layer's state update and read-out for one token: a state
    element is decayed and takes `phi(k) v^T` (one multiply-add), and is
    read once a query head (one each)."""
    kvh = cfg["num_key_value_heads"]
    readers = cfg["num_attention_heads"] // kvh
    return 2 * (1 + readers) * kvh * state_width(cfg) * cfg["head_dim"]


def retention_state_bytes_per_slot_layer(cfg, itemsize=4):
    return cfg["num_key_value_heads"] * state_width(cfg) \
        * cfg["head_dim"] * itemsize


def serve_request_flops(cfg, prompt_len, new_tokens):
    """Forward work one served request requires on this chip: every
    prompt and generated token but the last through the body, the
    retention's update and read-out a token a layer (the state is of
    fixed size: no term grows with the context), the head where a token
    is sampled."""
    n = prompt_len + new_tokens - 1
    layers = cfg["num_hidden_layers"]
    body = layers * (2 * layer_matmul_params(cfg)
                     + retention_flops_per_token(cfg))
    head = 2 * cfg["vocab_size"] * cfg["hidden_size"] * new_tokens
    return body * n + head


def retention_decode_work(cfg, lane_steps):
    """(FLOPs, bytes) of the retention's decode kernel over `lane_steps`
    live lanes summed over decode steps: every layer reads and writes the
    lane's state once."""
    layers = cfg["num_hidden_layers"]
    return (lane_steps * layers * retention_flops_per_token(cfg),
            lane_steps * layers * 2
            * retention_state_bytes_per_slot_layer(cfg))

