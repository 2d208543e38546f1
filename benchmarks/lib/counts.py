"""Operations and bytes that the ALGORITHM requires, from shapes alone.

These are the yardstick's: they count the work whatever implements it,
so a kernel swap in the program cannot make a share stale. Matmul
parameters only (no position or type tables, no biases or norms), the
tied output head once, causal attention at half of 4*B*H*S^2*D,
recomputation not counted. `cfg` is a configuration file's dict.
"""
from __future__ import annotations


def _sizes(cfg):
    h = cfg["hidden_size"]
    return h, cfg["num_layers"], cfg["intermediate_size"]


def matmul_params_body(cfg):
    """Per token, every layer: fused qkv 3h^2, out h^2, two MLP h*i."""
    h, layers, inter = _sizes(cfg)
    return layers * (4 * h * h + 2 * h * inter)


def matmul_params_head(cfg):
    """GPT: the tied vocabulary projection (rows as run, padded). BERT's
    classifier head works on one pooled row a sequence: not per token."""
    if cfg["architecture"] == "gpt2":
        return cfg["vocab_size_run"] * cfg["hidden_size"]
    return 0


def matmul_params(cfg):
    return matmul_params_body(cfg) + matmul_params_head(cfg)


def attention_flops_fwd_per_token(cfg, context, causal_self):
    """QK^T and PV against `context` keys: 4*context*hidden a layer; a
    causal self-attention over a whole sequence needs half of it."""
    h, layers, _ = _sizes(cfg)
    f = 4 * context * h * layers
    return f / 2 if causal_self else f


def train_flops_per_token(cfg, seq):
    """Forward + backward (3x forward) of one trained token."""
    causal = cfg["architecture"] == "gpt2"
    return 6 * matmul_params(cfg) + \
        3 * attention_flops_fwd_per_token(cfg, seq, causal)


def attention_train_flops_per_step(cfg, batch, seq):
    causal = cfg["architecture"] == "gpt2"
    return batch * seq * 3 * attention_flops_fwd_per_token(cfg, seq, causal)


def attention_train_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Forward reads q,k,v and writes o; backward reads q,k,v,o,do and
    writes dq,dk,dv: twelve [B,S,hidden] arrays a layer."""
    h, layers, _ = _sizes(cfg)
    return 12 * batch * seq * h * itemsize * layers


def kv_bytes_per_token(cfg, itemsize=2):
    h, layers, _ = _sizes(cfg)
    return 2 * layers * h * itemsize


def serve_request_flops(cfg, prompt_len, new_tokens):
    """Forward work one served request requires: every prompt and
    generated token through the body, attention over its own context
    (causal: token i sees i keys), the head only where a token is
    sampled (the last prompt position and each generated one but the
    last, which is never fed back)."""
    h, layers, _ = _sizes(cfg)
    n = prompt_len + new_tokens - 1           # tokens fed to the model
    body = 2 * matmul_params_body(cfg) * n
    attn = 4 * h * layers * (n * (n + 1) // 2)
    head = 2 * matmul_params_head(cfg) * new_tokens
    return body + attn + head


def weight_bytes(cfg, itemsize=2):
    return matmul_params(cfg) * itemsize
