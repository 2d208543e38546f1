"""Constructor `gpt_causal_lm`: the program's GPT-2-style decoder with a
tied language-model head, at the sizes of a configuration file."""


def build(cfg):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    return GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size_run"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"], dropout=0.0,
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        initializer_range=cfg["initializer_range"]))
