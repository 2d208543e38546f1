"""Constructor `brumby`: the program's power-retention decoder at the sizes
of a configuration file, created in the run dtype with no random draw: the
driver binds every leaf from the seed."""


def build(cfg):
    from paddle_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM

    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "rms_norm_eps", "rope_theta", "dtype")
    config = BrumbyConfig(
        **{k: cfg[k] for k in same}, max_seq_len=cfg["max_model_len_run"],
        chunk_size=cfg["prefill_sub_chunk"], init="zeros")
    if config.state_width != cfg["state_width_run"]:
        raise ValueError(
            f"the configuration states a state of {cfg['state_width_run']} "
            f"rows a KV head, the program keeps {config.state_width}")
    return BrumbyForCausalLM(config)
