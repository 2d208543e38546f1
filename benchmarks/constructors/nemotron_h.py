"""Constructor `nemotron_h`: the program's hybrid decoder (Mamba-2,
LatentMoE, grouped-KV attention by the layer pattern) at the sizes of a
configuration file, created in the run dtype with no random draw: the
driver binds every leaf from the seed."""


def build(cfg):
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    same = ("vocab_size", "hidden_size", "hybrid_override_pattern",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "n_routed_experts", "router_experts", "expert_offset",
            "num_experts_per_tok", "moe_latent_size",
            "moe_intermediate_size",
            "moe_shared_expert_intermediate_size",
            "routed_scaling_factor", "norm_eps", "dtype")
    return NemotronHForCausalLM(NemotronHConfig(
        **{k: cfg[k] for k in same}, max_seq_len=cfg["max_model_len_run"],
        init="zeros"))
