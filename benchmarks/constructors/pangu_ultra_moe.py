"""Constructor `pangu_ultra_moe`: the program's latent-attention decoder
(sandwich norms, dense then sigmoid-routed SwiGLU experts) at the sizes of
a configuration file, created in the run dtype with no random draw: the
driver binds every leaf from the seed."""


def build(cfg):
    from paddle_tpu.models.pangu_ultra_moe import (
        PanguUltraMoEConfig, PanguUltraMoEForCausalLM)

    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "router_experts", "expert_offset",
            "num_experts_per_tok", "routed_scaling_factor", "rope_theta",
            "rms_norm_eps", "dtype")
    return PanguUltraMoEForCausalLM(PanguUltraMoEConfig(
        **{k: cfg[k] for k in same}, max_seq_len=cfg["max_model_len_run"],
        init="zeros"))
