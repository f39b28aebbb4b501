"""Program mapping of the ``deepseek_v3`` architecture: a Hugging Face
``DeepseekV3Config`` as the program's MoE decoder with MLA, YaRN RoPE,
the sigmoid group-limited router, and an expert layer that holds the
configuration's share of the router's experts (``deployment``)."""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    prog, dep, rs = cfg["program"], cfg["deployment"], cfg["rope_scaling"]
    if rs["type"] != "yarn" or cfg["scoring_func"] != "sigmoid":
        raise ValueError("deepseek_v3 maps YaRN and the sigmoid router only")
    if rs["mscale"] != rs["mscale_all_dim"] or not cfg["norm_topk_prob"]:
        # the program's cos/sin are unscaled (mscale / mscale_all_dim = 1)
        # and its sigmoid router always normalises the top-k gates
        raise ValueError("deepseek_v3 maps mscale == mscale_all_dim and "
                         "normalised top-k gates only")
    return ModelConfig(
        arch_id=cfg["name"], family="moe", source=cfg["source"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        act="swiglu", norm="rmsnorm", pos="rope",
        rope_theta=float(cfg["rope_theta"]), rope_scaling="yarn",
        rope_factor=float(rs["factor"]),
        rope_orig_max_pos=int(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        rope_interleaved=True,
        n_experts=dep["router_experts"], top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense=cfg["first_k_dense_replace"],
        router_score="sigmoid", router_groups=cfg["n_group"],
        router_groups_kept=cfg["topk_group"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        n_experts_held=cfg["n_routed_experts"],
        expert_first=dep["first_held_expert"],
        use_mla=True, q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        n_mtp=cfg["num_nextn_predict_layers"], mla_absorb=True,
        dtype=prog["activation_dtype"], param_dtype=prog["param_dtype"])
