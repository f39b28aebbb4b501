"""CPU sizes of dsv3-serve-prefill (``bench/tests/sizing.py``). TINY:
every mechanism of the cell at small widths: 1 dense and 2 MoE layers,
a router 16 wide in 4 groups (2 kept), 4 experts a token, the layer
holding experts 4-7, YaRN on 16 rope dims. CONTROL: the cell's own
routing and vocabulary at hidden 1,024."""

TINY_CFG = {"hidden_size": 64, "num_hidden_layers": 3,
            "first_k_dense_replace": 1, "num_attention_heads": 4,
            "num_key_value_heads": 4, "q_lora_rank": 32,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 16, "v_head_dim": 16,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "n_routed_experts": 4, "n_group": 4, "topk_group": 2,
            "num_experts_per_tok": 4, "vocab_size": 512,
            "deployment": {"router_experts": 16, "first_held_expert": 4},
            # as the phi3 cells' tiny serving sizes: weights ten times the
            # published scale so that the logits spread as at the cell's
            # width and an altered token reads well below the best
            "init": {"std": 0.2}}

TINY = {
    "config": TINY_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 20,
                 "gen": {"dist": "uniform", "min": 8, "max": 24,
                         "grid": 9},
                 "steps_per_call": 4, "check_requests": 6},
}
# the cell's routing at hidden 1,024: a router 256 wide in 8 groups (4
# kept), 8 experts a token, experts 0-7 held (a quarter of a pair a
# token, so a routing flip between bf16 and float32 seldom touches a
# held expert, as in the cell), weights at 0.0529 = 0.02 x sqrt(7168 /
# 1024) so that the logits spread as at 7,168 wide; here the program
# read 0.064-0.539 and the fp8 control 1.512-1.910 on seeds 7-15 (seed
# 7: 0.070 and 1.665), either side of the cell's limit of 1.0 (CPU)
CONTROL_CFG = {"hidden_size": 1024, "num_hidden_layers": 3,
               "first_k_dense_replace": 1, "num_attention_heads": 8,
               "num_key_value_heads": 8, "q_lora_rank": 256,
               "kv_lora_rank": 128, "qk_nope_head_dim": 32,
               "qk_rope_head_dim": 32, "v_head_dim": 32,
               "intermediate_size": 2048, "moe_intermediate_size": 256,
               "init": {"std": 0.0529}}

CONTROL = {
    "config": CONTROL_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 40,
                 "gen": {"dist": "uniform", "min": 32, "max": 128,
                         "grid": 9},
                 "steps_per_call": 4, "check_requests": 8},
}
