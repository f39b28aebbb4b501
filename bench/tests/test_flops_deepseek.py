"""``bench/flops/deepseek-v3-ep32-5l.py`` against a count made by hand at
a tiny size."""
from bench import harness

TINY_DSV3 = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
             "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
             "v_head_dim": 2, "intermediate_size": 16,
             "moe_intermediate_size": 4, "num_hidden_layers": 2,
             "first_k_dense_replace": 1, "n_routed_experts": 2,
             "num_experts_per_tok": 4, "n_shared_experts": 1,
             "vocab_size": 10, "deployment": {"router_experts": 8}}


def test_deepseek_v3_counts_by_hand():
    f = harness.load_module("flops", "deepseek-v3-ep32-5l")
    # MLA: q_a 8x4, q_b 4x(2 heads x 4), kv_a 8x(4+2), kv_b 4x(2 x 4),
    # o (2 x 2)x8
    mla = 32 + 32 + 48 + 32 + 32
    expert = 3 * 8 * 4
    assert f.expert_params(TINY_DSV3) == expert
    # 4 experts a token over a router of 8, 2 held: 1 pair a token
    assert f.pairs_per_token(TINY_DSV3) == 1.0
    # the MoE layer: router 8x8, shared expert, one routed pair
    moe = 64 + expert + 1.0 * expert
    dense = 3 * 8 * 16
    params = 2 * mla + dense + moe + 8 * 10
    assert f.matmul_params(TINY_DSV3) == params
    # one token against 3 keys: 2 layers x 2 FLOP x 2 heads x (2+2+2) x 3
    assert f.attention_flops_per_token(TINY_DSV3, 3) == 2 * 2 * 2 * 6 * 3
    # a prompt of 2 and 2 generated: tokens at contexts 1, 2, 3
    assert f.serve_flops(TINY_DSV3, 2, 2) == sum(
        2 * params + 2 * 2 * 2 * 6 * c for c in (1, 2, 3))
    # 5 routed pairs and 3 tokens through the shared expert
    assert f.expert_layer_flops(TINY_DSV3, 5, 3) == 2 * expert * (5 + 3)
