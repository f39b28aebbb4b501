"""Operations the algorithm needs for deepseek-v3-ep32-5l, from its
shapes.

Counted at 2 FLOP per multiply-add. A forward token pays, in every
layer, MLA's projections once (W_qa, W_qb, W_kva, W_kvb, W_o: each
token's latent is expanded once), and attention's score and value
products at head widths nope + rope = 192 and v = 128 over its causal
context (position t, 0-based, attends to t + 1 keys); in the dense
layers the SwiGLU FFN; in the MoE layers the router over its full width,
the shared expert, and the held experts at the pairs a token is expected
to route here: num_experts_per_tok x held / router width (8 x 8 / 256 =
0.25); and once the output head over the 16160-id slice. The embedding
is a gather and free. The program's padding of the vocabulary, its full
(masked) score blocks, its tiles' padded rows and decode's latent-space
rewrite of attention are not required work.
"""
from __future__ import annotations


def _mla_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nd, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, qr, r = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * H * (nd + rd) + d * (r + rd)
            + r * H * (nd + vd) + H * vd * d)


def expert_params(cfg: dict) -> int:
    """One routed (or shared) expert's SwiGLU: up, gate, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_per_token(cfg: dict) -> float:
    """Pairs a token routes to the held experts, in expectation."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["deployment"]["router_experts"])


def matmul_params(cfg: dict) -> float:
    """Weights a token multiplies once (held experts at their expected
    share)."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - dense
    moe = (d * cfg["deployment"]["router_experts"]
           + cfg["n_shared_experts"] * expert_params(cfg)
           + pairs_per_token(cfg) * expert_params(cfg))
    return (cfg["num_hidden_layers"] * _mla_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"] + n_moe * moe
            + d * cfg["vocab_size"])


def attention_flops_per_token(cfg: dict, ctx: float) -> float:
    """Score and value products of one token against ``ctx`` keys."""
    H = cfg["num_attention_heads"]
    width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
             + cfg["v_head_dim"])
    return cfg["num_hidden_layers"] * 2 * H * width * ctx


def serve_flops(cfg: dict, prompt_len: int, gen_len: int) -> float:
    """One request: the prompt's tokens prefilled and ``gen_len`` tokens
    generated, each at its own context."""
    total = 0.0
    per_weight = 2.0 * matmul_params(cfg)
    for t in range(prompt_len + gen_len - 1):
        total += per_weight + attention_flops_per_token(cfg, t + 1)
    return total


def expert_layer_flops(cfg: dict, pairs: int, tokens: int) -> float:
    """The expert layers' work as counted: ``pairs`` routed to held
    experts and ``tokens`` (a token once per expert layer) through the
    shared expert."""
    return 2.0 * expert_params(cfg) * (pairs
                                       + cfg["n_shared_experts"] * tokens)
