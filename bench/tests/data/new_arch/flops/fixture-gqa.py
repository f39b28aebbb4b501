"""Operations the algorithm needs for the test fixture's fixture-gqa,
at 2 FLOP per multiply-add: every weight matrix once a token, and
attention over the causal context (t + 1 keys at position t)."""
from __future__ import annotations


def weight_flops(cfg: dict) -> float:
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    layer = 2 * d * d + 2 * d * kv + 3 * d * cfg["intermediate_size"]
    return 2.0 * (cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"])


def attention_flops(cfg: dict, ctx: float) -> float:
    return 4.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * ctx


def train_flops_per_token(cfg: dict, seq: int, q: int) -> float:
    """1 + q forwards and one backward at twice a forward."""
    fwd = weight_flops(cfg) + attention_flops(cfg, (seq + 1) / 2.0)
    return (q + 3) * fwd


def serve_flops(cfg: dict, prompt_len: int, gen_len: int) -> float:
    return sum(weight_flops(cfg) + attention_flops(cfg, t + 1)
               for t in range(prompt_len + gen_len - 1))
