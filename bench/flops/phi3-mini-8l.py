"""Operations the algorithm needs for phi3-mini-8l, from its shapes.

Counted at 2 FLOP per multiply-add. A forward token pays every weight
matrix once (q, k, v, o, up, gate, down in each layer; the output head
over the published vocabulary; the embedding is a gather and free) and
attention's score and value products over its causal context: position
t (0-based) attends to t + 1 keys, so a sequence of S averages (S+1)/2.
The program's padding of the vocabulary to 32256 rows and its full
(unmasked-then-masked) score blocks are waste, not required work.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * d + 2 * d * kv + d * d + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops_per_token(cfg: dict, ctx: float) -> float:
    """Score and value products of one token against ``ctx`` keys."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * 2 * 2 * d * ctx


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Mean over the positions of a length-``seq`` sequence."""
    return (2.0 * matmul_params(cfg)
            + attention_flops_per_token(cfg, (seq + 1) / 2.0))


def train_flops_per_token(cfg: dict, seq: int, q: int) -> float:
    """The cascaded step: 1 + q server forwards (the clean lane and q
    perturbed lanes) and one backward of the clean lane at twice a
    forward. Recomputation under remat is not counted, nor any backward
    of the perturbed lanes."""
    return (1 + q + 2) * forward_flops_per_token(cfg, seq)


def serve_flops(cfg: dict, prompt_len: int, gen_len: int) -> float:
    """One request: the prompt's tokens prefilled and ``gen_len`` tokens
    generated, each at its own context."""
    total = 0.0
    per_weight = 2.0 * matmul_params(cfg)
    for t in range(prompt_len + gen_len - 1):
        total += per_weight + attention_flops_per_token(cfg, t + 1)
    return total
