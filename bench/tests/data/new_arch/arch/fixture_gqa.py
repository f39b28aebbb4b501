"""Program mapping of the test fixture's ``fixture_gqa`` architecture:
a dense RoPE SwiGLU decoder with grouped KV heads."""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    prog = cfg["program"]
    return ModelConfig(
        arch_id=cfg["name"], family="dense", source=cfg["source"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        act="swiglu", norm="rmsnorm", pos="rope",
        rope_theta=float(cfg["rope_theta"]),
        dtype=prog["activation_dtype"], param_dtype=prog["param_dtype"])
