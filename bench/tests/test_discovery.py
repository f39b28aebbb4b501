"""Every name in BENCHMARK.json resolves to its own file, and every such
file is shaped as the harness reads it."""
import dataclasses
import os

import pytest

from bench import harness
from bench.tests.sizing import sizes

SPEC = harness.benchmark_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
LM_CONFIGS = [c["name"] for c in SPEC["configs"]
              if "model_type" in harness.load_config(c["name"])]


@pytest.mark.parametrize("cell", CELLS)
def test_workload_finds_config_driver_and_flops(cell):
    wl = harness.load_workload(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert wl["config"] == entry["config"]
    cfg = harness.load_config(wl["config"])
    assert "source" in cfg and "init" in cfg
    driver = harness.load_module("drivers", wl["driver"])
    assert callable(driver.run)
    assert set(wl["limits"]) == set(driver.CHECKS)
    harness.load_module("flops", wl["config"])
    sz = sizes(cell)
    for overrides in (sz.TINY, sz.CONTROL):
        assert set(overrides) <= {"config", "workload"}
    if wl["driver"] in ("train_sync", "serve_closed"):
        assert wl["config"] in LM_CONFIGS


@pytest.mark.parametrize("config", LM_CONFIGS)
def test_model_type_finds_arch_and_reference(config):
    cfg = harness.load_config(config)
    from repro.configs.base import ModelConfig
    assert isinstance(harness.load_arch(cfg).model_config(cfg), ModelConfig)
    ref = harness.load_reference(cfg)
    hash(ref.items(cfg))
    assert callable(ref.hidden)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    mod = harness.load_module("metrics", metric)
    assert callable(mod.compute)
    m = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    e2e = {e["name"] for e in SPEC["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m.get("workloads", []):
        assert cell in {w["name"] for w in SPEC["workloads"]}


def test_config_files_are_named_in_benchmark():
    for c in SPEC["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert c["file"] == f"bench/configs/{c['name']}.json"


def test_reader_returns_nothing_when_nothing_to_read():
    rec = {"out": {"trace": None, "e2e": {}}, "config": {}}
    for m in SPEC["per_layer"]:
        assert harness.load_module("metrics", m["name"]).compute(rec) is None


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.load_workload("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such_metric")
    with pytest.raises(harness.BenchError,
                       match=r"tests/sizes/no-such-cell\.py"):
        sizes("no-such-cell")


def test_unknown_model_type_names_the_missing_file():
    cfg = dict(harness.load_config("phi3-mini-8l"), model_type="no_such")
    with pytest.raises(harness.BenchError, match=r"arch/no_such\.py"):
        harness.load_arch(cfg)
    with pytest.raises(harness.BenchError, match=r"reference/no_such\.py"):
        harness.load_reference(cfg)
    del cfg["model_type"]
    with pytest.raises(harness.BenchError, match="model_type"):
        harness.load_arch(cfg)


# the ModelConfig that the phi3 cells ran before ``arch/phi3.py`` took
# the mapping over from the train driver, field for field
PHI3_MINI_8L = {
    "arch_id": "phi3-mini-8l", "family": "dense",
    "source": "https://huggingface.co/microsoft/Phi-3-mini-4k-instruct"
              "/blob/main/config.json",
    "n_layers": 8, "d_model": 3072, "n_heads": 32, "n_kv_heads": 32,
    "head_dim": 0, "d_ff": 8192, "vocab_size": 32064, "act": "swiglu",
    "norm": "rmsnorm", "pos": "rope", "rope_theta": 10000.0,
    "tie_embeddings": False, "attn_logit_softcap": 0.0, "qk_norm": False,
    "causal": True, "window_size": 0, "n_experts": 0, "top_k": 0,
    "moe_d_ff": 0, "n_shared_experts": 0, "first_k_dense": 0,
    "capacity_factor": 1.25, "router_noise": 0.0,
    "load_balance_coef": 0.01, "moe_groups": 16, "use_mla": False,
    "q_lora_rank": 0, "kv_lora_rank": 0, "qk_nope_dim": 0,
    "qk_rope_dim": 0, "v_head_dim": 0, "n_mtp": 0, "ssm_state": 0,
    "ssm_expand": 2, "ssm_head_dim": 64, "ssm_chunk": 128,
    "rwkv_head_dim": 64, "rwkv_chunk": 128, "attn_every": 0,
    "n_shared_blocks": 1, "is_encoder_decoder": False,
    "n_encoder_layers": 0, "encoder_seq": 0, "n_vision_tokens": 0,
    "frontend_dim": 0, "dtype": "bfloat16", "param_dtype": "bfloat16",
    "remat": True, "remat_policy": "full", "scan_layers": True,
    "seq_shard_acts": True, "iota_embed": False, "rs_outputs": False,
    "mla_absorb": False,
}


def test_phi3_arch_builds_the_model_config_field_for_field():
    cfg = harness.load_config("phi3-mini-8l")
    mcfg = harness.load_arch(cfg).model_config(cfg)
    assert dataclasses.asdict(mcfg) == PHI3_MINI_8L


def test_seed_of_any_size_gives_distinct_31_bit_seeds():
    big = 2 ** 40 + 7
    a, b = harness.seed32(big), harness.seed32(big + 1)
    assert a != b and 0 <= a < 2 ** 31 and 0 <= b < 2 ** 31
    assert harness.seed32(big) == a
