"""DeepSeek-V3 at small widths against its plain reference
(``bench/reference/deepseek_v3.py``, which imports nothing of the
program): the sigmoid group-limited router, YaRN, the dropless layer
that holds a share of the experts, and prefill then paged decode through
``fed.serve``. The program runs in float32 here, so that it and the
reference differ by rounding alone."""
import contextlib
import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import VFLConfig, get_config, list_archs
from repro.core.async_engine import EngineConfig
from repro.federation import Federation
from repro.models import attention, common, layers, moe
from repro.utils import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIGHEST = jax.lax.Precision.HIGHEST


def _load(kind, name):
    path = os.path.join(ROOT, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"dsv3_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "deepseek_v3")
ARCH = _load("arch", "deepseek_v3")

# every mechanism of the cell at small widths: 1 dense + 2 MoE layers, a
# router 16 wide in 4 groups (2 kept), 4 experts a token, the layer
# holding experts 4-7, YaRN on 16 rope dims
SMALL = {"hidden_size": 64, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "num_attention_heads": 4,
         "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts": 4, "n_group": 4, "topk_group": 2,
         "num_experts_per_tok": 4, "vocab_size": 512}


def small_cfg(**over):
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v3-ep32-5l.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "deepseek-v3-small"
    cfg.update(SMALL)
    cfg["deployment"] = dict(cfg["deployment"], router_experts=16,
                             first_held_expert=4)
    cfg["program"] = dict(cfg["program"], param_dtype="float32",
                          activation_dtype="float32")
    for k, v in over.items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    return cfg


def ref_cfg(cfg):
    return dict(REF.items(cfg))


def dot(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def moe_params(mcfg, key, std=0.2):
    specs = moe.moe_specs(mcfg, mcfg.d_model)
    return jax.tree.map(
        lambda s: (jnp.ones(s.shape) if s.init == "ones" else
                   std * jax.random.normal(jax.random.fold_in(
                       key, hash(s.shape) % 1000), s.shape)),
        specs, is_leaf=common.is_spec)


class session:
    """A profiler session writing to ``logdir``; a span outside any
    session goes first, so that the session's records are its own."""

    def __init__(self, logdir):
        self.logdir = str(logdir)

    def __enter__(self):
        with spans.span("vfl.test.between_sessions"):
            pass
        jax.profiler.start_trace(self.logdir)

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


def route_inputs(scores, bias):
    """Router weights and an input that give exactly these sigmoid
    scores: x is a row of ones, so x W_r = logit(scores)."""
    logits = np.log(scores) - np.log1p(-scores)
    d = 8
    w = np.tile(logits / d, (d, 1)).astype(np.float32)
    return (jnp.ones((1, 1, d), jnp.float32),
            {"router": jnp.asarray(w), "router_bias": jnp.asarray(
                bias, jnp.float32)})


# ------------------------------------------------------ the config --

# the fields this architecture added to ModelConfig, at their defaults:
# plain RoPE, the softmax router, every expert held
NEW_FIELDS = {
    "rope_scaling": "none", "rope_factor": 1.0, "rope_orig_max_pos": 0,
    "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0,
    "yarn_mscale_all_dim": 0.0, "rope_interleaved": False,
    "router_score": "softmax", "router_groups": 1,
    "router_groups_kept": 1, "routed_scale": 1.0,
    "n_experts_held": 0, "expert_first": 0}


def _phi3_bench_config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "phi3-mini-8l.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "phi3-mini-8l"
    return _load("arch", "phi3").model_config(cfg)


@pytest.mark.parametrize("arch_id", [a for a in list_archs()
                                     if a != "deepseek-v3-671b"]
                         + ["phi3-mini-8l (bench)"])
def test_phi3_config_is_unchanged_but_for_the_new_defaults(arch_id):
    """Every other configuration, and phi3-mini-8l as the benchmark
    builds it, keeps the new fields at the defaults that leave its
    program as it was: no rope keywords, no softmax scale factor."""
    mcfg = (_phi3_bench_config() if arch_id.endswith("(bench)")
            else get_config(arch_id))
    got = dataclasses.asdict(mcfg)
    assert {k: got[k] for k in NEW_FIELDS} == NEW_FIELDS
    assert layers.rope_args(mcfg, mcfg.resolved_head_dim) == {}
    assert layers.softmax_scale_factor(mcfg) == 1.0


@pytest.mark.parametrize("key,value", [("mscale", 0.707),
                                       ("norm_topk_prob", False)])
def test_arch_refuses_what_the_program_does_not_compute(key, value):
    cfg = small_cfg()
    if key == "mscale":
        cfg["rope_scaling"] = dict(cfg["rope_scaling"], mscale=value)
    else:
        cfg[key] = value
    with pytest.raises(ValueError, match="mscale == mscale_all_dim"):
        ARCH.model_config(cfg)


# -------------------------------------------------------------- YaRN --

def test_yarn_frequencies_and_scale_written_out():
    """dim 64, base 1e4, 4,096 original positions, beta 32/1: the ramp
    runs from pair 10 to pair 23, and mscale(40) = 0.1 ln 40 + 1."""
    rs = {"factor": 40, "original_max_position_embeddings": 4096,
          "beta_fast": 32, "beta_slow": 1}
    inv = layers.yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0)
    extra = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    assert np.all(inv[11:23] < extra[11:23])
    assert np.all(inv[11:23] > extra[11:23] / 40)
    np.testing.assert_array_equal(inv, REF.yarn_inv_freq(64, 1e4, rs))
    assert layers.yarn_mscale(40.0, 1.0) == pytest.approx(1.3689, abs=1e-4)
    mcfg = ARCH.model_config(small_cfg())
    assert layers.softmax_scale_factor(mcfg) == pytest.approx(
        1.3689 ** 2, abs=1e-3)


def test_rope_rotates_pairs_as_the_reference():
    cfg = small_cfg()
    mcfg = ARCH.model_config(cfg)
    x = jax.random.normal(jax.random.key(0), (2, 9, 4, 16))
    pos = jnp.arange(9) * 300          # past the original positions' ramp
    got = layers.apply_rope(x, pos, mcfg.rope_theta,
                            **layers.rope_args(mcfg, 16))
    want = REF.rope(x, pos, ref_cfg(cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ router --

def _route_both(cfg, x, p):
    mcfg = ARCH.model_config(cfg)
    gates, idx, aux = moe._router(mcfg, p, x)
    w, ridx = REF.route(ref_cfg(cfg), dot, p, x)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(w), rtol=1e-6)
    assert float(aux) == 0.0
    return np.asarray(idx)[0, 0], np.asarray(gates)[0, 0]


def test_router_matches_reference_on_random_weights():
    cfg = small_cfg(hidden_size=64)
    mcfg = ARCH.model_config(cfg)
    p = moe_params(mcfg, jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (3, 17, 64))
    gates, idx, _ = moe._router(mcfg, p, x)
    w, ridx = REF.route(ref_cfg(cfg), dot, p, x)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(w), rtol=1e-5)
    # normalised top-4 gates, scaled by 2.5
    np.testing.assert_allclose(np.asarray(jnp.sum(gates, -1)), 2.5,
                               rtol=1e-5)


def test_correction_bias_changes_the_choice_not_the_gate():
    s = np.full(16, 0.05)
    s[4:8] = [0.7, 0.7, 0.2, 0.2]            # group 1
    s[8:12] = [0.65, 0.65, 0.2, 0.2]         # group 2
    x, p = route_inputs(s, np.zeros(16))
    idx0, _ = _route_both(small_cfg(), x, p)
    assert sorted(idx0) == [4, 5, 8, 9]
    bias = np.zeros(16)
    bias[10] = 0.6                           # expert 10 chooses at 0.8
    x, p = route_inputs(s, bias)
    idx, gates = _route_both(small_cfg(), x, p)
    assert 10 in idx and 10 not in idx0
    chosen = s[idx]
    np.testing.assert_allclose(gates, chosen / chosen.sum() * 2.5,
                               rtol=1e-5)


def test_group_limit_changes_the_choice():
    s = np.full(16, 0.1)
    s[0:4] = [0.99, 0.01, 0.01, 0.01]        # group 0: sum of best two 1.0
    s[4:8] = [0.6, 0.6, 0.1, 0.1]            # group 1: 1.2
    s[8:12] = [0.55, 0.55, 0.1, 0.1]         # group 2: 1.1
    x, p = route_inputs(s, np.zeros(16))
    idx, _ = _route_both(small_cfg(), x, p)
    assert sorted(idx) == [4, 5, 8, 9]       # 0 would win ungrouped
    idx1, _ = _route_both(small_cfg(n_group=1, topk_group=1), x, p)
    assert 0 in idx1


def test_group_limit_holds_below_zero():
    """The groups left out are out of the choice (-inf), even where the
    kept groups' choice scores are below zero: a correction bias of -1
    on 6 of the 8 kept experts still leaves the other groups unchosen."""
    s = np.full(16, 0.3)
    s[4:8] = [0.9, 0.8, 0.3, 0.3]            # group 1
    s[8:12] = [0.85, 0.8, 0.3, 0.3]          # group 2
    bias = np.zeros(16)
    bias[[5, 6, 7, 9, 10, 11]] = -1.0
    x, p = route_inputs(s, bias)
    idx, _ = _route_both(small_cfg(), x, p)
    assert all(4 <= i < 12 for i in idx), idx


# ------------------------------------------------- the held experts --

def test_held_layer_drops_nothing_when_every_token_picks_the_same():
    """Every token routes its 4 pairs to held experts 4-7: each held
    expert gets every token (256 pairs, two tiles of 128), where a
    capacity of 1.25x the mean would keep 80."""
    cfg = small_cfg()
    mcfg = ARCH.model_config(cfg)
    p = moe_params(mcfg, jax.random.key(3))
    p["router"] = jnp.zeros_like(p["router"])
    p["router_bias"] = jnp.zeros(16).at[4:8].set(1.0)
    x = jax.random.normal(jax.random.key(4), (2, 128, 64))
    out, _, load = moe.moe_apply_held(mcfg, p, x)
    np.testing.assert_array_equal(np.asarray(load), 128)
    want = REF.moe(ref_cfg(cfg), dot, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_held_layer_matches_reference_on_random_routing():
    cfg = small_cfg()
    mcfg = ARCH.model_config(cfg)
    p = moe_params(mcfg, jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (3, 40, 64))
    out, _, load = moe.moe_apply_held(mcfg, p, x)
    want = REF.moe(ref_cfg(cfg), dot, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    _, idx = REF.route(ref_cfg(cfg), dot, p, x)
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    assert int(np.asarray(load).sum()) == int(held.sum())


def test_shares_add_up_to_the_uncut_layer():
    """The 4 shares of 4 experts each, with the shared expert counted
    once, give what the uncut reference layer (all 16 held) gives."""
    full = small_cfg(n_routed_experts=16,
                     deployment={"first_held_expert": 0})
    mfull = ARCH.model_config(full)
    p = moe_params(mfull, jax.random.key(7))
    x = jax.random.normal(jax.random.key(8), (2, 24, 64))
    want = REF.moe(ref_cfg(full), dot, p, x)
    total = 0.0
    for j in range(4):
        share = small_cfg(deployment={"first_held_expert": 4 * j})
        ps = dict(p, **{k: p[k][4 * j:4 * j + 4]
                        for k in ("w_up", "w_gate", "w_down")})
        out, _, _ = moe.moe_apply_held(ARCH.model_config(share), ps, x)
        total = total + out
    shared = moe._shared(mfull, p, x)
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- serving --

def test_absorbed_decode_matches_expanded_decode():
    """One decode step over a latent cache that a 9-token prefill chunk
    filled: scoring in latent space (the cell's decode) gives what
    expanding the cache per head gives, YaRN and mscale^2 included."""
    mcfg = ARCH.model_config(small_cfg())
    p = common.materialize(attention.attention_specs(mcfg),
                           jax.random.key(13))
    p = jax.tree.map(lambda a: a * 10.0 if a.ndim == 2 else a, p)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape[1:], s.dtype),
                         attention.cache_specs(mcfg, 2, 16),
                         is_leaf=common.is_spec)      # one layer's
    x = jax.random.normal(jax.random.key(14), (2, 10, mcfg.d_model))
    _, cache = attention.mla_apply(mcfg, p, x[:, :9],
                                   positions=jnp.arange(9), cache=cache,
                                   cur_pos=0)
    out = {}
    for absorb in (True, False):
        cfg = dataclasses.replace(mcfg, mla_absorb=absorb)
        out[absorb], _ = attention.mla_apply(
            cfg, p, x[:, 9:], positions=jnp.array([9]), cache=cache,
            cur_pos=9)
    assert float(jnp.max(jnp.abs(out[True]))) > 1.0
    np.testing.assert_allclose(np.asarray(out[True]),
                               np.asarray(out[False]), rtol=1e-4,
                               atol=1e-4)


def _serve(cfg, prompts, gens, *, absorb=True, session_dir=None):
    mcfg = dataclasses.replace(ARCH.model_config(cfg), mla_absorb=absorb)
    seq_len = 48
    fed = Federation.build(mcfg, VFLConfig(), EngineConfig(), n_clients=2,
                           seq_len=seq_len)
    leaves, tree = jax.tree.flatten(fed.model.param_specs,
                                    is_leaf=common.is_spec)
    key = jax.random.key(9)
    params = jax.tree.unflatten(tree, [
        jnp.ones(s.shape) if s.init == "ones" else
        0.2 * jax.random.normal(jax.random.fold_in(key, i), s.shape)
        for i, s in enumerate(leaves)])
    params["lm_head"]["table"] = params["lm_head"]["table"].at[
        mcfg.vocab_size:].set(0.0)
    srv = fed.serve(params, max_batch=4)
    for p_, g in zip(prompts, gens):
        srv.submit(p_, g)
    res = []
    with (session(session_dir) if session_dir is not None
          else contextlib.nullcontext()):
        while srv.pending or srv.active:
            res += srv.run(max_steps=4)
    return srv, params, sorted(res, key=lambda r: r.rid)


def _ref_logits(cfg, params, toks):
    x = jnp.take(params["embed"]["table"], toks, axis=0)
    h = REF.hidden(ref_cfg(cfg), dot, params, x)
    return np.asarray(dot("bsd,vd->bsv", h, params["lm_head"]["table"]))


def _prompts(n, plen, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, plen, dtype=np.int32) for _ in range(n)]


@pytest.mark.parametrize("absorb", [True, False])
def test_prefill_then_paged_decode_matches_reference(absorb):
    """fed.serve: chunked prefill (two parties' spans), then paged decode
    (the cell's absorbed path, and the expanded one), against the
    reference's full forward: every served token is the reference's
    argmax, its logits within rounding."""
    cfg = small_cfg()
    prompts, gens = _prompts(5, 28), [12, 7, 16, 9, 20]
    srv, params, res = _serve(cfg, prompts, gens, absorb=absorb)
    assert all(r.status == "ok" for r in res)
    for p_, g, r in zip(prompts, gens, res):
        toks = np.concatenate([p_, r.tokens])[None]
        lg = _ref_logits(cfg, params, toks)[0, len(p_) - 1:-1, :512]
        assert len(r.tokens) == g
        np.testing.assert_array_equal(r.tokens, lg.argmax(-1))
        gap = lg.max(-1) - lg[np.arange(g), r.tokens]
        assert float(gap.max()) < 1e-3


def test_expert_counters_count_what_was_routed(tmp_path):
    """The scheduler's expert counters: tokens through the expert layers
    (each layer counts a token), the prefill waves' held pairs as the
    reference routes them, two vfl.sched.moe records a run call, and no
    device read beyond the retirement waves'."""
    cfg = small_cfg()
    prompts, gens = _prompts(4, 28, seed=12), [5, 9, 6, 8]
    srv, params, res = _serve(cfg, prompts, gens, session_dir=tmp_path)
    c = srv.moe_counters()
    n_moe, rc = 2, ref_cfg(cfg)
    decoded = sum(g for g in gens)
    assert c["tokens"] == n_moe * (4 * 28 + decoded) and c["held"] == 4
    # the reference's routing of the prompts, layer by layer
    x = jnp.take(params["embed"]["table"], jnp.asarray(np.stack(prompts)),
                 axis=0)
    pos = jnp.arange(28)
    eps = rc["rms_norm_eps"]
    held_pairs = 0
    for name in ("dense_blocks", "blocks"):
        for i in range(params[name]["ln1"]["scale"].shape[0]):
            p_l = jax.tree.map(lambda a: a[i], params[name])
            x = x + REF.attention(rc, dot, p_l["attn"], REF.rms_norm(
                x, p_l["ln1"]["scale"], eps), pos)
            h = REF.rms_norm(x, p_l["ln2"]["scale"], eps)
            if "moe" in p_l:
                _, idx = REF.route(rc, dot, p_l["moe"], h)
                held_pairs += int(((np.asarray(idx) >= 4)
                                   & (np.asarray(idx) < 8)).sum())
                x = x + REF.moe(rc, dot, p_l["moe"], h)
            else:
                x = x + REF.swiglu(dot, h, p_l["mlp"]["w_up"],
                                   p_l["mlp"]["w_gate"],
                                   p_l["mlp"]["w_down"])
    assert int(c["wave_load_sum"]) == held_pairs
    assert int(c["pairs"]) >= held_pairs
    assert int(c["pairs"]) <= held_pairs + 4 * decoded * n_moe
    assert int(c["wave_load_max"]) * 4 >= held_pairs
    recs = [s for s in spans.spans() if s.name == "vfl.sched.moe"]
    runs = [s for s in spans.spans() if s.name == "vfl.sched.run"]
    fetches = [s for s in spans.spans() if s.name == "vfl.sched.retire_fetch"]
    # the first run call opens before any prefill: nothing to record yet
    assert len(recs) == 2 * len(runs) - 1 > 0
    assert srv.host_transfers == len(fetches)
    assert int(recs[-1].ids["pairs"]) == int(c["pairs"])


@pytest.mark.parametrize("name,want", [
    ("moe_expert_mfu.serve",
     # (pairs 300 + shared on 1,000 tokens) x 2 x 3 x 7168 x 2048 FLOP
     # over 2 s, of 197 TFLOP/s
     100.0 * 1300 * 2 * 3 * 7168 * 2048 / 2.0 / 197e12),
    ("moe_load_max_mean.serve", 60 * 8 / 240)])
def test_expert_readers(name, want, tmp_path):
    reader = _load("metrics", name).compute
    flops = _load("flops", "deepseek-v3-ep32-5l")
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v3-ep32-5l.json")) as f:
        cfg = json.load(f)
    rec = {"out": {"trace": {"window_s": 2.0}}, "config": cfg,
           "flops": flops, "peak": {"bf16_flops_per_s": 197e12}}
    assert reader(dict(rec, out={"trace": None})) is None
    with session(tmp_path):
        spans.record("vfl.sched.moe", 10 ** 9, 10 ** 9, tokens=500,
                     held=8, pairs=jnp.int32(100), wave_load_max=20,
                     wave_load_sum=jnp.int32(80))
        spans.record("vfl.sched.moe", 3 * 10 ** 9, 3 * 10 ** 9,
                     tokens=1500, held=8, pairs=jnp.int32(400),
                     wave_load_max=80, wave_load_sum=jnp.int32(320))
    assert reader(rec) == pytest.approx(want, rel=1e-9)
    assert math.isfinite(reader(rec))
