"""Party-scoped Federation lifecycle: build → train → checkpoint/resume →
serve. Covers the party handles, per-party checkpoint isolation (the
server's directory contains zero client leaves and vice versa),
mid-training resume equivalence (ledger + DP totals exactly continued),
the split serve plane (fed.decode == global decode, serve traffic in the
ledger), and the RDP accountant."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import VFLConfig, get_config, reduced
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core.async_engine import EngineConfig
from repro.core.privacy import GaussianLossChannel, Ledger, serve_messages
from repro.federation import Federation, SessionState, Transport
from repro.models import common
from repro.models.model_api import build_cache_specs, build_model
from repro.optim import sgd

SEQ = 16


def tiny_cfg(**overrides):
    return reduced(get_config("phi3-mini-3.8b"), d_model=64, n_heads=2,
                   n_kv_heads=1, d_ff=128, vocab_size=256, **overrides)


@pytest.fixture(scope="module")
def lm_session():
    cfg = tiny_cfg()
    fed = Federation.build(cfg, VFLConfig(), EngineConfig(method="cascaded"),
                           n_clients=2, seq_len=SEQ)
    return cfg, fed


# ---------------------------------------------------- party handles -------

def test_parties_engine_layout(lm_session):
    cfg, fed = lm_session
    params = fed.init_params(jax.random.key(0))
    parties = fed.parties
    assert len(parties) == 3 and parties.server.name == "server"
    server = parties.server.owned(params)
    assert "embed" not in server and "lm_head" in server
    c0 = parties.clients[0].owned(params)
    assert c0["embed"]["table"].shape == (cfg.padded_vocab, cfg.d_model)
    assert jnp.array_equal(c0["embed"]["table"],
                           params["clients"]["embed"]["table"][0])
    # the split reassembles losslessly
    rebuilt = parties.assemble(server, [p.owned(params)
                                        for p in parties.clients])
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rebuilt)):
        assert jnp.array_equal(a, b)


def test_parties_global_layout(lm_session):
    cfg, fed = lm_session
    gp = common.materialize(build_model(cfg, max_seq=SEQ).param_specs,
                            jax.random.key(1))
    parties = fed.parties
    server = parties.server.owned(gp)
    client = parties.clients[0].owned(gp)
    assert set(client) == {"embed"} and "embed" not in server
    merged = parties.merge_global(server, client)
    assert set(merged) == set(gp)


# ------------------------------------- per-party checkpoint isolation -----

def _npz_keys(path, party_dir):
    return list(np.load(os.path.join(path, party_dir, "arrays.npz")).files)


def test_checkpoint_isolation_engine_layout(lm_session, tmp_path):
    """ISSUE acceptance: flatten the server party's checkpoint — no
    client-owned leaf key appears, and vice versa."""
    cfg, fed = lm_session
    params = fed.init_params(jax.random.key(0))
    path = fed.save(str(tmp_path / "ck"), params, step=7)
    assert sorted(os.listdir(path)) == ["client_00", "client_01",
                                        "server", "session.json"]
    server_keys = _npz_keys(path, "server")
    assert server_keys and not any(k.startswith("embed")
                                   for k in server_keys)
    for m in range(2):
        ckeys = _npz_keys(path, f"client_{m:02d}")
        assert ckeys == ["embed::table"]
        assert not any(k.startswith(("lm_head", "blocks", "final_norm"))
                       for k in ckeys)


def test_checkpoint_isolation_global_layout(lm_session, tmp_path):
    cfg, fed = lm_session
    model = build_model(cfg, max_seq=SEQ)
    gp = common.materialize(model.param_specs, jax.random.key(1))
    opt = sgd(0.1, momentum=0.9)
    path = fed.save(str(tmp_path / "ck"), gp, step=3,
                    opt_state=opt.init(gp))
    assert not any(k.startswith("embed") for k in _npz_keys(path, "server"))
    assert all(k.startswith("embed") for k in _npz_keys(path, "clients"))
    # the optimizer's momentum tree splits on the same boundary
    assert not any("embed" in k for k in _npz_keys(path, "opt_server"))
    assert all("embed" in k for k in _npz_keys(path, "opt_clients"))


# ----------------------------------------------- save/restore roundtrip ---

def test_save_restore_roundtrip(lm_session, tmp_path):
    cfg, fed0 = lm_session
    noise = GaussianLossChannel(clip=5.0, epsilon=0.5, accountant="rdp")
    fed = Federation.build(cfg, VFLConfig(zoo_queries=2),
                           EngineConfig(method="cascaded"), n_clients=2,
                           seq_len=SEQ, noise=noise)
    params = fed.init_params(jax.random.key(0))
    ledger = fed.transport.account(batch=4, embed=cfg.d_model, n_rounds=5,
                                   zoo_queries=2)
    path = fed.save(str(tmp_path / "ck"), params, step=5, ledger=ledger,
                    dp_releases=30)
    fed2, params2, state = Federation.restore(path)
    assert state.step == 5 and state.dp_releases == 30
    assert state.ledger.total_bytes == ledger.total_bytes
    assert state.ledger.bytes_by_kind() == ledger.bytes_by_kind()
    assert fed2.transport == fed.transport          # incl. the DP channel
    assert fed2.vfl == fed.vfl and fed2.model_cfg == cfg
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert jnp.array_equal(a, b) and a.dtype == b.dtype
    assert state.dp_spent(fed2.transport) == noise.spent(30)


def test_restore_paper_mlp_session(tmp_path):
    cfg = PaperMLPConfig(n_features=16, n_classes=3, n_clients=2,
                         client_embed=8, server_embed=8)
    fed = Federation.build(cfg, VFLConfig(), EngineConfig())
    params = fed.init_params(jax.random.key(0))
    fed2, params2, _ = Federation.restore(
        fed.save(str(tmp_path / "ck"), params))
    assert fed2.n_clients == 2
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert jnp.array_equal(a, b)


def test_restore_adapter_session_needs_model(tmp_path):
    from repro.core.adapters import mlp_adapter
    adapter = mlp_adapter(n_clients=2, features=8, client_embed=8, d_ff=16,
                          server_embed=8, n_classes=2)
    fed = Federation.build(adapter, VFLConfig(), EngineConfig())
    params = fed.init_params(jax.random.key(0))
    path = fed.save(str(tmp_path / "ck"), params)
    with pytest.raises(ValueError, match="adapter-built"):
        Federation.restore(path)
    fed2, params2, _ = Federation.restore(path, model_cfg=adapter)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert jnp.array_equal(a, b)


# ---------------------------------------------- mid-training resume -------

def test_train_resume_equivalence(tmp_path):
    """ISSUE acceptance: save at step k, restore, continue → allclose to
    the straight-through run at step 2k; ledger and (ε, δ) totals exactly
    continued."""
    from repro.checkpoint import load_tree
    from repro.launch.train import train

    noise = GaussianLossChannel(clip=10.0, epsilon=1.0)
    kw = dict(batch=4, seq=SEQ, log_every=1000, noise=noise)
    A = str(tmp_path / "straight")
    B1, B2 = str(tmp_path / "half"), str(tmp_path / "resumed")
    ra = train("phi3-mini-3.8b", steps=4, checkpoint_path=A, **kw)
    train("phi3-mini-3.8b", steps=2, checkpoint_path=B1, **kw)
    rb = train(steps=4, resume=B1, checkpoint_path=B2, log_every=1000)
    assert rb["start_step"] == 2

    for party in ("server", "clients"):
        ta, _, _ = load_tree(os.path.join(A, party))
        tb, _, _ = load_tree(os.path.join(B2, party))
        for (ka, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(ta),
                jax.tree_util.tree_leaves_with_path(tb)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-5, atol=2e-5, err_msg=f"{party}{ka}")
    ma = json.load(open(os.path.join(A, "session.json")))
    mb = json.load(open(os.path.join(B2, "session.json")))
    assert ma["ledger_counts"] == mb["ledger_counts"]
    assert ma["dp_releases"] == mb["dp_releases"]
    assert ma["dp_spent"] == mb["dp_spent"]
    assert ra["dp_epsilon"] == rb["dp_epsilon"]
    # optimizer's step clock continued, not reset (the bug this fixes)
    opt_s, _, _ = load_tree(os.path.join(B2, "opt_server"))
    assert int(opt_s["step"]) == 4


def test_train_resume_keeps_schedule_horizon(tmp_path):
    """A decaying schedule must continue the ORIGINAL total_steps on
    resume, not silently re-stretch to the new total."""
    from repro.launch.train import train
    p1, p2 = str(tmp_path / "a"), str(tmp_path / "b")
    train("phi3-mini-3.8b", steps=2, batch=2, seq=SEQ, schedule="cosine",
          log_every=1000, checkpoint_path=p1)
    train(steps=4, resume=p1, checkpoint_path=p2, log_every=1000)
    meta1 = json.load(open(os.path.join(p1, "session.json")))["metadata"]
    meta2 = json.load(open(os.path.join(p2, "session.json")))["metadata"]
    assert meta1["schedule_total_steps"] == 2
    assert meta2["schedule_total_steps"] == 2      # horizon preserved
    assert meta2["schedule"] == "cosine"


def test_train_reports_first_step_apart_from_steps_per_s():
    """``compile_s`` is the first step up to its loss fetch (its
    compilation included); ``steps_per_s`` times only the steps after
    it, so the two add back up to the loop's wall time."""
    from repro.launch.train import train
    res = train("phi3-mini-3.8b", steps=4, batch=2, seq=SEQ,
                log_every=1000)
    assert res["compile_s"] > 0 and res["steps_per_s"] > 0
    # the compiled first step is slower than a steady one, and is out
    assert res["compile_s"] > 1.0 / res["steps_per_s"]
    assert res["compile_s"] + 3 / res["steps_per_s"] == pytest.approx(
        res["wall_s"], abs=0.1, rel=0.05)
    one = train("phi3-mini-3.8b", steps=1, batch=2, seq=SEQ,
                log_every=1000)
    assert one["steps_per_s"] is None and one["compile_s"] > 0


def test_train_resume_rejects_exhausted_steps(tmp_path):
    from repro.launch.train import train
    p = str(tmp_path / "ck")
    train("phi3-mini-3.8b", steps=2, batch=4, seq=SEQ, log_every=1000,
          checkpoint_path=p)
    with pytest.raises(ValueError, match="total step count"):
        train(steps=2, resume=p)


# -------------------------------------------------- serve plane -----------

def _global_greedy_decode(cfg, model, gp, toks, gen_len, key, temperature):
    """The pre-session serve loop (launch/serve.py), inlined as oracle."""
    B, prompt_len = toks.shape
    max_seq = prompt_len + gen_len
    caches = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
        build_cache_specs(cfg, B, max_seq),
        is_leaf=lambda x: hasattr(x, "logical"))
    decode = jax.jit(model.decode_fn, donate_argnums=(2,))
    logits = None
    for t in range(prompt_len):
        logits, caches = decode(gp, {"tokens": toks[:, t:t + 1]}, caches, t)
    out = []
    for t in range(prompt_len, max_seq):
        lg = logits[:, -1].astype(jnp.float32)
        if temperature > 0:
            nxt = jax.random.categorical(jax.random.fold_in(key, 100 + t),
                                         lg / temperature, axis=-1)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        nxt = jnp.minimum(nxt, cfg.vocab_size - 1).astype(jnp.int32)
        out.append(np.asarray(nxt))
        logits, caches = decode(gp, {"tokens": nxt[:, None]}, caches, t)
    return np.stack(out, axis=1)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_decode_matches_global_serve(temperature):
    """ISSUE acceptance: fed.decode runs split inference with the
    training party split and matches global decode token for token."""
    cfg = tiny_cfg()
    B, PL, GL = 2, 4, 4
    fed = Federation.build(cfg, VFLConfig(), EngineConfig(), n_clients=2,
                           seq_len=PL + GL)
    model = build_model(cfg, max_seq=PL + GL)
    key = jax.random.key(0)
    gp = common.materialize(model.param_specs, key)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (B, PL), 0,
                              cfg.vocab_size)
    res = fed.decode(gp, toks, gen_len=GL, temperature=temperature, key=key)
    ref = _global_greedy_decode(cfg, model, gp, toks, GL, key, temperature)
    np.testing.assert_array_equal(res.tokens, ref)


def test_decode_wire_accounting():
    """Serve-time messages land in the ledger EXACTLY: one embedding up
    per decode call, token ids down only on the gen_len generation steps
    (the clients already own the prompt), never a gradient."""
    cfg = tiny_cfg()
    B, PL, GL = 2, 3, 5
    fed = Federation.build(cfg, VFLConfig(), EngineConfig(), n_clients=2,
                           seq_len=PL + GL)
    params = fed.init_params(jax.random.key(0))
    prior = Ledger()
    prior.messages.extend(serve_messages(B, cfg.d_model))   # pre-existing
    res = fed.decode(params, jnp.zeros((B, PL), jnp.int32), gen_len=GL,
                     ledger=prior)
    up, token = serve_messages(B, cfg.d_model)
    assert res.ledger is prior                      # extended, not replaced
    assert res.wire_bytes == ((PL + GL + 1) * up.nbytes
                              + (GL + 1) * token.nbytes)
    assert not res.transmits_gradients
    by_kind = res.ledger.bytes_by_kind()
    assert by_kind == {"embedding": (PL + GL + 1) * up.nbytes,
                       "token": (GL + 1) * token.nbytes}


def test_save_rejects_party_count_mismatch(tmp_path):
    """An adapter session whose stacked client dim disagrees with the
    session's n_clients must refuse a per-party save (rows would be
    silently dropped)."""
    from repro.core.adapters import mlp_adapter
    adapter = mlp_adapter(n_clients=4, features=8, client_embed=8, d_ff=16,
                          server_embed=8, n_classes=2)
    fed = Federation.build(adapter, VFLConfig(), EngineConfig())  # default 2
    params = adapter.init_params(jax.random.key(0))
    with pytest.raises(ValueError, match="n_clients=4"):
        fed.save(str(tmp_path / "ck"), params)
    fed4 = Federation.build(adapter, VFLConfig(), EngineConfig(),
                            n_clients=4)
    fed4.save(str(tmp_path / "ck"), params)
    assert sorted(p for p in os.listdir(tmp_path / "ck")
                  if p.startswith("client")) == [
        "client_00", "client_01", "client_02", "client_03"]


def test_decode_validation(lm_session):
    cfg, fed = lm_session
    params = fed.init_params(jax.random.key(0))
    with pytest.raises(ValueError, match="seq_len"):
        fed.decode(params, jnp.zeros((1, SEQ), jnp.int32), gen_len=4)
    tab = Federation.build(
        PaperMLPConfig(n_features=16, n_classes=3, n_clients=2,
                       client_embed=8, server_embed=8),
        VFLConfig(), EngineConfig())
    with pytest.raises(ValueError, match="serve plane"):
        tab.decode({"clients": {}, "server": {}},
                   jnp.zeros((1, 2), jnp.int32), gen_len=1)


def test_serve_driver_federated_equals_global():
    """launch/serve.py's split path and its global shim agree token for
    token (replicated client tables ⇒ identical model function)."""
    from repro.launch.serve import serve
    kw = dict(batch=2, prompt_len=4, gen_len=4, temperature=0.8)
    fed_res = serve("phi3-mini-3.8b", n_clients=2, **kw)
    glob_res = serve("phi3-mini-3.8b", n_clients=0, **kw)
    assert fed_res["mode"] == "federated" and glob_res["mode"] == "global"
    assert fed_res["sample_output"] == glob_res["sample_output"]
    assert fed_res["wire_bytes"] > 0 and not fed_res["wire_has_gradients"]


# ---------------------------------------------------- RDP accountant ------

def test_rdp_accountant_tighter_for_many_releases():
    basic = GaussianLossChannel(clip=1.0, epsilon=0.1, delta=1e-6)
    rdp = GaussianLossChannel(clip=1.0, epsilon=0.1, delta=1e-6,
                              accountant="rdp")
    assert rdp.sigma == basic.sigma            # same mechanism, same noise
    assert rdp.spent(0) == (0.0, 0.0)
    for k in (1_000, 10_000, 100_000):
        e_basic, d_basic = basic.spent(k)
        e_rdp, d_rdp = rdp.spent(k)
        assert 0 < e_rdp < e_basic < math.inf
        assert d_rdp == 1e-6 <= d_basic        # δ, not (k+1)δ
    # monotone in k
    es = [rdp.spent(k)[0] for k in (10, 100, 1_000)]
    assert es == sorted(es)


def test_rdp_accountant_validation():
    with pytest.raises(ValueError, match="accountant"):
        GaussianLossChannel(accountant="pld")
    # selectable through the Transport / session plumbing
    ch = GaussianLossChannel(clip=5.0, epsilon=0.5, accountant="rdp")
    t = Transport("cascaded", noise=ch)
    eps, delta = t.privacy_spent(1000)
    assert np.isfinite(eps) and delta == ch.delta


def test_session_state_defaults():
    s = SessionState()
    assert s.step == 0 and s.ledger.total_bytes == 0
    assert s.dp_spent(Transport("cascaded")) == (math.inf, 0.0)
    assert s.async_state is None


# ------------------------------------- durable async plane (wire plane) ---

def test_population_resume_under_faults(tmp_path):
    """ISSUE acceptance: kill a faulted ``run_population`` at round k,
    ``fed.save`` the async plane, ``Federation.restore``, continue — the
    combined trace is the straight-through run bitwise, with ledger
    multiset/byte totals and the DP budget exactly continued."""
    import collections

    from repro.configs.paper_mlp import PaperMLPConfig
    from repro.data import make_classification, vertical_partition
    from repro.wire import FaultPlan

    cfg = PaperMLPConfig(n_features=32, n_classes=4, n_clients=4,
                         client_embed=16, server_embed=32)
    X, y = make_classification(0, 256, cfg.n_features, cfg.n_classes)
    Xp = jnp.asarray(vertical_partition(X, cfg.n_clients))
    y = jnp.asarray(y)
    noise = GaussianLossChannel(clip=10.0, epsilon=1.0)
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05)
    ec = EngineConfig(method="cascaded", steps=16, batch_size=8)
    plan = FaultPlan(seed=5, drop=0.25, latency_ms=2.0, max_retries=1)

    fed = Federation.build(cfg, vfl, ec, noise=noise)
    params = fed.init_params(jax.random.key(0))
    full = fed.run_population(params, Xp, y, fault_plan=plan)

    half = fed.run_population(params, Xp, y, fault_plan=plan, until=7)
    path = fed.save(str(tmp_path / "ck"), half.params,
                    step=half.state.step, ledger=half.ledger,
                    dp_releases=half.dp_releases,
                    async_state=half.state)
    manifest = json.load(open(os.path.join(path, "session.json")))
    assert manifest["async_plane"] is True
    assert os.path.isdir(os.path.join(path, "async_plane"))

    fed2, params2, state = Federation.restore(path)
    assert state.async_state is not None and state.async_state.step == 7
    cont = fed2.run_population(params2, Xp, y, fault_plan=plan,
                               state=state.async_state,
                               ledger=state.ledger,
                               dp_releases=state.dp_releases)
    assert np.array_equal(full.losses[7:], cont.losses)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(full.params),
            jax.tree_util.tree_leaves_with_path(cont.params)):
        assert jnp.array_equal(a, b), pa
    np.testing.assert_array_equal(full.state.delays, cont.state.delays)
    np.testing.assert_array_equal(full.state.last_active,
                                  cont.state.last_active)
    assert full.state.clock_ms == cont.state.clock_ms
    # accounting continues exactly: message multiset, byte totals, DP
    assert (collections.Counter(full.ledger.messages)
            == collections.Counter(cont.ledger.messages))
    assert full.serialized_bytes == cont.serialized_bytes
    assert full.dp_releases == cont.dp_releases
    assert (full.epsilon, full.delta) == (cont.epsilon, cont.delta)
    assert np.isfinite(cont.epsilon)
    # the faults actually fired across the kill point
    assert (cont.stats["uplink_drops"] + cont.stats["downlink_drops"]) > 0
