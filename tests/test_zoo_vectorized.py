"""Tentpole coverage: the vectorized (stacked-lane) ZOO fan-out must be
numerically equivalent to the unrolled per-query oracle at a fixed PRNG
key, end to end — estimator, cascade step, Pallas kernel, async engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import VFLConfig
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core import async_engine, cascade, zoo
from repro.core.adapters import mlp_adapter, tabular_adapter
from repro.core.partition import merge_params, split_params
from repro.data import make_classification, vertical_partition
from repro.kernels.zoo_dual_matmul.ops import zoo_dual_matmul_stacked
from repro.kernels.zoo_dual_matmul.ref import zoo_dual_matmul_stacked_ref
from repro.models import common, tabular
from repro.optim import sgd

CLIENT_KEYS = ("embed",)


def tree_allclose(a, b, **kw):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **kw)


def quad_loss(w):
    return (0.5 * jnp.sum(jnp.square(w["a"]))
            + jnp.sum(w["b"] * w["a"][:3]), {"s": jnp.sum(w["a"])})


def make_toy():
    key = jax.random.key(0)
    params = {
        "embed": {"w": jax.random.normal(key, (8, 4)) * 0.3},
        "head": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                        (4, 3)) * 0.3},
    }
    x = jax.random.randint(jax.random.fold_in(key, 2), (16,), 0, 8)
    y = jax.random.randint(jax.random.fold_in(key, 3), (16,), 0, 3)

    def loss_fn(p, batch):
        h = jnp.take(p["embed"]["w"], batch["x"], axis=0)
        logits = h @ p["head"]["w"]
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, batch["y"][:, None], -1)[:, 0]
        return jnp.mean(lse - gold), {}

    return params, {"x": x, "y": y}, loss_fn


# ------------------------------------------------- estimator equivalence --

@pytest.mark.parametrize("dist", ["sphere", "normal"])
@pytest.mark.parametrize("q", [1, 4])
def test_stacked_gradient_matches_unrolled_oracle(dist, q):
    w = {"a": jnp.asarray(np.linspace(-1, 1, 8), jnp.float32),
         "b": jnp.ones(3, jnp.float32)}
    key = jax.random.key(42)
    g_u, l_u, a_u = zoo.zoo_gradient(key, quad_loss, w, 1e-3, dist, q,
                                     unrolled=True)
    g_s, l_s, a_s = zoo.zoo_gradient(key, quad_loss, w, 1e-3, dist, q)
    tree_allclose(g_u, g_s, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(float(l_u), float(l_s), rtol=1e-6)
    np.testing.assert_allclose(float(a_u["s"]), float(a_s["s"]), rtol=1e-5)


def test_stacked_gradient_matches_with_row_mask():
    w = {"emb": jax.random.normal(jax.random.key(7), (8, 4))}
    mask = {"emb": jnp.asarray([1., 0, 1, 1, 0, 0, 0, 0])}

    def loss(t):
        return jnp.sum(jnp.square(t["emb"])) * 0.5

    key = jax.random.key(3)
    g_u, _, _ = zoo.zoo_gradient(key, loss, w, 1e-3, "sphere", 4,
                                 row_mask=mask, unrolled=True)
    g_s, _, _ = zoo.zoo_gradient(key, loss, w, 1e-3, "sphere", 4,
                                 row_mask=mask)
    tree_allclose(g_u, g_s, rtol=2e-5, atol=1e-6)
    # masked rows never receive gradient on either path
    assert np.all(np.asarray(g_s["emb"])[np.asarray([1, 4, 5, 6, 7])] == 0)


def test_sample_directions_match_per_key_draws():
    """Lane l of the stacked draw == sample_direction(split(key, q)[l])."""
    tree = {"a": jnp.zeros((5, 3)), "b": jnp.zeros(7)}
    key = jax.random.key(11)
    u_stack, d_eff = zoo.sample_directions(key, tree, 3, "sphere")
    for l, k in enumerate(jax.random.split(key, 3)):
        u_l, d_l = zoo.sample_direction(k, tree, "sphere")
        tree_allclose(jax.tree.map(lambda u: u[l], u_stack), u_l,
                      rtol=1e-6, atol=0)
    assert d_eff.shape == (3,)
    np.testing.assert_allclose(np.asarray(d_eff), 22.0)


# --------------------------------------------------- cascade equivalence --

@pytest.mark.parametrize("q", [1, 4])
def test_fused_cascade_step_matches_unrolled_oracle(q):
    params, batch, loss_fn = make_toy()
    key = jax.random.key(5)
    outs = {}
    for fused in (True, False):
        vfl = VFLConfig(mu=1e-3, zoo_queries=q, fused_dual=fused,
                        lr_server=0.05, lr_client=0.05)
        opt = sgd(0.05)
        step = jax.jit(cascade.make_cascaded_step(loss_fn, CLIENT_KEYS, vfl,
                                                  opt))
        outs[fused] = step(params, opt.init(params), batch, key)
    p_f, _, o_f = outs[True]
    p_u, _, o_u = outs[False]
    # tolerance note: the ZOO signal (ĥ−h) is a catastrophic cancellation
    # (~1e-5 here) amplified by φ/μ ≈ 3e4, so two float32 evaluation
    # orders legitimately differ at the 1e-4 level in the updated params
    tree_allclose(p_f, p_u, rtol=2e-3, atol=5e-4)
    np.testing.assert_allclose(float(o_f.loss), float(o_u.loss), rtol=1e-6)
    np.testing.assert_allclose(float(o_f.loss_perturbed),
                               float(o_u.loss_perturbed), rtol=1e-5)
    np.testing.assert_allclose(float(o_f.grad_client_norm),
                               float(o_u.grad_client_norm), rtol=5e-3)


class _GradsOut:
    """An optimizer whose update returns the gradients it is given, so a
    step's new params are its merged gradient."""
    def init(self, params):
        return None

    def update(self, grads, state, params):
        return grads, state


class _Recording:
    """A DP transport that keeps what crosses its downlink, both ways."""
    def __init__(self, inner):
        self.noise, self.inner = inner.noise, inner
        self.sent, self.recv = [], []

    def downlink(self, losses, key):
        out = self.inner.downlink(losses, key)
        self.sent.append(losses)
        self.recv.append(out)
        return out


@pytest.mark.parametrize("q", [1, 4])
def test_fused_cascade_server_grad_is_the_clean_lane_grad(q):
    """Eq. 4 on the fused path: the server's gradient is ``jax.grad`` of
    the clean lane's loss alone, in float32."""
    params, batch, loss_fn = make_toy()
    vfl = VFLConfig(mu=1e-3, zoo_queries=q, lr_server=0.05, lr_client=0.05)
    step = jax.jit(cascade.make_cascaded_step(loss_fn, CLIENT_KEYS, vfl,
                                              _GradsOut()))
    grads, _, out = step(params, None, batch, jax.random.key(5))
    client, server = split_params(params, CLIENT_KEYS)
    loss, want = jax.value_and_grad(
        lambda s: loss_fn(merge_params(client, s), batch)[0])(server)
    tree_allclose(split_params(grads, CLIENT_KEYS)[1], want,
                  rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(float(out.loss), float(loss), rtol=1e-6)


def test_fused_cascade_downlink_carries_clean_then_lanes():
    """The 1+q losses handed to a DP transport's downlink are the clean
    lane's, then lanes 1..q in draw order; the client's Eq. 3 update is
    built from what the downlink returns, noise and all."""
    from repro.core.privacy import GaussianLossChannel
    from repro.federation.transport import Transport
    params, batch, loss_fn = make_toy()
    q, mu, key = 4, 1e-3, jax.random.key(6)
    vfl = VFLConfig(mu=mu, zoo_queries=q, lr_server=0.05, lr_client=0.05)
    wire = _Recording(Transport("cascaded", noise=GaussianLossChannel(
        clip=5.0, epsilon=0.5, delta=1e-5)))
    step = cascade.make_cascaded_step(loss_fn, CLIENT_KEYS, vfl,
                                      _GradsOut(), transport=wire)
    grads, _, out = step(params, None, batch, key)
    (sent,), (recv,) = wire.sent, wire.recv
    client, server = split_params(params, CLIENT_KEYS)
    u_stack, d_eff = zoo.sample_directions(key, client, q, vfl.zoo_dist)
    want = [loss_fn(params, batch)[0]] + [
        loss_fn(merge_params(zoo.perturb(
            client, jax.tree.map(lambda u: u[i], u_stack), mu), server),
            batch)[0] for i in range(q)]
    assert sent.shape == (1 + q,)
    np.testing.assert_allclose(np.asarray(sent), np.asarray(want),
                               rtol=1e-6)
    assert not np.allclose(np.asarray(recv), np.asarray(sent))
    g_client = zoo.grad_from_losses(u_stack, recv[1:], recv[0], mu,
                                    zoo.phi_factor(vfl.zoo_dist, d_eff))
    tree_allclose(split_params(grads, CLIENT_KEYS)[0], g_client,
                  rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(float(out.loss), float(sent[0]), rtol=0)
    np.testing.assert_allclose(float(out.loss_perturbed), float(sent[1]),
                               rtol=0)


def test_full_zoo_step_vectorized_matches_oracle():
    params, batch, loss_fn = make_toy()
    key = jax.random.key(9)
    res = {}
    for oracle in (True, False):
        vfl = VFLConfig(mu=1e-3, zoo_queries=4, lr_server=0.01,
                        lr_client=0.01, zoo_unrolled_oracle=oracle)
        opt = sgd(0.01)
        step = jax.jit(cascade.make_full_zoo_step(loss_fn, CLIENT_KEYS, vfl,
                                                  opt))
        res[oracle] = step(params, opt.init(params), batch, key)
    tree_allclose(res[True][0], res[False][0], rtol=1e-5, atol=1e-7)


# ----------------------------------------------------- stacked Pallas op --

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,q", [(128, 64, 128, 4), (64, 32, 16, 3),
                                     (128, 128, 128, 16)])
def test_zoo_dual_matmul_stacked_sweep(M, K, N, q, dtype):
    ks = jax.random.split(jax.random.key(M + K + N + q), 3)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype)
    us = jax.random.normal(ks[2], (q, K, N), dtype)
    y, y_hat = zoo_dual_matmul_stacked(x, w, us, 1e-2, bm=64,
                                       bn=min(64, N))
    ry, ry_hat = zoo_dual_matmul_stacked_ref(x, w, us, 1e-2)
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-1
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ry, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(y_hat, np.float32),
                               np.asarray(ry_hat, np.float32),
                               atol=tol, rtol=tol)


def test_stacked_kernel_lane_directions():
    """(ŷ_l − y)/μ must equal x@u_l per lane — the ZOO estimator's signal."""
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (128, 64))
    w = jax.random.normal(ks[1], (64, 128))
    us = jax.random.normal(ks[2], (4, 64, 128))
    y, y_hat = zoo_dual_matmul_stacked(x, w, us, 1e-3)
    np.testing.assert_allclose(np.asarray((y_hat - y[None]) / 1e-3),
                               np.einsum("mk,qkn->qmn", np.asarray(x),
                                         np.asarray(us)),
                               atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,q", [(128, 64, 128, 4), (64, 32, 128, 3)])
def test_stacked_kernel_bias_relu_epilogue(M, K, N, q, dtype):
    """The fused bias+ReLU epilogue (the tabular client path) matches the
    unfused oracle in interpret mode, lane for lane."""
    from repro.kernels.zoo_dual_matmul.ref import (
        zoo_dual_matmul_stacked_bias_relu_ref)
    ks = jax.random.split(jax.random.key(M + N + q), 5)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype)
    us = jax.random.normal(ks[2], (q, K, N), dtype)
    b = jax.random.normal(ks[3], (N,), jnp.float32)
    ub = jax.random.normal(ks[4], (q, N), jnp.float32)
    y, y_hat = zoo_dual_matmul_stacked(x, w, us, 1e-2, b=b, ub=ub,
                                       bm=64, bn=64)
    ry, ry_hat = zoo_dual_matmul_stacked_bias_relu_ref(x, w, us, b, ub, 1e-2)
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-1
    assert float(jnp.min(y)) >= 0.0 and float(jnp.min(y_hat)) >= 0.0
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ry, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(y_hat, np.float32),
                               np.asarray(ry_hat, np.float32),
                               atol=tol, rtol=tol)
    with pytest.raises(ValueError, match="both b and ub"):
        zoo_dual_matmul_stacked(x, w, us, 1e-2, b=b)


def test_tabular_pallas_lanes_match_xla_lanes():
    """tabular_adapter(use_pallas_lanes=True) — the fused-epilogue kernel
    path — produces the same (1+q) activation lanes as the XLA oracle."""
    from repro.core import zoo
    from repro.core.adapters import tabular_adapter
    cfg = PaperMLPConfig(n_features=512, n_classes=4, n_clients=4,
                         client_embed=128, server_embed=64)
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    c0 = jax.tree.map(lambda a: a[0], params["clients"])
    x = jax.random.normal(jax.random.key(1), (64, cfg.features_per_client))
    u_stack, _ = zoo.sample_directions(jax.random.key(2), c0, 3)
    lanes_pallas = tabular_adapter(cfg, use_pallas_lanes=True).client_lanes(
        c0, u_stack, 1e-3, x)
    lanes_xla = tabular_adapter(cfg).client_lanes(c0, u_stack, 1e-3, x)
    assert lanes_pallas.shape == (4, 64, cfg.client_embed)
    np.testing.assert_allclose(np.asarray(lanes_pallas),
                               np.asarray(lanes_xla), atol=2e-5, rtol=2e-5)


# ---------------------------------------------- async engine + adapters --

@pytest.fixture(scope="module")
def tabular_setup():
    cfg = PaperMLPConfig(n_features=32, n_classes=4, n_clients=4,
                         client_embed=16, server_embed=32)
    X, y = make_classification(0, 512, cfg.n_features, cfg.n_classes)
    Xp = jnp.asarray(vertical_partition(X, cfg.n_clients))
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    return cfg, Xp, jnp.asarray(y), params


def test_async_engine_mlp_adapter_smoke(tabular_setup):
    """The jitted scan drives a NON-tabular repro.models client/server pair
    (SwiGLU-MLP clients + SwiGLU-MLP server) through the same protocol."""
    _, Xp, y, _ = tabular_setup
    ad = mlp_adapter(n_clients=4, features=32, client_embed=16, d_ff=32,
                     server_embed=32, n_classes=4)
    params = ad.init_params(jax.random.key(1))
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05, zoo_queries=2)
    res = async_engine.run(
        async_engine.EngineConfig(method="cascaded", steps=150,
                                  batch_size=32),
        vfl, params, Xp, y, adapter=ad)
    assert np.isfinite(res.losses).all()
    assert res.losses[-15:].mean() < res.losses[:15].mean()


def test_async_engine_block_activation(tabular_setup):
    """block_size > 1 vmaps several concurrent client activations/round."""
    cfg, Xp, y, params = tabular_setup
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05)
    res = async_engine.run(
        async_engine.EngineConfig(method="cascaded", steps=120,
                                  batch_size=32, block_size=3),
        vfl, params, Xp, y)
    assert np.isfinite(res.losses).all()
    assert res.losses[-15:].mean() < res.losses[:15].mean()
    # with 3 of 4 clients active per round staleness stays lower than the
    # one-client schedule over the same horizon
    res_1 = async_engine.run(
        async_engine.EngineConfig(method="cascaded", steps=120,
                                  batch_size=32, block_size=1),
        vfl, params, Xp, y)
    assert res.mean_delay < res_1.mean_delay


def test_block_schedule_draws_distinct_clients():
    sched = async_engine.make_schedule(jax.random.key(0), 200, 5,
                                       block_size=3)
    assert sched.shape == (200, 3)
    s = np.asarray(sched)
    for t in range(200):
        assert len(set(s[t])) == 3, s[t]


def test_lanes_routing_matches_generic_path(tabular_setup):
    """use_lanes=True (adapter fused dual-pass) == the generic vectorized
    zoo_gradient path, trajectory-level, at a fixed engine seed."""
    cfg, Xp, y, params = tabular_setup
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05, zoo_queries=2)
    ad = tabular_adapter(cfg)
    kw = dict(method="cascaded", steps=25, batch_size=16)
    r_lanes = async_engine.run(
        async_engine.EngineConfig(use_lanes=True, **kw), vfl, params, Xp, y,
        adapter=ad)
    r_gen = async_engine.run(
        async_engine.EngineConfig(**kw), vfl, params, Xp, y, adapter=ad)
    # tolerance note: lanes compute x@w + μ(x@u) vs x@(w+μu) generically —
    # a float32 evaluation-order gap amplified by φ/μ over the trajectory,
    # and CPU matmul reduction order makes it run-to-run nondeterministic
    np.testing.assert_allclose(r_lanes.losses, r_gen.losses, atol=1e-3)


def test_pallas_lanes_match_jnp_lanes(tabular_setup):
    """Routing the stacked perturbation through the zoo_dual_matmul Pallas
    kernel reproduces the XLA lanes bit-for-bit at trajectory level."""
    cfg, Xp, y, params = tabular_setup
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05, zoo_queries=2)
    kw = dict(method="cascaded", steps=4, batch_size=16, use_lanes=True)
    r_jnp = async_engine.run(
        async_engine.EngineConfig(**kw), vfl, params, Xp, y,
        adapter=tabular_adapter(cfg))
    r_pl = async_engine.run(
        async_engine.EngineConfig(**kw), vfl, params, Xp, y,
        adapter=tabular_adapter(cfg, use_pallas_lanes=True))
    np.testing.assert_allclose(r_pl.losses, r_jnp.losses, atol=1e-5)


def test_default_adapter_reuses_compiled_runner(tabular_setup):
    """run() without adapter= must hit the compiled-runner cache on the
    second call (the adapter factories are memoized for this)."""
    cfg, Xp, y, params = tabular_setup
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05)
    ec = async_engine.EngineConfig(method="cascaded", steps=5, batch_size=8)
    before = async_engine._make_runner.cache_info()
    async_engine.run(ec, vfl, params, Xp, y)
    async_engine.run(ec, vfl, params, Xp, y)
    after = async_engine._make_runner.cache_info()
    assert after.hits >= before.hits + 1
    assert after.misses <= before.misses + 1


def test_engine_rejects_lanes_for_sync_methods(tabular_setup):
    cfg, Xp, y, params = tabular_setup
    with pytest.raises(ValueError, match="use_lanes"):
        async_engine.run(
            async_engine.EngineConfig(method="split", steps=2, batch_size=8,
                                      use_lanes=True),
            VFLConfig(), params, Xp, y)


def test_engine_rejects_block_for_sync_methods(tabular_setup):
    cfg, Xp, y, params = tabular_setup
    with pytest.raises(ValueError, match="block_size"):
        async_engine.run(
            async_engine.EngineConfig(method="syn-zoo", steps=2,
                                      batch_size=8, block_size=3),
            VFLConfig(), params, Xp, y)


def test_engine_rejects_lanes_without_hook(tabular_setup):
    cfg, Xp, y, _ = tabular_setup
    ad = mlp_adapter(n_clients=4, features=32, client_embed=16, d_ff=32,
                     server_embed=32, n_classes=4)
    params = ad.init_params(jax.random.key(1))
    with pytest.raises(ValueError, match="client_lanes"):
        async_engine.run(
            async_engine.EngineConfig(method="cascaded", steps=2,
                                      batch_size=8, use_lanes=True),
            VFLConfig(), params, Xp, y, adapter=ad)
