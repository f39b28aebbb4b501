"""Device-sharded async engine: equivalence, wire accounting, validation.

The multi-device half runs in a subprocess that forces 8 virtual host
devices (``tests/_sharded_equiv_child.py``) — this process keeps the real
topology per conftest. The in-process half exercises the shard_map code
path on a 1-shard mesh, where it must be BITWISE identical to the
single-device engine."""
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo
from repro.configs import VFLConfig
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core import async_engine
from repro.core.privacy import round_messages
from repro.data import make_classification, vertical_partition
from repro.launch.mesh import make_client_mesh
from repro.models import common, tabular


@pytest.fixture(scope="module")
def setup():
    cfg = PaperMLPConfig(n_features=32, n_classes=4, n_clients=4,
                         client_embed=16, server_embed=32)
    X, y = make_classification(0, 256, cfg.n_features, cfg.n_classes)
    Xp = jnp.asarray(vertical_partition(X, cfg.n_clients))
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    return cfg, Xp, jnp.asarray(y), params


VFL = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05)


@pytest.mark.parametrize("block,steps,use_lanes,rows", [
    (1, 25, False, None),
    (4, 15, False, None),
    (1, 25, True, None),
    (4, 15, True, None),
    # fewer rows than the batch: every batch repeats sample indices
    (1, 25, False, 6),
    (4, 15, True, 6),
], ids=["block1", "block4", "block1-lanes", "block4-lanes",
        "block1-repeats", "block4-lanes-repeats"])
def test_sharded_mesh1_bitwise(setup, block, steps, use_lanes, rows):
    """The shard_map path on a trivial mesh reads the stale table before
    writing the round's rows, so it is the oracle for the single-device
    round, which writes first: every concurrent client still sees the
    others' rows stale, repeated sample indices included. Blocks too:
    the gather/psum boundaries are float-exact."""
    cfg, Xp, y, params = setup
    if rows is not None:
        Xp, y = Xp[:, :rows], y[:rows]
    ec = async_engine.EngineConfig(method="cascaded", steps=steps,
                                   batch_size=8, block_size=block,
                                   use_lanes=use_lanes)
    single = async_engine.run(ec, VFL, params, Xp, y)
    shard = async_engine.run(ec, VFL, params, Xp, y,
                             mesh=make_client_mesh(1))
    assert np.array_equal(single.losses, shard.losses)
    for a, b in zip(jax.tree.leaves(single.params),
                    jax.tree.leaves(shard.params)):
        assert jnp.array_equal(a, b)
    assert single.table.shape == shard.table.shape == (
        cfg.n_clients, Xp.shape[1], cfg.client_embed)
    assert jnp.array_equal(single.table, shard.table)
    assert single.mean_delay == shard.mean_delay
    assert single.max_delay_seen == shard.max_delay_seen


@pytest.mark.parametrize("use_lanes", [True, False],
                         ids=["lanes", "plain"])
def test_async_round_updates_table_in_place(use_lanes):
    """The compiled single-device round writes the server's embedding
    table in place: no copy of the whole table inside the scan's loop
    (reading the stale rows before the write made XLA copy it twice a
    round). ``test_tpu_compile.py`` checks the v5e's program too."""
    n = 1000
    hlo = _hlo.async_runner_hlo(n=n, block=1, use_lanes=use_lanes)
    assert " while(" in hlo
    assert _hlo.loop_copies(hlo, {f"f32[4,{n},128]",
                                  f"f32[{n},4,128]"}) == []


def test_sharded_eight_virtual_devices():
    """Full acceptance pair (bitwise b=1, allclose b=4/4-shard) on a forced
    8-virtual-device topology — own process, own XLA_FLAGS."""
    child = os.path.join(os.path.dirname(__file__), "_sharded_equiv_child.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, child], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, f"stdout:{proc.stdout}\nstderr:{proc.stderr}"
    assert "CHILD_OK" in proc.stdout


# --------------------------------------------------- engine-side ledger ---

def test_engine_result_wire_accounting(setup):
    """run() threads a q-aware Ledger: block rounds log block_size× the
    per-client messages, and EngineResult reports the totals."""
    cfg, Xp, y, params = setup
    q, block, steps, bs = 3, 2, 5, 8
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05, zoo_queries=q)
    ec = async_engine.EngineConfig(method="cascaded", steps=steps,
                                   batch_size=bs, block_size=block)
    res = async_engine.run(ec, vfl, params, Xp, y)
    per_client = sum(m.nbytes
                     for m in round_messages("cascaded", bs,
                                             cfg.client_embed, q))
    assert res.wire_bytes == steps * block * per_client
    assert not res.transmits_gradients
    assert len(res.ledger.messages) == steps * block * (2 * q + 2)


def test_engine_result_vafl_ships_gradients(setup):
    cfg, Xp, y, params = setup
    ec = async_engine.EngineConfig(method="vafl", steps=3, batch_size=8)
    res = async_engine.run(ec, VFL, params, Xp, y)
    assert res.transmits_gradients
    per_client = sum(m.nbytes
                     for m in round_messages("vafl", 8, cfg.client_embed))
    assert res.wire_bytes == 3 * per_client


def test_sync_method_logs_all_clients(setup):
    """Sync rounds activate every client: M× the per-client messages."""
    cfg, Xp, y, params = setup
    ec = async_engine.EngineConfig(method="syn-zoo", steps=4, batch_size=8)
    res = async_engine.run(ec, VFL, params, Xp, y)
    per_client = sum(m.nbytes
                     for m in round_messages("syn-zoo", 8, cfg.client_embed))
    assert res.wire_bytes == 4 * cfg.n_clients * per_client


# -------------------------------------------------------- validation ------

def test_mesh_rejects_sync_method(setup):
    cfg, Xp, y, params = setup
    ec = async_engine.EngineConfig(method="split", steps=2, batch_size=8)
    with pytest.raises(ValueError, match="asynchronous"):
        async_engine.run(ec, VFL, params, Xp, y, mesh=make_client_mesh(1))


def test_validate_mesh_divisibility_errors():
    fake = types.SimpleNamespace(shape={"data": 3})
    with pytest.raises(ValueError, match="block_size"):
        async_engine._validate_mesh(fake, False, "cascaded", block=4, M=6)
    with pytest.raises(ValueError, match="n_clients"):
        async_engine._validate_mesh(fake, False, "cascaded", block=3, M=4)
    with pytest.raises(ValueError, match="axis"):
        async_engine._validate_mesh(
            types.SimpleNamespace(shape={"model": 2}), False, "cascaded",
            block=2, M=4)


def test_make_client_mesh_bounds():
    with pytest.raises(ValueError):
        make_client_mesh(0)
    with pytest.raises(ValueError):
        make_client_mesh(jax.device_count() + 1)
    mesh = make_client_mesh()
    assert mesh.shape["data"] == jax.device_count()
