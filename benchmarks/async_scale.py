"""Device-sharded async client block: block_size scaling sweep.

Runs the async engine's cascaded protocol at ``block_size ∈ {1, 4, 16}``
twice per point — on the single-device path and on the shard_map path
over a ``("data",)`` mesh of forced virtual host devices — and records

  * steady-state per-round wall clock (compile excluded; the runner is
    lru-cached, so the timed second ``run`` reuses the executable),
  * the sublinearity of per-round time in block_size (activating 16×
    the clients per round must cost well under 16× the wall clock), and
  * exactness: sharded ``block_size=1`` losses must match the existing
    single-device engine bitwise.

A CPU-only command of its own: ``main()`` holds JAX to the CPU and
forces ``--xla_force_host_platform_device_count=8`` before the backend
starts, so run it as ``PYTHONPATH=src python -m benchmarks.async_scale
[--full]``. Its times are CPU times; the same path on real chips is
``chip_smoke.py --four-chips``.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import VFLConfig
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core import async_engine
from repro.data import make_classification, vertical_partition
from repro.launch.mesh import make_client_mesh
from repro.models import common, tabular

BLOCKS = (1, 4, 16)
N_CLIENTS = 16      # divisible by every shard count we sweep


def _setup(n: int = 512, f: int = 64, c: int = 10, server_embed: int = 64):
    cfg = PaperMLPConfig(n_features=f, n_classes=c, n_clients=N_CLIENTS,
                         client_embed=32, server_embed=server_embed)
    X, y = make_classification(0, n, f, c)
    Xp = jnp.asarray(vertical_partition(X, N_CLIENTS))
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    return cfg, Xp, jnp.asarray(y), params


def _n_shards(block: int) -> int:
    """Largest shard count ≤ device_count dividing both block and M."""
    d = min(jax.device_count(), block)
    while block % d or N_CLIENTS % d:
        d -= 1
    return d


def bench_async_scale(fast: bool = True, row=None, blocks=BLOCKS):
    """Emit name,us_per_call,derived rows.

    Returns ({(path, block): us}, bitwise_equal_at_b1, growths_by_path)."""
    if row is None:
        def row(name, us, derived):
            print(f"{name},{us:.1f},{derived}", flush=True)

    cfg, Xp, y, params = _setup()
    steps = 30 if fast else 120
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05, zoo_queries=4)
    results = {}
    losses = {}
    for block in blocks:
        shards = _n_shards(block)
        mesh = make_client_mesh(shards)
        for label, kw in (("single", {}), ("sharded", {"mesh": mesh})):
            ec = async_engine.EngineConfig(method="cascaded", steps=steps,
                                           batch_size=64, block_size=block)
            t0 = time.perf_counter()
            async_engine.run(ec, vfl, params, Xp, y, **kw)  # compile+warm
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = async_engine.run(ec, vfl, params, Xp, y, **kw)
            us = (time.perf_counter() - t0) / steps * 1e6
            results[(label, block)] = us
            losses[(label, block)] = res.losses
            row(f"async_scale_{label}_b{block}", us,
                f"shards={shards if label == 'sharded' else 1};"
                f"compile_s={compile_s:.2f};"
                f"wire_bytes_per_round={res.wire_bytes // steps}")

    exact = bool(np.array_equal(losses[("single", blocks[0])],
                                losses[("sharded", blocks[0])]))
    row("async_scale_equivalence", 0.0,
        f"sharded_b{blocks[0]}_losses_bitwise_match_single={exact}")

    growths = {}
    for label in ("single", "sharded"):
        lo, hi = results[(label, blocks[0])], results[(label, blocks[-1])]
        growths[label] = growth = hi / max(lo, 1e-9)
        row(f"async_scale_{label}_scaling", 0.0,
            f"round_time_growth_b{blocks[0]}->b{blocks[-1]}={growth:.2f}x;"
            f"linear_would_be={blocks[-1] // blocks[0]}x;"
            f"sublinear={growth < blocks[-1] / blocks[0]}")
    return results, exact, growths


def _force_virtual_cpu_devices(n: int = 8) -> None:
    """Hold JAX to ``n`` virtual CPU devices; must run before the backend
    starts (the first device query)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
    jax.config.update("jax_platforms", "cpu")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", dest="fast", action="store_false", default=True)
    args = ap.parse_args()
    _force_virtual_cpu_devices()
    print("name,us_per_call,derived")
    print(f"# devices={jax.device_count()}")
    _, exact, growths = bench_async_scale(args.fast)
    # enforce the acceptance criteria so CI fails on a regression, not
    # just prints it
    assert exact, "sharded block=1 losses diverged from single-device"
    linear = BLOCKS[-1] / BLOCKS[0]
    assert growths["sharded"] < linear, (
        f"sharded per-round time grew {growths['sharded']:.2f}x for "
        f"{linear:.0f}x the block — not sublinear")


if __name__ == "__main__":
    main()
