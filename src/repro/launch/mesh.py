"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; smoke tests and benches see the real single device.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 = 256 chips per pod (v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Single-device mesh for CPU smoke runs (axes exist, size 1)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_client_mesh(n_shards: Optional[int] = None) -> jax.sharding.Mesh:
    """1-D ``("data",)`` mesh over the first ``n_shards`` local devices.

    This is the axis the async engine shard_maps the activated client
    block over (the ``"clients"`` logical rows of the embedding table
    partition along it). ``n_shards=None`` takes every visible device;
    tests/benches pass an explicit divisor of the block size so the same
    code runs on 1 real CPU device and on
    ``--xla_force_host_platform_device_count=8`` virtual meshes."""
    devices = jax.devices()
    n = len(devices) if n_shards is None else int(n_shards)
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"n_shards={n_shards} out of range for {len(devices)} devices")
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("data",))
