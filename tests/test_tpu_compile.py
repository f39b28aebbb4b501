"""Compile the main path's kernels and train step for a TPU v5e, no chip.

The TPU compiler is installed beside the CPU backend and compiles for a
described ``v5e:2x2`` topology. What it refuses (a block that breaks the
TPU tiling, more scoped VMEM than a program may use, a program larger
than the chip's 16 GB) fails here at no chip time. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
pytest-xdist workers all import every test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"     # no compiler logs under /tmp
    # a program compiled for a described chip cannot be read back from
    # the persistent cache here; keep such compiles out of it
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "bias_relu"])
@pytest.mark.parametrize("M,K,N,dtype", [
    (256, 196, 128, "float32"),     # the paper's tabular client, M = 4
    (256, 3072, 3072, "bfloat16"),  # a client at phi3-mini's hidden width
    # full-K stripes that need more than the default 16 MB of scoped VMEM
    (128, 7168, 256, "float32"),
], ids=["paper_f32", "wide_bf16", "deep_k_f32"])
def test_stacked_kernels_compile(one_chip, fused, M, K, N, dtype):
    from repro.kernels.zoo_dual_matmul.kernel import (
        zoo_dual_matmul_stacked_bias_relu_pallas,
        zoo_dual_matmul_stacked_pallas)
    q = 4
    dtype = jnp.dtype(dtype)
    args = [_on(one_chip, (M, K), dtype), _on(one_chip, (K, N), dtype),
            _on(one_chip, (q, K, N), dtype)]
    if fused:
        fn = zoo_dual_matmul_stacked_bias_relu_pallas
        args += [_on(one_chip, (N,), dtype), _on(one_chip, (q, N), dtype)]
    else:
        fn = zoo_dual_matmul_stacked_pallas
    args.append(_on(one_chip, (), jnp.float32))
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def cascaded_step(one_chip):
    """The sync cascaded step at phi3-mini's published widths, 2 layers,
    batch 8 x seq 512, q = 4, compiled once for both tests below."""
    import _hlo
    return _hlo.cascaded_step_compiled(one_chip, n_layers=2, batch=8,
                                       seq=512, q=4)


def test_cascaded_train_step_fits_one_chip(cascaded_step):
    """The program fits one v5e's memory."""
    mem = cascaded_step.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < peak < V5E_HBM_BYTES, peak


def test_cascaded_step_backpropagates_the_clean_lane_only(cascaded_step):
    """The server's backward runs over the clean lane alone: the q = 4
    perturbed lanes are one forward pass over ``[4,8,512,*]``, and no
    array carries all 1+q = 5 lanes of the batch. Such an array means a
    backward, and its saved activations, over every lane: 2.3x the
    step's FLOPs at this size."""
    import re
    hlo = cascaded_step.as_text()
    assert re.search(r"\[4,8,512[,\]]", hlo)
    assert re.findall(r"\w+\[5,8,512[,\]][^ ]*", hlo) == []


@pytest.mark.parametrize("block", [1, 4])
def test_async_round_updates_table_in_place(one_chip, block):
    """The paper's tabular job at its size (60,000 rows, chunks of 2,000
    rounds): the v5e's program keeps the server's embedding table in
    place inside the scan, with no whole-table copy or layout change a
    round (before, one 123 MB copy into the gather's layout every
    round)."""
    import _hlo
    n = 60000
    hlo = _hlo.async_runner_hlo(n=n, block=block, steps=2000,
                                sharding=one_chip)
    assert " while(" in hlo
    assert _hlo.loop_copies(hlo, {f"f32[4,{n},128]",
                                  f"f32[{n},4,128]"}) == []


def test_held_expert_layer_at_deepseek_widths(one_chip):
    """One MoE layer of DeepSeek-V3 at its widths holding 8 of the 256
    experts (one chip of EP32), over a prefill chunk of 4 x 1,024
    tokens: the v5e's program runs the held pairs in a loop of 128-row
    tiles through one expert's weights at a time, and never gathers all
    4,096 x 8 pairs' rows or runs an expert on every token."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import common, moe

    cfg = dataclasses.replace(get_config("deepseek-v3-671b"),
                              n_experts_held=8)
    p = jax.tree.map(lambda s: _on(one_chip, s.shape, jnp.dtype(s.dtype)),
                     moe.moe_specs(cfg, cfg.d_model),
                     is_leaf=common.is_spec)
    x = _on(one_chip, (4, 1024, cfg.d_model), jnp.bfloat16)
    compiled = jax.jit(lambda p, x: moe.moe_apply_held(cfg, p, x)).lower(
        p, x).compile()
    hlo = compiled.as_text()
    assert " while(" in hlo
    assert "bf16[128,7168]" in hlo and "bf16[128,2048]" in hlo
    for every in ("[32768,7168]", "[4,1024,8,2048]", "[4096,8,2048]"):
        assert every not in hlo, every
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 10 ** 9, mem.temp_size_in_bytes
