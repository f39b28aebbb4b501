"""End-to-end behaviour tests for the paper's system.

Exercises the public API the way a user would: build a model from the
registry, train it with the cascaded VFL driver, serve it, and check the
paper's qualitative claims (cascaded ≈ FOO ≫ full-ZOO; no gradients on
the wire)."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.launch.serve import serve
from repro.launch.train import train

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_train_driver_cascaded_loss_decreases():
    res = train("phi3-mini-3.8b", steps=60, batch=8, seq=64,
                method="cascaded", lr=0.02, log_every=1000)
    assert res["loss_last"] < res["loss_first"]
    assert not res["wire_has_gradients"]


@pytest.mark.slow
def test_train_driver_methods_ordering():
    """Paper Table II at smoke scale: with the wire kept gradient-free,
    cascaded hybrid descends clearly faster than full-ZOO (whose server is
    also ZOO and therefore dimension-limited, Rmk IV.12)."""
    kw = dict(steps=200, batch=8, seq=64, log_every=1000)
    cas = train("phi3-mini-3.8b", method="cascaded", lr=0.05, **kw)
    zoo = train("phi3-mini-3.8b", method="zoo-vfl", lr=0.003, **kw)
    foo = train("phi3-mini-3.8b", method="split-learning", lr=0.05,
                steps=60, batch=8, seq=64, log_every=1000)
    assert foo["wire_has_gradients"]
    assert not cas["wire_has_gradients"]
    drop_cas = cas["loss_first"] - cas["loss_last"]
    drop_zoo = zoo["loss_first"] - zoo["loss_last"]
    assert drop_cas > 2.0 * drop_zoo, (drop_cas, drop_zoo)


@pytest.mark.slow
def test_serve_driver_families():
    for arch in ("granite-20b", "zamba2-2.7b", "whisper-medium"):
        res = serve(arch, batch=2, prompt_len=8, gen_len=8)
        assert res["gen_len"] == 8
        assert len(res["sample_output"]) == 8


def test_config_registry_complete():
    from repro.configs import INPUT_SHAPES, list_archs
    assert len(list_archs()) == 10
    assert set(INPUT_SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                                 "long_500k"}


def test_active_rows_shrinks_zoo_dimension():
    """Beyond-paper: active-row perturbation must not break training and
    keeps the client update supported on touched rows only."""
    res = train("phi3-mini-3.8b", steps=10, batch=4, seq=32,
                method="cascaded", active_rows=True, log_every=1000)
    assert np.isfinite(res["loss_last"])


def test_serve_full_layers_keeps_published_widths():
    """``--full --layers 2``: the published widths at a depth of 2."""
    from repro.configs import get_config
    published = get_config("phi3-mini-3.8b")
    res = serve("phi3-mini-3.8b", batch=1, prompt_len=2, gen_len=2,
                use_reduced=False, n_layers=2, n_clients=1)
    assert res["model"] == {
        "n_layers": 2, "d_model": published.d_model,
        "n_heads": published.n_heads, "n_kv_heads": published.n_kv_heads,
        "d_ff": published.d_ff, "vocab_size": published.vocab_size}
    assert (res["model"]["d_model"], res["model"]["vocab_size"]) == (
        3072, 32064)
    assert len(res["sample_output"]) == 2


def test_chip_smoke_fails_without_a_tpu():
    """The chip check has no CPU fallback: off the TPU it exits non-zero
    and never prints its ok line."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_respects_the_environment(monkeypatch):
    import jax

    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir_to_set() is None
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.cache_dir_to_set()
    assert first == compile_cache.cache_dir_to_set()
    assert pathlib.Path(first) == REPO / ".jax_cache"
