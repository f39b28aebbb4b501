"""Primitive layers: norms, activations, RoPE, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamSpec


# ---------------------------------------------------------------- norms ----

def norm_specs(cfg, d: int):
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec((d,), "float32", (None,), "ones"),
                "bias": ParamSpec((d,), "float32", (None,), "zeros")}
    return {"scale": ParamSpec((d,), "float32", (None,), "ones")}


def apply_norm(cfg, p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


def rms_norm_simple(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ----------------------------------------------------------- activations ---

def activation(name: str, x, gate=None):
    if name == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate) * x
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {name!r}")


# ------------------------------------------------------------------ RoPE ---

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, orig_max_pos: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies of ``dim`` rotary dims (float32): pairs
    below the ramp keep theta's frequency, pairs past it are divided by
    ``factor``, and the ramp between runs from the pair that turns
    ``beta_fast`` times over ``orig_max_pos`` positions to the one that
    turns ``beta_slow`` times (computed in float64)."""
    def pair_of(rotations):
        return (dim * math.log(orig_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_args(cfg, dim: int) -> dict:
    """``apply_rope``'s keywords for ``dim`` rotary dims of ``cfg``:
    empty for plain RoPE; YaRN's frequencies, and the pair layout, where
    the config says so. YaRN's cos/sin scale, mscale / mscale_all_dim,
    is 1 (DeepSeek-V3 publishes both as 1) and is not applied."""
    kw = {}
    if cfg.rope_scaling == "yarn":
        kw["inv"] = yarn_inv_freq(dim, cfg.rope_theta, cfg.rope_factor,
                                  cfg.rope_orig_max_pos, cfg.yarn_beta_fast,
                                  cfg.yarn_beta_slow)
    elif cfg.rope_scaling != "none":
        raise ValueError(f"unknown rope_scaling {cfg.rope_scaling!r}")
    if cfg.rope_interleaved:
        kw["interleaved"] = True
    return kw


def softmax_scale_factor(cfg) -> float:
    """What YaRN multiplies the attention softmax scale by: mscale^2 at
    ``mscale_all_dim`` (1 without YaRN)."""
    if cfg.rope_scaling != "yarn" or not cfg.yarn_mscale_all_dim:
        return 1.0
    return yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) ** 2


def apply_rope(x, positions, theta: float, has_heads: bool = True, *,
               inv=None, interleaved: bool = False):
    """x: (..., S, H, hd) if has_heads else (..., S, hd); positions: (S,)
    (or (1,) for decode — broadcasts). ``inv`` replaces theta's inverse
    frequencies (YaRN); ``interleaved``
    rotates pairs (2i, 2i+1) as DeepSeek's modelling does: the dims are
    regrouped evens-then-odds and rotated by halves, so q and k come out
    in the regrouped order (their dot product is the interleaved one)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta) if inv is None else jnp.asarray(inv)
    ang = positions[..., :, None].astype(jnp.float32) * inv   # (S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if has_heads:                                      # align with (S, H, hd)
        cos, sin = cos[..., :, None, :], sin[..., :, None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        xf = jnp.swapaxes(xf.reshape(xf.shape[:-1] + (hd // 2, 2)),
                          -1, -2).reshape(xf.shape)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- embedding ---

def embed_specs(cfg):
    return {"table": ParamSpec((cfg.padded_vocab, cfg.d_model),
                               cfg.param_dtype, ("vocab", "embed"), "normal")}


def embed_lookup(p, tokens, *, iota: bool = False):
    """Token embedding. iota=True uses the one-hot-matmul form: on a
    vocab-sharded table the plain gather triggers GSPMD's 'involuntary full
    rematerialization' (the table is replicated per device); the matmul
    form keeps the contraction shard-local (§Perf)."""
    if not iota:
        return jnp.take(p["table"], tokens, axis=0)
    table = p["table"]
    V = table.shape[0]
    onehot = jax.nn.one_hot(tokens, V, dtype=table.dtype)
    return jnp.einsum("...v,vd->...d", onehot, table)


def unembed(p, x):
    """x (..., d) -> logits (..., padded_vocab)."""
    return jnp.einsum("...d,vd->...v", x, p["table"])
