"""Plain reference of a phi3-style decoder.

Written from the published description (arXiv:2404.14219; the Hugging
Face ``Phi3`` modelling): token embedding, then per layer RMSNorm,
multi-head attention with rotary positions (rotate-half convention) and
a causal mask, residual, RMSNorm, SwiGLU MLP (silu(gate) * up, down),
residual; a final RMSNorm and an untied output head. Layers run under
``jax.checkpoint`` so the backward pass fits beside its weights; the
head, the loss and the cascaded step are ``reference/lm.py``'s.

It imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def items(cfg: dict) -> tuple:
    """The configuration's keys that the reference reads, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x (B, S, H, hd); positions (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.asarray(positions, F32)[..., None] * inv
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(cfg, dot, p, x, positions):
    """One decoder layer over x (B, S, d) float32, full causal attention."""
    B, S, d = x.shape
    H = cfg["num_attention_heads"]
    Hkv = cfg["num_key_value_heads"]
    hd = d // H
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    q = dot("bsd,dh->bsh", h, p["attn"]["wq"]).reshape(B, S, H, hd)
    k = dot("bsd,dh->bsh", h, p["attn"]["wk"]).reshape(B, S, Hkv, hd)
    v = dot("bsd,dh->bsh", h, p["attn"]["wv"]).reshape(B, S, Hkv, hd)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = dot("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
    x = x + dot("bsh,hd->bsd", o, p["attn"]["wo"])
    h = rms_norm(x, p["ln2"]["scale"], eps)
    up = dot("bsd,df->bsf", h, p["mlp"]["w_up"])
    gate = dot("bsd,df->bsf", h, p["mlp"]["w_gate"])
    return x + dot("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   p["mlp"]["w_down"])


def hidden(cfg, dot, params, x):
    """The stacked layers and the final norm over embeddings x (B, S, d)."""
    positions = jnp.arange(x.shape[1])
    body = jax.checkpoint(
        lambda h, p: (layer(cfg, dot, p, h, positions), None))
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
