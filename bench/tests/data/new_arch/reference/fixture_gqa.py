"""Plain reference of the test fixture's ``fixture_gqa`` decoder: per
layer RMSNorm, causal attention with rotary positions in which each KV
head serves a group of consecutive query heads (scores taken per group,
the KV heads never copied), residual, RMSNorm, SwiGLU MLP, residual; a
final RMSNorm. It imports nothing of the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def items(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(F32)


def rope(x, theta):
    """x (B, S, ..., hd), rotate-half, positions 0..S-1."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(cfg, dot, p, x):
    B, S, d = x.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    G, hd = H // Hkv, d // H
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    q = dot("bsd,dh->bsh", h, p["attn"]["wq"]).reshape(B, S, Hkv, G, hd)
    k = dot("bsd,dh->bsh", h, p["attn"]["wk"]).reshape(B, S, Hkv, hd)
    v = dot("bsd,dh->bsh", h, p["attn"]["wv"]).reshape(B, S, Hkv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    s = dot("bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = dot("bkgqs,bskd->bqkgd", a, v).reshape(B, S, d)
    x = x + dot("bsh,hd->bsd", o, p["attn"]["wo"])
    h = rms_norm(x, p["ln2"]["scale"], eps)
    up = dot("bsd,df->bsf", h, p["mlp"]["w_up"])
    gate = dot("bsd,df->bsf", h, p["mlp"]["w_gate"])
    return x + dot("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   p["mlp"]["w_down"])


def hidden(cfg, dot, params, x):
    x, _ = jax.lax.scan(lambda h, p: (layer(cfg, dot, p, h), None), x,
                        params["blocks"])
    return rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
