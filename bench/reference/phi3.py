"""Plain reference of a phi3-style decoder and of one cascaded step.

Written from the published description (arXiv:2404.14219; the Hugging
Face ``Phi3`` modelling): token embedding, then per layer RMSNorm,
multi-head attention with rotary positions (rotate-half convention) and
a causal mask, residual, RMSNorm, SwiGLU MLP (silu(gate) * up, down),
residual; a final RMSNorm and an untied output head. Everything runs in
float32 at ``highest`` matmul precision, layer by layer under
``jax.checkpoint`` so the backward pass fits beside its weights.

It imports nothing of the program. Weights arrive in the program's
layout (stacked layers), made by the benchmark from the seed.

``dot`` switches the matmul precision: ``"f32"`` is the reference,
``"fp8"`` rounds every matmul operand to float8_e4m3fn (the control: one
step below the bfloat16 the configuration states).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def make_dot(mode: str):
    if mode == "f32":
        def dot(spec, a, b):
            return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                              precision=jax.lax.Precision.HIGHEST)
    elif mode == "fp8":
        def dot(spec, a, b):
            def q(x):
                return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
            return jnp.einsum(spec, q(a), q(b), preferred_element_type=F32)
    else:
        raise ValueError(f"unknown precision {mode!r}")
    return dot


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


def logits_of(cfg, dot, params, table, tokens):
    x = jnp.take(table, tokens, axis=0).astype(F32)
    h = hidden(cfg, dot, params, x)
    return dot("bsd,vd->bsv", h, params["lm_head"]["table"])


def lm_loss(cfg, dot, params, table, tokens):
    """Mean next-token cross entropy over every position but the last."""
    lg = logits_of(cfg, dot, params, table, tokens)[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - gold)


def direction(key, shape, q: int, i: int):
    """The estimator's i-th of q unit-sphere directions over the client's
    one leaf (the embedding table): lane key = split(key, q)[i], the
    leaf's key = split(lane key, 1)[0], u = N(0, I) / |N(0, I)|."""
    k = jax.random.split(key, q)[i]
    kl = jax.random.split(k, 1)[0]
    u = jax.random.normal(kl, shape, F32)
    return u * jax.lax.rsqrt(jnp.sum(jnp.square(u)))


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode", "q"),
                   donate_argnums=(0,))
def cascaded_step(params, tokens, key, hp, *, cfg_items, mode, q):
    """One step of the cascaded method on the global parameter tree.

    Server (every leaf but ``embed``): first-order SGD on the clean loss.
    Client (``embed.table``): Eq. 3 with q sphere lanes,
    g = d/mu * mean_i (h_i - h_0) u_i, applied at lr_client. Parameters
    stay in their stored dtype, as the configuration states.
    Returns (new params, clean loss, lane losses (1+q,))."""
    cfg = dict(cfg_items)
    dot = make_dot(mode)
    table = params["embed"]["table"]
    server = {k: v for k, v in params.items() if k != "embed"}

    def loss_at(srv32, tbl):
        return lm_loss(cfg, dot, srv32, tbl, tokens)

    h0, g_server = jax.value_and_grad(loss_at)(
        jax.tree.map(lambda a: a.astype(F32), server), table)
    mu = hp["mu"]
    lane_losses = [h0]
    for i in range(q):
        u = direction(key, table.shape, q, i)
        lane = (table.astype(F32) + mu * u).astype(table.dtype)
        lane_losses.append(loss_at(
            jax.tree.map(lambda a: a.astype(F32), server), lane))
    losses = jnp.stack(lane_losses)
    d = float(table.size)
    coef = (d / mu) * (losses[1:] - losses[0]) / q
    g_client = sum(coef[i] * direction(key, table.shape, q, i)
                   for i in range(q))
    new_server = jax.tree.map(
        lambda p, g: (p.astype(F32) - hp["lr"] * g).astype(p.dtype),
        server, g_server)
    new_table = (table.astype(F32)
                 - hp["lr"] * (g_client * (hp["lr_client"] / hp["lr"]))
                 ).astype(table.dtype)
    new = dict(new_server)
    new["embed"] = {"table": new_table}
    return new, h0, losses
