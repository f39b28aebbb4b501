"""What every LM architecture's plain reference shares: the matmul in
the reference's precision or the control's, the logits and the
next-token loss over an architecture's ``hidden``, and one cascaded step
(Eq. 3 with the program's lane-key convention).

An architecture is named by its configuration's ``model_type``; its
``reference/<model_type>.py`` gives ``hidden(cfg, dot, params, x)``, the
layers and the final norm over embeddings x (B, S, d) in float32. The
name is a static argument of each jit, so that each architecture traces
its own program. Everything runs in float32 at ``highest`` matmul
precision.

It imports nothing of the program. Weights arrive in the program's
layout (stacked layers), made by the benchmark from the seed.

``dot`` switches the matmul precision: ``"f32"`` is the reference,
``"fp8"`` rounds every matmul operand to float8_e4m3fn (the control: one
step below the bfloat16 the configurations state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness

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


def hidden_of(arch: str):
    """``hidden`` of ``reference/<arch>.py``."""
    return harness.load_module("reference", arch).hidden


def logits_of(arch, cfg, dot, params, table, tokens):
    x = jnp.take(table, tokens, axis=0).astype(F32)
    h = hidden_of(arch)(cfg, dot, params, x)
    return dot("bsd,vd->bsv", h, params["lm_head"]["table"])


def lm_loss(arch, cfg, dot, params, table, tokens):
    """Mean next-token cross entropy over every position but the last."""
    lg = logits_of(arch, cfg, dot, params, table, tokens)[:, :-1]
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


@functools.partial(jax.jit,
                   static_argnames=("arch", "cfg_items", "mode", "q"),
                   donate_argnums=(0,))
def cascaded_step(params, tokens, key, hp, *, arch, cfg_items, mode, q):
    """One step of the cascaded method on the global parameter tree of
    architecture ``arch``.

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
        return lm_loss(arch, cfg, dot, srv32, tbl, tokens)

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
