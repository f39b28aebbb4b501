"""Plain reference of the paper's asynchronous cascaded protocol on its
tabular MLP (arXiv:2306.16077, Alg. 1 and sec. VI-A-b).

Clients: c_m = relu(x_m W_m + b_m). Server: logits = relu([c_1..c_M] W1
+ b1) W2 + b2, cross entropy. Each round t activates one party m_t and a
batch idx_t; the server keeps the latest embedding of every (party, row)
in a table and

1. computes the activated party's fresh embedding, puts it in the
   batch's stale embeddings, and takes one first-order SGD step on the
   cross entropy (Eq. 4), recording that loss h_t;
2. for the party: draws q unit-sphere directions u over (b, W), evaluates
   the loss (with the updated server, the other parties stale) at the
   clean and the perturbed party, and steps
   g = d/mu * mean_i (h_i - h_0) u_i (Eq. 3) at lr_client;
3. writes the fresh (pre-update) embedding into the table.

The randomness follows the engine's documented conventions, so the
reference sees the same parties, rows and directions: the run key is
split into (schedule, rows, directions); the schedule is a uniform draw
of a party per round, the rows uniform integers, the direction keys one
per round; a round's party key is fold_in(fold_in(round key, 2), 0); its
lane keys split that q ways and each lane key splits once per leaf in
the order (b, W).

Everything is float32 at ``highest`` precision. Two controls run the
same algebra one precision lower: ``dtype="bfloat16"`` rounds
parameters, data and activations to bfloat16 (below float32 at the
TPU's default precision); ``dots="bf16x3"`` computes every matrix
product as the three bfloat16 passes of the TPU's ``high`` precision
(below float32 at ``highest``), written out so that it rounds alike on
every backend. It imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def schedule(seed_key, T: int, M: int, n: int, bs: int):
    k_sched, k_idx, k_zoo = jax.random.split(seed_key, 3)
    parties = jax.random.choice(k_sched, M, (T,), p=jnp.ones(M) / M)
    rows = jax.random.randint(k_idx, (T, bs), 0, n)
    return parties, rows, jax.random.split(k_zoo, T)


def bf16x3(a, b):
    """a @ b as the TPU computes float32 at ``high`` precision, written
    out so that every backend computes it alike: each operand's high
    part truncated to bfloat16, its low part (the rest) rounded to
    bfloat16, and the three products hi*hi + hi*lo + lo*hi, each exact
    and accumulated in float32 (on a v5e this is within 1.6e-7 of the
    chip's own ``high``, of the largest output, where rounding the high
    part instead is 1.4e-5 away). ``reduce_precision`` and a bit mask
    keep the parts: a float32 -> bfloat16 -> float32 round trip the TPU's
    compiler may fold away, leaving one bfloat16 pass."""
    def split(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), F32)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    def mm(x, y):
        # products of bfloat16 values are exact in float32
        return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    (ah, al), (bh, bl) = split(a), split(b)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def matmul(a, b, dots):
    return a @ b if dots == "f32" else bf16x3(a, b)


def client_fwd(w, b, x, dots="f32"):
    return jax.nn.relu(matmul(x, w, dots) + b)


def server_loss(srv, c_all, yb, dots="f32"):
    M, B, e = c_all.shape
    h = c_all.transpose(1, 0, 2).reshape(B, M * e)
    h = jax.nn.relu(matmul(h, srv["w1"], dots) + srv["b1"])
    logits = (matmul(h, srv["w2"], dots) + srv["b2"]).astype(F32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, yb[:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def directions(key, f: int, e: int, q: int):
    """(u_b (q, e), u_w (q, f, e)), each lane of unit norm over both."""
    def one(k):
        kb, kw = jax.random.split(k, 2)
        ub = jax.random.normal(kb, (e,), F32)
        uw = jax.random.normal(kw, (f, e), F32)
        inv = jax.lax.rsqrt(jnp.sum(ub * ub) + jnp.sum(uw * uw))
        return ub * inv, uw * inv
    return jax.vmap(one)(jax.random.split(key, q))


@functools.partial(jax.jit, static_argnames=("T", "bs", "q", "dtype",
                                             "dots"))
def run_chunk(params, x_parts, y, seed_key, hp, *, T, bs, q,
              dtype="float32", dots="f32"):
    """T rounds from ``params``; returns (params, per-round losses (T,))."""
    dt = jnp.dtype(dtype)
    M, n, f = x_parts.shape
    e = params["clients"]["w"].shape[-1]
    d = float(f * e + e)
    cast = functools.partial(jax.tree.map, lambda a: a.astype(dt))
    params = cast(params)
    x_parts = x_parts.astype(dt)
    parties, rows, zkeys = schedule(seed_key, T, M, n, bs)
    fwd = functools.partial(client_fwd, dots=dots)
    loss_of = functools.partial(server_loss, dots=dots)
    table = jax.vmap(fwd)(params["clients"]["w"], params["clients"]["b"],
                          x_parts)
    mu = hp["mu"].astype(dt)

    def round_(carry, t_in):
        p, table = carry
        m, idx, key = t_in
        yb = y[idx]
        wm, bm = p["clients"]["w"][m], p["clients"]["b"][m]
        xm = x_parts[m, idx]
        c_stale = table[:, idx]
        c_fresh = fwd(wm, bm, xm)
        h, g = jax.value_and_grad(loss_of)(
            p["server"], c_stale.at[m].set(c_fresh), yb)
        srv = jax.tree.map(lambda w, gg: (w - hp["lr"] * gg).astype(dt),
                           p["server"], g)
        ub, uw = directions(jax.random.fold_in(jax.random.fold_in(key, 2),
                                               0), f, e, q)
        ub, uw = ub.astype(dt), uw.astype(dt)
        lanes = [c_fresh] + [fwd(wm + mu * uw[i], bm + mu * ub[i], xm)
                             for i in range(q)]
        losses = jnp.stack([loss_of(srv, c_stale.at[m].set(c), yb)
                            for c in lanes]).astype(F32)
        coef = (d / hp["mu"]) * (losses[1:] - losses[0]) / q
        gw = jnp.tensordot(coef, uw.astype(F32), axes=1)
        gb = jnp.tensordot(coef, ub.astype(F32), axes=1)
        clients = {
            "w": p["clients"]["w"].at[m].set(
                (wm - hp["lr_client"] * gw).astype(dt)),
            "b": p["clients"]["b"].at[m].set(
                (bm - hp["lr_client"] * gb).astype(dt)),
        }
        table = table.at[m, idx].set(c_fresh)
        return ({"clients": clients, "server": srv}, table), h.astype(F32)

    (params, _), losses = jax.lax.scan(round_, (params, table),
                                       (parties, rows, zkeys))
    return jax.tree.map(lambda a: a.astype(F32), params), losses
