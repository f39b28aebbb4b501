"""Mixture-of-Experts with expert-parallel sharding.

Three dispatch paths (selected automatically):

* ``train/prefill`` — per-batch-row sort/scatter **capacity dispatch**:
  within each batch row, (S·k) token-expert pairs are sorted by expert id,
  positioned by rank-in-expert, and scattered into an (E, C, d) buffer with
  capacity C = ceil(S·k/E · capacity_factor). Expert matmuls are then dense
  batched GEMMs einsum'd against (E, d, f) weights — FLOPs ≈ active-token
  FLOPs × capacity_factor, and the expert dim shards over the "model" mesh
  axis (expert parallelism; the scatter induces the all-to-all).
  All per-row ops vectorize over the (data-sharded) batch dim, so dispatch
  never communicates across data shards.
* ``decode, large batch`` — dense loop over experts with masking: every
  expert computes every token. With B·k >= E every expert's weights must be
  read anyway, so decode stays memory-optimal even though FLOPs (cheap,
  decode is memory-bound) are inflated E/k×.
* ``decode, tiny batch`` (B·k << E, e.g. long_500k) — gather only the
  routed experts' weights (B·k weight rows instead of E) — §Perf
  optimization, enabled with ``gather_experts=True``.

A layer that holds a share of the experts (``n_experts_held``, one chip
of an expert-parallel deployment) takes none of these: it routes over
all ``n_experts``, then computes every pair routed to an expert it holds,
dropless, for prefill and decode alike (:func:`moe_apply_held`); what
the absent experts would add is left to their chips. With every expert
held it gives the dense decode path's result, but it does not replace
that path: its loop over tiles reads one expert's weights at a dynamic
index, which under GSPMD gathers an ``experts``-sharded weight whole on
every chip (the dense path's einsums keep each chip on its own experts),
and its trip count, set by the routing, makes it a ``while`` loop that
reverse mode cannot differentiate.

Routers: softmax top-k with a load-balance loss, or DeepSeek-V3's
sigmoid "noaux_tc" (:func:`_router_sigmoid`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec
from repro.models.layers import activation
from repro.sharding.rules import shard_constraint


def moe_specs(cfg, d: int):
    pd = cfg.param_dtype
    E, f = cfg.n_experts_held or cfg.n_experts, cfg.moe_d_ff
    sp = {
        # router is tiny (d×E fp32) — keep it replicated; FSDP-sharding it
        # makes GSPMD reshard the full fp32 activation stream instead
        "router": ParamSpec((d, cfg.n_experts), "float32", (None, None),
                            "scaled"),
        "w_up": ParamSpec((E, d, f), pd, ("experts", "expert_d", None), "scaled"),
        "w_down": ParamSpec((E, f, d), pd, ("experts", None, "expert_d"), "scaled"),
    }
    if cfg.act == "swiglu":
        sp["w_gate"] = ParamSpec((E, d, f), pd, ("experts", "expert_d", None), "scaled")
    if cfg.router_score == "sigmoid":
        # the correction bias (chooses experts, never weighs them)
        sp["router_bias"] = ParamSpec((cfg.n_experts,), "float32", (None,))
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        sp["shared_up"] = ParamSpec((d, fs), pd, ("embed", "ffn"), "scaled")
        sp["shared_down"] = ParamSpec((fs, d), pd, ("ffn", "embed"), "scaled")
        if cfg.act == "swiglu":
            sp["shared_gate"] = ParamSpec((d, fs), pd, ("embed", "ffn"), "scaled")
    return sp


def _router(cfg, p, x):
    """x (B,S,d) -> (gates (B,S,k) fp32 normalized, idx (B,S,k), aux loss)."""
    if cfg.router_score == "sigmoid":
        return _router_sigmoid(cfg, p, x)
    if cfg.router_score != "softmax":
        raise ValueError(f"unknown router_score {cfg.router_score!r}")
    # keep x bf16 on the wire; accumulate in fp32 via the dot itself
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # switch-style load-balance loss
    E = cfg.n_experts
    me = jnp.mean(probs, axis=(0, 1))                              # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2),
        axis=(0, 1)) / cfg.top_k                                   # (E,)
    aux = E * jnp.sum(me * ce) * cfg.load_balance_coef
    return gates, idx, aux


def _router_sigmoid(cfg, p, x):
    """DeepSeek-V3's router ("noaux_tc"), in float32 throughout.

    s = sigmoid(x W_r) over all ``n_experts``; the choice scores s + b
    (b the correction bias) rank the ``router_groups`` groups by the sum
    of their two best, the ``router_groups_kept`` best groups stay and
    the rest are masked to -inf, and the top-k experts by choice score
    are taken. Their gates are s (without b), divided by their sum,
    times ``routed_scale``. No auxiliary loss."""
    B, S, _ = x.shape
    E, G, k = cfg.n_experts, cfg.router_groups, cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    choice = s + p["router_bias"]
    if G > 1:
        grouped = choice.reshape(B, S, G, E // G)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, cfg.router_groups_kept)
        kept = jnp.sum(jax.nn.one_hot(keep, G, dtype=jnp.int32), -2) > 0
        choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            B, S, E)
    _, idx = jax.lax.top_k(choice, k)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg.routed_scale, idx, jnp.float32(0.0)


def _expert_ffn_grouped(cfg, p, xb):
    """xb (B,G,E,C,d) -> same, through the per-expert MLP (E sharded)."""
    h = jnp.einsum("bgecd,edf->bgecf", xb, p["w_up"])
    g = jnp.einsum("bgecd,edf->bgecf", xb, p["w_gate"]) \
        if cfg.act == "swiglu" else None
    h = activation(cfg.act, h, g)
    h = shard_constraint(h, ("batch", None, "experts", None, None))
    y = jnp.einsum("bgecf,efd->bgecd", h, p["w_down"])
    # pin the einsum output to expert-parallel BEFORE the reverse
    # all-to-all, otherwise GSPMD back-propagates the group sharding into
    # the einsum and replicates the expert weights (14 GiB for deepseek).
    return shard_constraint(y, ("batch", None, "experts", None, None))


def moe_apply_dispatch(cfg, p, x):
    """Grouped sort-based capacity dispatch (train & prefill).

    GATHER-ONLY + GROUP-LOCAL: each batch row's sequence is split into
    ``moe_groups`` groups aligned with the sequence-parallel shards, and
    dispatch (sort, rank, capacity) happens *within* a group — so all the
    index math and token gathers are shard-local, and the single reshard
    (group-sharded -> expert-sharded) of the (…,E,C,d) buffer lowers to an
    all-to-all, exactly the EP pattern of production MoE systems. Large
    scatters are avoided entirely (GSPMD would replicate them).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    G = min(cfg.moe_groups, S)
    while S % G:                                         # smoke-size guard
        G -= 1
    Sg = S // G
    N = Sg * k                                           # pairs per group
    C = max(int(math.ceil(N / E * cfg.capacity_factor)), 4)

    gates, idx, aux = _router(cfg, p, x)                 # (B,S,k)
    xg = x.reshape(B, G, Sg, d)
    xg = shard_constraint(xg, ("batch", "seq_act", None, None))
    flat_e = idx.reshape(B, G, N)                        # expert id per pair
    flat_g = gates.reshape(B, G, N)
    tok_of_pair = jnp.repeat(jnp.arange(Sg), k)[None, None]      # (1,1,N)
    tok_of_pair = jnp.broadcast_to(tok_of_pair, (B, G, N))

    order = jnp.argsort(flat_e, axis=-1, stable=True)    # sort pairs by expert
    inv_order = jnp.argsort(order, axis=-1)
    se = jnp.take_along_axis(flat_e, order, -1)
    st = jnp.take_along_axis(tok_of_pair, order, -1)

    counts = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=2)
    starts = jnp.cumsum(counts, axis=-1) - counts        # (B,G,E) exclusive
    rank = jnp.arange(N)[None, None] - jnp.take_along_axis(starts, se, -1)
    keep = rank < C

    # dispatch: gather the c-th pair of each expert from the sorted stream
    xs = jnp.take_along_axis(xg, st[..., None], axis=2)  # (B,G,N,d)
    idx_ec = starts[..., None] + jnp.arange(C)[None, None, None]  # (B,G,E,C)
    valid = (jnp.arange(C)[None, None, None]
             < jnp.minimum(counts, C)[..., None])
    idx_flat = jnp.clip(idx_ec.reshape(B, G, E * C), 0, N - 1)
    xb = jnp.take_along_axis(xs, idx_flat[..., None], axis=2)    # (B,G,EC,d)
    xb = xb * valid.reshape(B, G, E * C, 1).astype(xb.dtype)
    xb = xb.reshape(B, G, E, C, d)
    # the reshard below IS the all-to-all: groups -> experts
    xb = shard_constraint(xb, ("batch", None, "experts", None, None))

    yb = _expert_ffn_grouped(cfg, p, xb)
    yb = shard_constraint(yb, ("batch", "seq_act", None, None, None)) \
        .reshape(B, G, E * C, d)

    # return path: pair n reads slot (se[n], rank[n]) — another gather
    slot = jnp.clip(se * C + jnp.clip(rank, 0, C - 1), 0, E * C - 1)
    ys = jnp.take_along_axis(yb, slot[..., None], axis=2)        # (B,G,N,d)
    sg = jnp.take_along_axis(flat_g, order, -1)
    ys = ys * (sg * keep)[..., None]

    # unsort (gather via inverse permutation), pairs -> (Sg, k), sum
    ys = jnp.take_along_axis(ys, inv_order[..., None], axis=2)
    out = jnp.sum(ys.reshape(B, G, Sg, k, d).astype(jnp.float32), axis=3)
    out = out.reshape(B, S, d)

    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out.astype(x.dtype), aux


def moe_apply_dense(cfg, p, x):
    """Masked dense loop (decode with large batch): every expert runs every
    token; contributions are gated by the router mask."""
    B, S, d = x.shape
    E = cfg.n_experts
    gates, idx, aux = _router(cfg, p, x)
    comb = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                   * gates[..., None], axis=2)            # (B,S,E)

    h = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    g = jnp.einsum("bsd,edf->bsef", x, p["w_gate"]) if cfg.act == "swiglu" else None
    h = activation(cfg.act, h, g)
    y = jnp.einsum("bsef,efd->bsed", h, p["w_down"])
    out = jnp.einsum("bsed,bse->bsd", y.astype(jnp.float32), comb)

    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out.astype(x.dtype), aux


def moe_apply_gather(cfg, p, x):
    """Tiny-batch decode: gather the k routed experts' weights per token.
    Reads B·k expert weight sets instead of E (§Perf for long_500k)."""
    B, S, d = x.shape
    assert S == 1
    gates, idx, aux = _router(cfg, p, x)                  # (B,1,k)
    idxf = idx[:, 0]                                      # (B,k)
    up = jnp.take(p["w_up"], idxf, axis=0)                # (B,k,d,f)
    down = jnp.take(p["w_down"], idxf, axis=0)            # (B,k,f,d)
    h = jnp.einsum("bd,bkdf->bkf", x[:, 0], up)
    if cfg.act == "swiglu":
        gate_w = jnp.take(p["w_gate"], idxf, axis=0)
        g = jnp.einsum("bd,bkdf->bkf", x[:, 0], gate_w)
    else:
        g = None
    h = activation(cfg.act, h, g)
    y = jnp.einsum("bkf,bkfd->bkd", h, down)
    out = jnp.einsum("bkd,bk->bd", y.astype(jnp.float32), gates[:, 0])[:, None]
    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out.astype(x.dtype), aux


def _shared(cfg, p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["shared_up"])
    g = jnp.einsum("bsd,df->bsf", x, p["shared_gate"]) if cfg.act == "swiglu" else None
    h = activation(cfg.act, h, g)
    return jnp.einsum("bsf,fd->bsd", h, p["shared_down"]).astype(jnp.float32)


def _tile_rows(n_tokens: int, k: int, n_held: int) -> int:
    """Rows of one tile of the held-expert loop: about one expert's mean
    load (a power of two from 8 to 128), so that a tile is one MXU pass
    at prefill sizes and a decode step pays few padded rows."""
    mean = max(n_tokens * min(k, n_held) // n_held, 1)
    return min(128, max(8, 1 << (mean - 1).bit_length()))


def held_expert_ffn(cfg, p, x, local, gates):
    """Every (token, expert) pair routed to a held expert, none dropped.

    x (T, d); local (T, k) expert ids less ``expert_first`` (a pair is
    held where 0 <= local < n_experts_held); gates (T, k) float32.
    Pairs are sorted by held expert and cut into tiles of ``tm`` rows,
    each expert's run padded to whole tiles; a loop whose trip count is
    the number of tiles in use runs each tile through its expert's MLP
    and adds the gated rows into their tokens. The work is that of the
    pairs routed here (rounded up to tiles), not of every expert on
    every token, and no capacity applies. Returns (T, d) float32."""
    T, d = x.shape
    k, Eh = cfg.top_k, cfg.n_experts_held
    tm = _tile_rows(T, k, Eh)
    N = T * k
    held = (local >= 0) & (local < Eh)
    key = jnp.where(held, local, Eh).reshape(N)
    order = jnp.argsort(key, stable=True)          # held pairs by expert
    tok_sorted = (order // k).astype(jnp.int32)
    gate_sorted = gates.reshape(N)[order]
    counts = jnp.sum(jax.nn.one_hot(key, Eh + 1, dtype=jnp.int32),
                     axis=0)[:Eh]
    starts = jnp.cumsum(counts) - counts
    tiles = (counts + tm - 1) // tm
    tiles_end = jnp.cumsum(tiles)

    def tile(i, out):
        e = jnp.sum(tiles_end <= i)                # the tile's expert
        r = starts[e] + (i - (tiles_end[e] - tiles[e])) * tm + jnp.arange(tm)
        valid = r < starts[e] + counts[e]
        rc = jnp.minimum(r, N - 1)
        tok = jnp.where(valid, tok_sorted[rc], T)  # row T is thrown away
        g = jnp.where(valid, gate_sorted[rc], 0.0)
        xt = x[jnp.minimum(tok, T - 1)]
        h = xt @ p["w_up"][e]
        gt = xt @ p["w_gate"][e] if cfg.act == "swiglu" else None
        y = activation(cfg.act, h, gt) @ p["w_down"][e]
        return out.at[tok].add(y.astype(jnp.float32) * g[:, None])

    out = jax.lax.fori_loop(0, tiles_end[-1], tile,
                            jnp.zeros((T + 1, d), jnp.float32))
    return out[:T]


def moe_apply_held(cfg, p, x):
    """The layer of one chip of an expert-parallel deployment: route over
    all ``n_experts``, compute every pair routed to the held share,
    dropless, and add the shared expert for every token.

    Returns (out (B,S,d), aux, load): ``load`` (B, n_experts_held) int32
    counts each row's pairs per held expert. Named scopes ``moe.route``,
    ``moe.experts``, ``moe.shared``."""
    B, S, d = x.shape
    Eh = cfg.n_experts_held
    with jax.named_scope("moe.route"):
        gates, idx, aux = _router(cfg, p, x)
        local = idx - cfg.expert_first
        held = (local >= 0) & (local < Eh)
        load = jnp.sum(jax.nn.one_hot(jnp.where(held, local, Eh), Eh + 1,
                                      dtype=jnp.int32)[..., :Eh],
                       axis=(1, 2))
    with jax.named_scope("moe.experts"):
        out = held_expert_ffn(cfg, p, x.reshape(B * S, d),
                              local.reshape(B * S, -1),
                              gates.reshape(B * S, -1)).reshape(B, S, d)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + _shared(cfg, p, x)
    return out.astype(x.dtype), aux, load


def moe_apply(cfg, p, x, *, decode: bool = False, gather_experts: bool = False):
    """(out, aux, load): ``load`` is the held layer's per-row pairs per
    held expert, None on the paths that hold every expert."""
    if cfg.n_experts_held:
        return moe_apply_held(cfg, p, x)
    if decode and gather_experts and x.shape[0] * cfg.top_k <= cfg.n_experts:
        return moe_apply_gather(cfg, p, x) + (None,)
    if decode:
        return moe_apply_dense(cfg, p, x) + (None,)
    return moe_apply_dispatch(cfg, p, x) + (None,)
