"""Attention: GQA/MQA/MHA + MLA, causal/sliding-window, KV cache decode.

Two execution paths:
* ``mha_chunked`` — pure-jnp online-softmax attention with query chunking
  (the XLA path, also the oracle for the Pallas flash kernel).
* decode path — one new token against a (possibly sequence-sharded) cache,
  computed as a masked einsum over the full cache (baseline) or a gathered
  sliding window (``window_gather=True``, a §Perf optimization).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec
from repro.models.layers import (apply_rope, rms_norm_simple, rope_args,
                                 softmax_scale_factor)
from repro.sharding.rules import shard_constraint

NEG_INF = -1e30


# ------------------------------------------------------------ param specs --

def attention_specs(cfg, d: Optional[int] = None):
    d = d or cfg.d_model
    hd = cfg.resolved_head_dim
    pd = cfg.param_dtype
    if cfg.use_mla:
        sp = {
            "wq_a": ParamSpec((d, cfg.q_lora_rank), pd, ("embed", "latent"), "scaled"),
            "q_norm": ParamSpec((cfg.q_lora_rank,), "float32", (None,), "ones"),
            "wq_b": ParamSpec((cfg.q_lora_rank,
                               cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
                              pd, ("latent", "heads_out"), "scaled"),
            "wkv_a": ParamSpec((d, cfg.kv_lora_rank + cfg.qk_rope_dim), pd,
                               ("embed", None), "scaled"),
            "kv_norm": ParamSpec((cfg.kv_lora_rank,), "float32", (None,), "ones"),
            "wkv_b": ParamSpec((cfg.kv_lora_rank,
                                cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)),
                               pd, ("latent", "heads_out"), "scaled"),
            "wo": ParamSpec((cfg.n_heads * cfg.v_head_dim, d), pd,
                            ("heads_out", "embed"), "scaled"),
        }
        return sp
    sp = {
        "wq": ParamSpec((d, cfg.n_heads * hd), pd, ("embed", "heads_out"), "scaled"),
        "wk": ParamSpec((d, cfg.n_kv_heads * hd), pd, ("embed", "kv_out"), "scaled"),
        "wv": ParamSpec((d, cfg.n_kv_heads * hd), pd, ("embed", "kv_out"), "scaled"),
        "wo": ParamSpec((cfg.n_heads * hd, d), pd, ("heads_out", "embed"), "scaled"),
    }
    if cfg.qk_norm:
        sp["q_norm"] = ParamSpec((hd,), "float32", (None,), "ones")
        sp["k_norm"] = ParamSpec((hd,), "float32", (None,), "ones")
    return sp


def cache_specs(cfg, batch: int, seq: int):
    """Abstract KV-cache layout for decode shapes, in the activations'
    dtype."""
    dtype = cfg.dtype
    hd = cfg.resolved_head_dim
    if cfg.use_mla:
        # MLA caches the compressed latent + shared rope key only.
        width = cfg.kv_lora_rank + cfg.qk_rope_dim
        return {"latent": ParamSpec((cfg.n_layers, batch, seq, width), dtype,
                                    ("layers", "cache_batch", "cache_seq", None))}
    return {
        "k": ParamSpec((cfg.n_layers, batch, seq, cfg.n_kv_heads, hd), dtype,
                       ("layers", "cache_batch", "cache_seq", "cache_heads", None)),
        "v": ParamSpec((cfg.n_layers, batch, seq, cfg.n_kv_heads, hd), dtype,
                       ("layers", "cache_batch", "cache_seq", "cache_heads", None)),
    }


# ------------------------------------------------- chunked full attention --

def mha_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                q_chunk: int = 512, logit_softcap: float = 0.0,
                q_offset: int = 0, scale: Optional[float] = None):
    """q: (B, Sq, Hq, hd), k/v: (B, Skv, Hkv, hd). GQA via head grouping.

    Scans over query chunks; each chunk materializes (B, H, qc, Skv) scores
    — bounded memory for 32k prefill. ``window`` > 0 enables sliding-window
    masking (keys older than ``window`` are masked out).
    """
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    vd = v.shape[-1]                                     # may differ (MLA)
    G = Hq // Hkv
    scale = hd ** -0.5 if scale is None else scale
    qc = min(q_chunk, Sq)
    pad = (-Sq) % qc                                     # ragged Sq (whisper
    if pad:                                              # encoder: 1500)
        q = jnp.concatenate(
            [q, jnp.zeros((B, pad, Hq, hd), q.dtype)], axis=1)
        Sq_p = Sq + pad
    else:
        Sq_p = Sq
    n_chunks = Sq_p // qc

    qr = q.reshape(B, n_chunks, qc, Hkv, G, hd)
    kpos = jnp.arange(Skv)

    def one_chunk(carry, qi):
        qch, idx = qi                                    # (B, qc, Hkv, G, hd)
        qpos = q_offset + idx * qc + jnp.arange(qc)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qch.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = jnp.ones((qc, Skv), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    _, outs = jax.lax.scan(
        one_chunk, None,
        (qr.transpose(1, 0, 2, 3, 4, 5), jnp.arange(n_chunks)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, Hq, vd)
    return out[:, :Sq]


# ------------------------------------------------------------ decode path --

def decode_attend(q, k_cache, v_cache, cur_pos, *, window: int = 0,
                  logit_softcap: float = 0.0, window_gather: bool = False,
                  scale: Optional[float] = None):
    """One-token decode. q: (B, 1, Hq, hd); caches: (B, S, Hkv, hd).

    Baseline reads the full cache with a position mask. With
    ``window_gather`` and window>0, dynamic-slices only the live window —
    cuts the HBM read from S to W keys (§Perf optimization).
    """
    B, _, Hq, hd = q.shape
    _, S, Hkv, _ = k_cache.shape
    vd = v_cache.shape[-1]                               # may differ (MLA)
    G = Hq // Hkv
    scale = hd ** -0.5 if scale is None else scale
    qr = q.reshape(B, Hkv, G, hd).astype(jnp.float32) * scale
    cur_pos = jnp.asarray(cur_pos)

    if window_gather and window > 0 and window < S:
        assert cur_pos.ndim == 0, "window_gather needs a shared cur_pos"
        start = jnp.clip(cur_pos + 1 - window, 0, S - window)
        k_cache = jax.lax.dynamic_slice_in_dim(k_cache, start, window, axis=1)
        v_cache = jax.lax.dynamic_slice_in_dim(v_cache, start, window, axis=1)
        kpos = start + jnp.arange(window)
    else:
        kpos = jnp.arange(S)

    s = jnp.einsum("bhgd,bkhd->bhgk", qr, k_cache.astype(jnp.float32))
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    if cur_pos.ndim:                                     # per-row positions
        mask = kpos[None, :] <= cur_pos[:, None]
        if window > 0:
            mask &= kpos[None, :] > (cur_pos[:, None] - window)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    else:
        mask = kpos <= cur_pos
        if window > 0:
            mask &= kpos > (cur_pos - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, Hq, vd).astype(q.dtype)


# ------------------------------------------------------- paged cache ops --

def paged_update_gather(pool, row, dest_page, in_page, gather_rows):
    """Write one row per batch element into the page pool, then gather
    each element's full (masked) sequence extent back out.

    pool: (n_pages, page_size, *tail); row: (B, *tail) the new entry;
    dest_page/in_page: (B,) write coordinates (inactive rows land on the
    trash page — never read); gather_rows: (B, S_pad) flat pool rows.
    Returns (new_pool, gathered (B, S_pad, *tail))."""
    P, pg = pool.shape[:2]
    flat = pool.reshape((P * pg,) + pool.shape[2:])
    flat = flat.at[dest_page * pg + in_page].set(row.astype(pool.dtype))
    return flat.reshape(pool.shape), flat[gather_rows]


# -------------------------------------------------------------- GQA block --

def attention_apply(cfg, p, x, *, positions, cache=None, cur_pos=None,
                    window: int = 0, kv_override=None, causal=True,
                    window_gather: bool = False, paging=None):
    """Full attention sub-layer. Returns (out, new_cache_slice).

    cache: dict(k=(B,S,Hkv,hd), v=...) for this layer, or None. With
    ``paging`` set (the continuous scheduler's batched decode step) the
    cache leaves are shared page pools (n_pages, page_size, Hkv, hd)
    instead, ``cur_pos`` is a per-row (B,) vector, and the new k/v row is
    scattered through the slot's block table.
    kv_override: (B, Se, d) source for cross-attention (whisper decoder).
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype

    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, cfg.n_heads, hd)
    if kv_override is None:
        k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    else:
        src = kv_override
        Se = src.shape[1]
        k = jnp.einsum("bsd,dh->bsh", src, p["wk"]).reshape(B, Se, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,dh->bsh", src, p["wv"]).reshape(B, Se, cfg.n_kv_heads, hd)

    if cfg.qk_norm:
        q = rms_norm_simple(q, p["q_norm"])
        k = rms_norm_simple(k, p["k_norm"])

    if cfg.pos == "rope" and kv_override is None:
        rk = rope_args(cfg, hd)
        q = apply_rope(q, positions, cfg.rope_theta, **rk)
        k = apply_rope(k, positions, cfg.rope_theta, **rk)

    q = shard_constraint(q, ("batch", None, "heads_act", None))
    new_cache = None
    if paging is not None and kv_override is None:
        # paged decode: one token per slot. Scatter the new k/v row into
        # the shared pool through the slot's block table, gather the
        # slot's full seq_len extent back, and attend with the per-row
        # position mask — masked positions (stale page contents, the
        # zero page) contribute exactly 0.0, so this is bitwise-equal to
        # the dense slot-stacked path it replaces.
        assert S == 1, "paged attention decodes one token per slot"
        dest_page, in_page = paging.write_rows(cur_pos)
        rows = paging.gather_rows()
        pool_k, k_cache = paged_update_gather(
            cache["k"], k[:, 0], dest_page, in_page, rows)
        pool_v, v_cache = paged_update_gather(
            cache["v"], v[:, 0], dest_page, in_page, rows)
        o = decode_attend(q, k_cache, v_cache, cur_pos, window=window,
                          logit_softcap=cfg.attn_logit_softcap)
        new_cache = {"k": pool_k, "v": pool_v}
    elif cache is not None and kv_override is None:
        # decode: write this step's k/v at cur_pos, attend over the cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), cur_pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), cur_pos, axis=1)
        if S == 1:
            o = decode_attend(q, k_cache, v_cache, cur_pos, window=window,
                              logit_softcap=cfg.attn_logit_softcap,
                              window_gather=window_gather)
        else:
            # chunked prefill: the whole S-token chunk attends causally
            # over the updated cache in one pass. The causal mask offset
            # by cur_pos hides both the future and the not-yet-written
            # (zero) cache slots past cur_pos + S.
            o = mha_chunked(q, k_cache, v_cache, causal=True, window=window,
                            logit_softcap=cfg.attn_logit_softcap,
                            q_offset=cur_pos)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        o = mha_chunked(q, k, v, causal=causal and kv_override is None,
                        window=window,
                        logit_softcap=cfg.attn_logit_softcap)
    o = shard_constraint(o, ("batch", None, "heads_act", None))
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, cfg.n_heads * hd),
                     p["wo"]).astype(dt)
    return out, new_cache


# -------------------------------------------------------------- MLA block --

def _mla_absorbed_decode(cfg, p, q_nope, q_rope, lat, kr, cur_pos, *,
                         window: int, scale: float):
    """Weight-absorbed MLA decode (§Perf): fold W_uk into the query and
    W_uv into the output so attention runs directly against the latent
    cache — per step the cache read is S·(r+rd) instead of the expanded
    S·H·(nd+vd) (~72× less HBM traffic for deepseek-v3)."""
    B, S1, H, nd = q_nope.shape
    r = cfg.kv_lora_rank
    vd = cfg.v_head_dim
    wkv_b = p["wkv_b"].reshape(r, H, nd + vd)
    w_uk, w_uv = wkv_b[..., :nd], wkv_b[..., nd:]

    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))          # (B,1,H,r)
    s = (jnp.einsum("bshr,bkr->bhsk", q_lat, lat.astype(jnp.float32))
         + jnp.einsum("bshd,bkd->bhsk", q_rope.astype(jnp.float32),
                      kr.astype(jnp.float32))) * scale
    kpos = jnp.arange(lat.shape[1])
    cur_pos = jnp.asarray(cur_pos)
    if cur_pos.ndim:                                      # per-row positions
        mask = kpos[None, :] <= cur_pos[:, None]
        if window > 0:
            mask &= kpos[None, :] > (cur_pos[:, None] - window)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    else:
        mask = kpos <= cur_pos
        if window > 0:
            mask &= kpos > (cur_pos - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)                    # (B,H,1,S)
    ctx = jnp.einsum("bhsk,bkr->bshr", pattn, lat.astype(jnp.float32))
    o = jnp.einsum("bshr,rhv->bshv", ctx, w_uv.astype(jnp.float32))
    return o.astype(q_nope.dtype)                         # (B,1,H,vd)


def _mla_expand(cfg, p, latent, k_rope, dtype):
    """Expand latent -> per-head (k, v); k = [k_nope | k_rope(bcast)]."""
    B, S, _ = latent.shape
    H, nd, vd, rd = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.qk_rope_dim
    kv = jnp.einsum("bkr,rh->bkh", latent, p["wkv_b"]).reshape(B, S, H, nd + vd)
    kv = shard_constraint(kv, ("batch", None, "heads_act", None))
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rd))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    k = shard_constraint(k, ("batch", None, "heads_act", None))
    return k.astype(dtype), v.astype(dtype)


def mla_apply(cfg, p, x, *, positions, cache=None, cur_pos=None,
              window: int = 0, paging=None):
    """DeepSeek-V3 Multi-head Latent Attention.

    Cache stores only (kv_lora_rank + qk_rope_dim) per token; k/v are
    re-expanded from the latent for a prefill chunk, and a one-token
    decode either expands them too or (``mla_absorb``) scores in latent
    space. Query/key are concatenated [nope|rope] so the chunked GQA path
    is reused (scale = (nd+rd)^-1/2, times YaRN's mscale^2 where the
    config has YaRN, as DeepSeek's). Named scopes: ``mla.prefill`` over
    a multi-token chunk, ``mla.decode`` over a one-token step.
    """
    with jax.named_scope("mla.decode" if x.shape[1] == 1
                         else "mla.prefill"):
        return _mla(cfg, p, x, positions=positions, cache=cache,
                    cur_pos=cur_pos, window=window, paging=paging)


def _mla(cfg, p, x, *, positions, cache, cur_pos, window, paging):
    B, S, d = x.shape
    H = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = x.dtype
    scale = (nd + rd) ** -0.5 * softmax_scale_factor(cfg)
    rk = rope_args(cfg, rd)

    qa = rms_norm_simple(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rh->bsh", qa, p["wq_b"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, **rk)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = shard_constraint(q, ("batch", None, "heads_act", None))

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])        # (B,S,lora+rd)
    latent = rms_norm_simple(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv_a[..., cfg.kv_lora_rank:], positions,
                        cfg.rope_theta, has_heads=False,
                        **rk)                              # (B,S,rd) shared

    new_cache = None
    if paging is not None:
        # paged decode over the latent pool (n_pages, page_size, width):
        # same scatter-through-table + full-extent gather as the GQA path.
        assert S == 1, "paged MLA decodes one token per slot"
        packed = jnp.concatenate([latent, k_rope], axis=-1)
        dest_page, in_page = paging.write_rows(cur_pos)
        pool, lat_cache = paged_update_gather(
            cache["latent"], packed[:, 0], dest_page, in_page,
            paging.gather_rows())
        new_cache = {"latent": pool}
        lat = lat_cache[..., :cfg.kv_lora_rank].astype(dt)
        kr = lat_cache[..., cfg.kv_lora_rank:].astype(dt)
        if cfg.mla_absorb:
            o = _mla_absorbed_decode(cfg, p, q_nope, q_rope, lat, kr,
                                     cur_pos, window=window, scale=scale)
        else:
            k, v = _mla_expand(cfg, p, lat, kr, dt)
            o = decode_attend(q, k, v, cur_pos, window=window, scale=scale)
    elif cache is not None:
        packed = jnp.concatenate([latent, k_rope], axis=-1)
        lat_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], packed.astype(cache["latent"].dtype), cur_pos, axis=1)
        new_cache = {"latent": lat_cache}
        lat = lat_cache[..., :cfg.kv_lora_rank].astype(dt)
        kr = lat_cache[..., cfg.kv_lora_rank:].astype(dt)
        if S > 1:
            # chunked prefill: expand the latent cache once and run the
            # whole chunk causally against it (absorption is a per-token
            # decode optimization; chunks amortize the expansion anyway)
            k, v = _mla_expand(cfg, p, lat, kr, dt)
            o = mha_chunked(q, k, v, causal=True, window=window,
                            scale=scale, q_offset=cur_pos)
        elif cfg.mla_absorb:
            o = _mla_absorbed_decode(cfg, p, q_nope, q_rope, lat, kr,
                                     cur_pos, window=window, scale=scale)
        else:
            k, v = _mla_expand(cfg, p, lat, kr, dt)
            o = decode_attend(q, k, v, cur_pos, window=window, scale=scale)
    else:
        k, v = _mla_expand(cfg, p, latent, k_rope, dt)
        o = mha_chunked(q, k, v, causal=True, window=window, scale=scale)
    o = shard_constraint(o, ("batch", None, "heads_act", None))
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * vd),
                     p["wo"]).astype(dt)
    return out, new_cache
