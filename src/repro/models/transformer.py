"""Decoder-only transformer assembly for all decoder families.

Covers: dense (internlm2/granite/phi3/nemotron), moe (qwen3/deepseek incl.
MLA + first-k-dense + MTP), vlm (internvl2 — stub patch embeds + projector),
ssm (rwkv6), hybrid (zamba2 — mamba2 trunk + shared attention block).

Layers are scanned (stacked params, ``lax.scan``) with optional remat so the
61-layer configs lower quickly and the HLO stays compact. KV caches ride the
scan as per-layer xs/ys.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ParamSpec, freeze_state, stack_layer_specs
from repro.models.layers import (apply_norm, embed_lookup, norm_specs,
                                 unembed)
from repro.models.mlp import mlp_apply, mlp_specs
from repro.sharding.rules import shard_constraint


# ============================================================ param specs ==

def _attn_block_specs(cfg, d_ff: Optional[int] = None, moe: bool = False):
    sp = {"ln1": norm_specs(cfg, cfg.d_model),
          "ln2": norm_specs(cfg, cfg.d_model)}
    if cfg.use_mla:
        sp["attn"] = attn.attention_specs(cfg)
    else:
        sp["attn"] = attn.attention_specs(cfg)
    if moe:
        sp["moe"] = moe_mod.moe_specs(cfg, cfg.d_model)
    else:
        sp["mlp"] = mlp_specs(cfg, cfg.d_model, d_ff or cfg.d_ff)
    return sp


def _rwkv_block_specs(cfg):
    return {"ln1": norm_specs(cfg, cfg.d_model),
            "tmix": rwkv_mod.rwkv_specs(cfg, cfg.d_model),
            "ln2": norm_specs(cfg, cfg.d_model),
            "cmix": rwkv_mod.rwkv_channel_mix_specs(cfg, cfg.d_model)}


def _mamba_block_specs(cfg):
    return {"ln1": norm_specs(cfg, cfg.d_model),
            "ssm": ssm_mod.ssm_specs(cfg, cfg.d_model)}


def backbone_specs(cfg, max_seq: int):
    """Full parameter spec tree for a decoder-only config."""
    sp = {"embed": {"table": ParamSpec((cfg.padded_vocab, cfg.d_model),
                                       cfg.param_dtype, ("vocab", "embed"))},
          "final_norm": norm_specs(cfg, cfg.d_model),
          "lm_head": {"table": ParamSpec((cfg.padded_vocab, cfg.d_model),
                                         cfg.param_dtype, ("vocab", "embed"),
                                         "scaled")}}
    if cfg.pos == "learned":
        sp["pos_embed"] = ParamSpec((max_seq, cfg.d_model), cfg.param_dtype,
                                    ("vocab", "embed"))
    if cfg.frontend_dim:
        sp["proj"] = {"w": ParamSpec((cfg.frontend_dim, cfg.d_model),
                                     cfg.param_dtype, ("frontend", "embed"),
                                     "scaled"),
                      "b": ParamSpec((cfg.d_model,), "float32", (None,), "zeros")}

    if cfg.family == "ssm":
        sp["blocks"] = stack_layer_specs(_rwkv_block_specs(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        inner = stack_layer_specs(_mamba_block_specs(cfg), cfg.attn_every)
        sp["blocks"] = stack_layer_specs(inner, n_super)
        sp["shared_block"] = _attn_block_specs(cfg)
    elif cfg.n_experts:
        n_moe = cfg.n_layers - cfg.first_k_dense
        sp["blocks"] = stack_layer_specs(
            _attn_block_specs(cfg, moe=True), n_moe)
        if cfg.first_k_dense:
            sp["dense_blocks"] = stack_layer_specs(
                _attn_block_specs(cfg, moe=False), cfg.first_k_dense)
        if cfg.n_mtp:
            sp["mtp"] = {"block": _attn_block_specs(cfg, moe=False),
                         "proj": ParamSpec((2 * cfg.d_model, cfg.d_model),
                                           cfg.param_dtype, ("embed", None),
                                           "scaled"),
                         "norm": norm_specs(cfg, cfg.d_model)}
    else:
        sp["blocks"] = stack_layer_specs(_attn_block_specs(cfg), cfg.n_layers)
    return sp


# ============================================================== blocks =====

def _attn_block_apply(cfg, p, x, *, positions, cache=None, cur_pos=None,
                      window=0, decode=False, window_gather=False,
                      gather_experts=False, paging=None):
    h = apply_norm(cfg, p["ln1"], x)
    if cfg.use_mla:
        a, new_cache = attn.mla_apply(cfg, p["attn"], h, positions=positions,
                                      cache=cache, cur_pos=cur_pos,
                                      window=window, paging=paging)
    else:
        a, new_cache = attn.attention_apply(
            cfg, p["attn"], h, positions=positions, cache=cache,
            cur_pos=cur_pos, window=window, window_gather=window_gather,
            paging=paging)
    if cfg.rs_outputs:
        # force the TP output projection's partial sums to land directly in
        # the seq-sharded residual layout => reduce-scatter, not all-reduce
        a = shard_constraint(a, ("batch", "seq_act", "embed_act"))
    x = x + a
    h = apply_norm(cfg, p["ln2"], x)
    aux, load = jnp.float32(0.0), None
    if "moe" in p:
        m, aux, load = moe_mod.moe_apply(cfg, p["moe"], h, decode=decode,
                                         gather_experts=gather_experts)
    else:
        m = mlp_apply(cfg, p["mlp"], h)
    if cfg.rs_outputs:
        m = shard_constraint(m, ("batch", "seq_act", "embed_act"))
    return x + m, new_cache, aux, load


def _rwkv_block_apply(cfg, p, x, *, state=None, active=None):
    h = apply_norm(cfg, p["ln1"], x)
    tstate = None if state is None else {"wkv": state["wkv"],
                                         "shift": state["shift"]}
    t, new_t = rwkv_mod.rwkv_time_mix(cfg, p["tmix"], h, state=tstate)
    x = x + t
    h2 = apply_norm(cfg, p["ln2"], x)
    prev_c = None if state is None else state["shift_c"].astype(x.dtype)
    c = rwkv_mod.rwkv_channel_mix(cfg, p["cmix"], h2, prev=prev_c)
    new_state = None
    if state is not None:
        new_state = {"wkv": new_t["wkv"], "shift": new_t["shift"],
                     "shift_c": h2[:, -1].astype(state["shift_c"].dtype)}
        if active is not None:
            new_state = jax.tree.map(
                lambda n, o: freeze_state(active, n, o), new_state, state)
    return x + c, new_state


def _mamba_block_apply(cfg, p, x, *, state=None, active=None):
    h = apply_norm(cfg, p["ln1"], x)
    s, new_state = ssm_mod.ssm_apply(cfg, p["ssm"], h, state=state)
    if state is not None and active is not None:
        new_state = jax.tree.map(
            lambda n, o: freeze_state(active, n, o), new_state, state)
    return x + s, new_state


# ======================================================== backbone passes ==

def _maybe_remat(cfg, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _boundary(cfg, x):
    """Block boundary: sequence-parallel residual sharding (the remat-saved
    tensor). See DESIGN.md §6 — 16× smaller activation checkpoints."""
    if cfg.seq_shard_acts:
        return shard_constraint(x, ("batch", "seq_act", "embed_act"))
    return x


def scan_apply(cfg, body, carry, xs, n: int):
    """lax.scan over stacked layer params, or an unrolled Python loop when
    cfg.scan_layers=False (used by the dry-run cost-model probes — XLA's
    cost_analysis counts a while-loop body ONCE, so probes unroll)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(n):
        xs_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xs_i)
        ys.append(y)
    # None subtrees (no cache, no expert load) stay None
    ys_stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys) if ys else None
    return carry, ys_stacked


def backbone_apply(cfg, params, x, *, positions, caches=None, cur_pos=None,
                   window=0, window_gather=False, gather_experts=False,
                   paging=None):
    """Run the stacked blocks. x: (B,S,d) embeddings.

    caches: family-specific stacked state (leading dim = layers), or None.
    ``paging`` (a :class:`repro.models.common.PageContext`) switches the
    sequence-indexed cache leaves to the paged-pool layout with per-row
    positions (the continuous scheduler's batched decode step); recurrent
    state leaves are then slot-batched and frozen on inactive rows.
    Returns (hidden (B,S,d), new_caches, aux_losses, stats): ``stats``
    is ``{"moe_load": (moe layers, B, n_experts_held) int32}`` where the
    expert layers hold a share (pairs per held expert, per row), else
    empty.
    """
    decode = caches is not None
    active = None if paging is None else paging.active

    if cfg.family == "ssm":
        def body(h, xs):
            p_l, st_l = xs
            h2, new_st = _rwkv_block_apply(cfg, p_l, _boundary(cfg, h),
                                           state=st_l, active=active)
            return h2, new_st
        body = _maybe_remat(cfg, body)
        x, new_caches = scan_apply(cfg, body, x, (params["blocks"], caches),
                                   cfg.n_layers)
        return x, new_caches, jnp.float32(0.0), {}

    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        shared_p = params["shared_block"]

        def super_body(h, xs):
            p_sup, st_sup, attn_cache = xs
            h = _boundary(cfg, h)

            # inner: attn_every mamba blocks
            def inner(h2, xs2):
                p_l, st_l = xs2
                h3, new_st = _mamba_block_apply(cfg, p_l, _boundary(cfg, h2),
                                                state=st_l, active=active)
                return h3, new_st
            h, new_sts = scan_apply(cfg, inner, h, (p_sup, st_sup),
                                    cfg.attn_every)
            # shared attention block (weights reused across sites)
            h, new_attn_cache, _, _ = _attn_block_apply(
                cfg, shared_p, h, positions=positions, cache=attn_cache,
                cur_pos=cur_pos, window=window, decode=decode,
                window_gather=window_gather, paging=paging)
            return h, (new_sts, new_attn_cache)
        super_body = _maybe_remat(cfg, super_body)

        if decode:
            ssm_states, attn_caches = caches
        else:
            ssm_states, attn_caches = None, None
        xs = (params["blocks"], ssm_states, attn_caches)
        x, new_caches = scan_apply(cfg, super_body, x, xs, n_super)
        return x, new_caches, jnp.float32(0.0), {}

    # attention families (dense / moe / vlm backbone)
    aux_total = jnp.float32(0.0)

    def body(carry, xs):
        h, aux = carry
        p_l, c_l = xs
        h2, new_c, a, load = _attn_block_apply(
            cfg, p_l, _boundary(cfg, h), positions=positions, cache=c_l,
            cur_pos=cur_pos, window=window, decode=decode,
            window_gather=window_gather, gather_experts=gather_experts,
            paging=paging)
        return (h2, aux + a), (new_c, load)
    body = _maybe_remat(cfg, body)

    if cfg.first_k_dense and cfg.n_experts:
        dense_caches = None if caches is None else caches["dense"]
        (x, aux_total), (new_dense, _) = scan_apply(
            cfg, body, (x, aux_total), (params["dense_blocks"], dense_caches),
            cfg.first_k_dense)
    else:
        new_dense = None

    n_main = (cfg.n_layers - cfg.first_k_dense
              if (cfg.first_k_dense and cfg.n_experts) else cfg.n_layers)
    main_caches = None
    if caches is not None:
        main_caches = caches["main"] if isinstance(caches, dict) and "main" in caches else caches
    (x, aux_total), (new_main, loads) = scan_apply(
        cfg, body, (x, aux_total), (params["blocks"], main_caches), n_main)

    if new_dense is not None:
        new_caches = {"dense": new_dense, "main": new_main}
    else:
        new_caches = new_main
    stats = {} if loads is None else {"moe_load": loads}
    return x, (new_caches if decode else None), aux_total, stats


# ============================================================== forward ====

def embed_inputs(cfg, params, inputs, *, positions):
    """Map raw inputs -> (B,S,d) embeddings. Handles VLM patch concat.

    This is the CLIENT part of the cascade partition (DESIGN.md §2)."""
    emb_scale = 1.0
    if cfg.family == "vlm" and "patch_embeds" in inputs:
        tokens = inputs["tokens"]                       # (B, S_text)
        patches = inputs["patch_embeds"]                # (B, Nv, frontend)
        te = embed_lookup(params["embed"], tokens, iota=cfg.iota_embed)
        pe = (jnp.einsum("bnf,fd->bnd", patches.astype(te.dtype),
                         params["proj"]["w"])
              + params["proj"]["b"].astype(te.dtype))
        x = jnp.concatenate([pe, te], axis=1)
    else:
        tokens = inputs["tokens"]
        x = embed_lookup(params["embed"], tokens, iota=cfg.iota_embed)
    if cfg.pos == "learned":
        pos_table = params["pos_embed"]
        pe = jnp.take(pos_table, jnp.clip(positions, 0, pos_table.shape[0] - 1),
                      axis=0)
        x = x + pe.astype(x.dtype)
    x = shard_constraint(x, ("batch", None, "embed_act"))
    return x * emb_scale


def forward(cfg, params, inputs, *, caches=None, cur_pos=None, window=0,
            window_gather=False, gather_experts=False):
    """Full forward. Training/prefill: inputs over S. Decode: S==1.

    Returns (logits (B,S,vocab), new_caches, aux)."""
    if caches is None:
        S = inputs["tokens"].shape[1]
        if cfg.family == "vlm" and "patch_embeds" in inputs:
            S += cfg.n_vision_tokens
        positions = jnp.arange(S)
    else:
        # decode positions: cur_pos for the classic one-token step, or a
        # cur_pos-offset run for a multi-token chunk (chunked prefill)
        positions = jnp.asarray(cur_pos) + jnp.arange(
            inputs["tokens"].shape[1])                  # (S,)
    x = embed_inputs(cfg, params, inputs, positions=positions)
    h, new_caches, aux, _ = backbone_apply(
        cfg, params, x, positions=positions, caches=caches, cur_pos=cur_pos,
        window=window, window_gather=window_gather,
        gather_experts=gather_experts)
    h = apply_norm(cfg, params["final_norm"], h)
    logits = unembed(params["lm_head"], h)
    logits = shard_constraint(logits, ("batch", None, "vocab_act"))
    return logits, new_caches, aux


# ============================================================= loss ========

def lm_loss(cfg, params, inputs, *, window=0, label_mask=None):
    """Next-token CE over the text positions. Returns (loss, aux_dict)."""
    logits, _, aux = forward(cfg, params, inputs, window=window)
    labels = inputs["labels"]
    if cfg.family == "vlm":
        # logits cover [vision; text]; predict text tokens only
        logits = logits[:, cfg.n_vision_tokens:]
    ce = softmax_xent(logits[:, :-1], labels[:, 1:], cfg.padded_vocab)
    mask = jnp.ones_like(labels[:, 1:], jnp.float32) if label_mask is None \
        else label_mask[:, 1:].astype(jnp.float32)
    loss = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    if cfg.n_mtp and "mtp" in params:
        loss = loss + 0.3 * _mtp_loss(cfg, params, inputs, window=window)
    return loss + aux, {"aux": aux}


def _mtp_loss(cfg, params, inputs, *, window=0):
    """DeepSeek-style multi-token-prediction head (depth-1): one extra
    block predicts t+2 from [emb(tok_t) ; emb(tok_{t+1})]. (Simplified:
    the combiner consumes embeddings rather than final hidden states, so
    the MTP head costs one block + one unembed — see DESIGN.md §8.)"""
    tokens, labels = inputs["tokens"], inputs["labels"]
    x = embed_lookup(params["embed"], tokens, iota=cfg.iota_embed)
    # combine shifted embedding with itself as a cheap proxy for h_t
    e_next = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
    comb = jnp.concatenate([x, e_next], axis=-1)
    h = jnp.einsum("bsd,de->bse", comb, params["mtp"]["proj"])
    h, _, _, _ = _attn_block_apply(cfg, params["mtp"]["block"], h,
                                positions=jnp.arange(h.shape[1]),
                                window=window)
    h = apply_norm(cfg, params["mtp"]["norm"], h)
    lg = unembed(params["lm_head"], h)
    ce = softmax_xent(lg[:, :-2], labels[:, 2:], cfg.padded_vocab)
    return jnp.mean(ce)


def softmax_xent(logits, labels, vocab):
    """Stable CE, SPMD-safe over a vocab-sharded logits dim.

    take_along_axis over a sharded dim makes GSPMD all-gather the full
    fp32 logits (tens of GB for 128k vocabs); the masked-reduction form
    below stays shard-local and only all-reduces (B,S) scalars."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vidx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(vidx == labels[..., None], logits, 0.0), axis=-1)
    return lse - gold
