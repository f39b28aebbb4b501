"""Plain reference of DeepSeek-V3's layers over one chip's share of the
routed experts.

Written from the published description (arXiv:2412.19437 and the Hugging
Face ``DeepseekV3`` modelling and DeepSeek's inference code): per layer
RMSNorm, Multi-head Latent Attention, residual, RMSNorm, then a SwiGLU
MLP (the leading dense layers) or the MoE layer, residual; a final
RMSNorm (the head and the logits are ``reference/lm.py``'s).

MLA: q = W_qb(RMSNorm(W_qa x)) split per head into 128 "nope" and 64
rope dims; W_kva x gives the 512-wide latent (RMSNorm'd) and one 64-dim
rope key shared by the heads; W_kvb expands the latent per head into
k_nope and v. The rope dims rotate pairs (2i, 2i+1), written as the
modelling writes it: regroup evens-then-odds, then rotate by halves.
Frequencies are YaRN's (factor, original positions, beta_fast,
beta_slow; cos and sin times mscale / mscale_all_dim's, 1 here), and the
softmax scale is (nope + rope)^-1/2 times mscale(mscale_all_dim)^2.

Router ("noaux_tc", in float32): s = sigmoid(x W_r) over the router's
full width; the choice scores s + b (b the correction bias) rank the
groups by the sum of their two best; the topk_group best groups stay
and the rest are -inf (as DeepSeek's inference code masks them); the
top num_experts_per_tok experts by choice score are taken, and their
gates are s, divided by their sum, times routed_scaling_factor.

Departures, each what the configuration states: the layer holds the
experts ``[first_held_expert, + n_routed_experts)`` of the router's
``router_experts`` and adds only their gated outputs (the other chips'
experts add nothing here), plus the shared expert for every token; each
held expert runs on every token and its output is weighed by the gate
the token gave it (0 where it was not chosen). Weights stay in their
stored dtype and are cast to float32 per matmul (``lm.py``'s ``dot``).

It imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rms_norm_eps", "rope_theta", "n_group", "topk_group",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "n_routed_experts")


def items(cfg: dict) -> tuple:
    """The configuration's keys that the reference reads, hashable."""
    dep = cfg["deployment"]
    return (tuple((k, cfg[k]) for k in KEYS)
            + (("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),
               ("router_experts", dep["router_experts"]),
               ("first_held_expert", dep["first_held_expert"])))


def rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(F32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, rs):
    """YaRN's inverse frequencies (HF ``DeepseekV3YarnRotaryEmbedding``),
    in float64, as float32."""
    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / rs["factor"]
    inv_freq_mask = 1.0 - ramp
    return (freq_inter * (1 - inv_freq_mask)
            + freq_extra * inv_freq_mask).astype(np.float32)


def rope(x, positions, cfg):
    """x (B, S, H, rd) or (B, S, rd): pairs (2i, 2i+1) rotated."""
    rs = dict(cfg["rope_scaling"])
    rd = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(rd, cfg["rope_theta"], rs))
    ang = jnp.asarray(positions, F32)[:, None] * inv           # (S, rd/2)
    emb = jnp.concatenate([ang, ang], axis=-1)
    m = (yarn_mscale(rs["factor"], rs["mscale"])
         / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (rd // 2, 2)), -1, -2
                     ).reshape(x.shape)
    half = rd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(cfg, dot, p, h, positions):
    B, S, _ = h.shape
    H = cfg["num_attention_heads"]
    nd, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    rs = dict(cfg["rope_scaling"])
    q = dot("bsd,dr->bsr", h, p["wq_a"])
    q = dot("bsr,rh->bsh", rms_norm(q, p["q_norm"], eps), p["wq_b"])
    q = q.reshape(B, S, H, nd + rd)
    q = jnp.concatenate([q[..., :nd], rope(q[..., nd:], positions, cfg)],
                        axis=-1)
    ckv = dot("bsd,dr->bsr", h, p["wkv_a"])
    k_pe = rope(ckv[..., r:], positions, cfg)                   # (B,S,rd)
    kv = dot("bsr,rh->bsh", rms_norm(ckv[..., :r], p["kv_norm"], eps),
             p["wkv_b"]).reshape(B, S, H, nd + vd)
    k = jnp.concatenate(
        [kv[..., :nd], jnp.broadcast_to(k_pe[:, :, None], (B, S, H, rd))],
        axis=-1)
    v = kv[..., nd:]
    scale = ((nd + rd) ** -0.5
             * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)
    s = dot("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = dot("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * vd)
    return dot("bsh,hd->bsd", o, p["wo"])


def route(cfg, dot, p, h):
    """(gates (B,S,k) float32, expert ids (B,S,k)) over the router's
    full width."""
    B, S, _ = h.shape
    E, G = cfg["router_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(dot("bsd,de->bse", h, p["router"]))
    choice = (s + p["router_bias"].astype(F32)).reshape(B, S, G, E // G)
    group_scores = jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1)
    _, group_idx = jax.lax.top_k(group_scores, cfg["topk_group"])
    group_mask = jnp.any(group_idx[..., None] == jnp.arange(G), axis=-2)
    choice = jnp.where(group_mask[..., None], choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice.reshape(B, S, E),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], idx


def swiglu(dot, h, up, gate, down):
    a = jax.nn.silu(dot("bsd,df->bsf", h, gate)) * dot("bsd,df->bsf", h, up)
    return dot("bsf,fd->bsd", a, down)


def moe(cfg, dot, p, h):
    """The held experts' gated outputs plus the shared expert."""
    w, idx = route(cfg, dot, p, h)
    first = cfg["first_held_expert"]
    held = jnp.arange(first, first + cfg["n_routed_experts"])

    def one_expert(acc, xs):
        e, up, gate, down = xs
        g = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # (B,S)
        return acc + g[..., None] * swiglu(dot, h, up, gate, down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (held, p["w_up"], p["w_gate"], p["w_down"]))
    return out + swiglu(dot, h, p["shared_up"], p["shared_gate"],
                        p["shared_down"])


def layer(cfg, dot, p, x, positions):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, dot, p["attn"], rms_norm(x, p["ln1"]["scale"],
                                                     eps), positions)
    h = rms_norm(x, p["ln2"]["scale"], eps)
    if "moe" in p:
        return x + moe(cfg, dot, p["moe"], h)
    return x + swiglu(dot, h, p["mlp"]["w_up"], p["mlp"]["w_gate"],
                      p["mlp"]["w_down"])


def hidden(cfg, dot, params, x):
    """The dense layers, the MoE layers and the final norm over
    embeddings x (B, S, d)."""
    positions = jnp.arange(x.shape[1])

    def body(h, p):
        return layer(cfg, dot, p, h, positions), None

    x, _ = jax.lax.scan(body, x, params["dense_blocks"])
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
