"""Model adapters: the async engine's protocol for client/server pairs.

The asynchronous protocol simulation (``repro.core.async_engine``) only
needs three things from a model: a per-client feature extractor, a server
loss over the stacked client embeddings, and (optionally) a fused
"lanes" forward that evaluates the clean + q ZOO-perturbed client
forwards in one pass. Packaging those as a :class:`ModelAdapter` lets the
same jitted scan body drive ANY ``repro.models`` client/server pair — the
paper's tabular MLP, a SwiGLU-MLP stack, or (via
:func:`from_model_config`) any registered LM-scale ``ModelConfig``: the
clients own the embedding/bottom layers and the server owns the
transformer/MoE/SSM backbone plus head.

Adapters are frozen dataclasses so the engine can hash them as part of
its compiled-runner cache key.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import marks, tags
from repro.configs.base import ModelConfig
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core import zoo
from repro.core.partition import split_params
from repro.kernels.zoo_dual_matmul.ops import zoo_dual_matmul_stacked
from repro.models import common, mlp, tabular
from repro.models.common import ParamSpec


@dataclasses.dataclass(frozen=True)
class ModelAdapter:
    """Protocol bridging one model family into the async VFL engine.

    * ``client_forward(client_m, x_m)``        -> (bs, e) embedding
    * ``server_loss(server, c_all, y_batch)``  -> scalar loss over the
      (M, bs, e) table slice of all client embeddings
    * ``param_specs()``                        -> {"clients": stacked (M, ...)
      specs, "server": specs} for ``common.materialize``
    * ``client_lanes(client_m, u_stack, mu, x_m)`` (optional) -> (1+q, bs, e):
      lane 0 the clean forward, lanes 1..q the μ-perturbed forwards — the
      hook that routes the stacked ZOO fan-out through a fused kernel.
    * ``row_mask(client_m, x_m)`` (optional) -> 0/1 row-mask pytree
      matching ``client_m``: restricts the ZOO perturbation to the rows a
      batch actually touches (active-row mode — shrinks the effective ZOO
      dimension from vocab·d to uniq_tokens·d for embedding clients).
    * ``table_logical`` — per-dim logical axis names of the server's
      (M, n, e) embedding table; the engine's device-sharded path resolves
      its partitioning from these via ``repro.sharding.rules`` (the
      leading "clients" axis shards rows across the mesh "data" axis).

    Serve plane (optional — set by :func:`from_model_config`; tabular
    adapters have no decode concept and leave them ``None``):

    * ``client_embed(client_m, tokens)``  -> (bs, S, d): the owning party
      embeds its tokens — one call covers a single decode token (S=1) or
      a whole prompt span (chunked prefill), its only serve-time uplink.
    * ``server_decode(server, x, caches, cur_pos)`` -> (logits, caches):
      backbone + head over the uploaded embedding; KV/SSM caches and
      logits never leave the server.
    * ``server_prefill(server, x, caches, t0)`` -> (logits, caches,
      stats): consume a whole (bs, chunk, d) span upload in ONE compiled
      pass (positions t0 .. t0+chunk) — the chunked-prefill hook.
      ``stats`` is the backbone's (``transformer.backbone_apply``):
      per-row expert load where the expert layers hold a share, else
      empty. Optional: the serve engine falls back to the per-token step
      loop for adapters that leave it ``None``.
    * ``cache_specs(batch, max_seq)``     -> decode-state spec tree.
    * ``server_decode_paged(server, x, caches, tables, cur_pos, active,
      page_size)`` -> (logits, caches, stats): the continuous scheduler's
      batched paged decode step — x is (n_slots, 1, d) uploads, caches
      carry sequence leaves as shared page pools, ``tables`` (n_slots,
      pages_per_seq) block tables, ``cur_pos``/``active`` per-slot
      vectors. Optional; without it the scheduler cannot page.
    """
    name: str
    client_forward: Callable
    server_loss: Callable
    param_specs: Callable
    client_lanes: Optional[Callable] = None
    table_logical: Tuple[Optional[str], ...] = ("clients", None, None)
    row_mask: Optional[Callable] = None
    client_embed: Optional[Callable] = None
    server_decode: Optional[Callable] = None
    server_prefill: Optional[Callable] = None
    cache_specs: Optional[Callable] = None
    server_decode_paged: Optional[Callable] = None

    def init_params(self, key):
        return common.materialize(self.param_specs(), key)

    @tags.wire("up", accounted_by="Transport.account", kind="embedding",
               reason="Split-Learning oracle: fresh client embeddings "
                      "uploaded every step; the sync cascade meters it "
                      "per round")
    def global_loss(self, params, x_parts, y_batch):
        """Synchronous view: every client fresh, one loss (Split-Learning)."""
        c = marks.wire_boundary(
            jax.vmap(self.client_forward)(params["clients"], x_parts),
            kind="emb", direction="up")
        return self.server_loss(params["server"], c, y_batch)


# ========================================================== paper tabular ==

# NOTE: both factories are lru-cached so repeated calls with the same
# config return the SAME adapter object (same closure identities) — the
# engine's compiled-runner cache keys on the adapter, so without this every
# `run()` using a default adapter would retrace and recompile its scan.

@functools.lru_cache(maxsize=None)
def tabular_adapter(cfg: Optional[PaperMLPConfig] = None,
                    *, use_pallas_lanes: bool = False) -> ModelAdapter:
    """The paper's §VI-A-b MLP (single-FC clients, two-FC server).

    ``use_pallas_lanes=True`` computes the clean + q perturbed client
    forwards through the fused ``zoo_dual_matmul_stacked`` Pallas kernel
    with the bias+ReLU epilogue fused into the same pass (one read of
    x/W per output tile, HBM traffic constant in q, activated outputs
    written once); the default composes the same lanes with plain XLA ops.
    """
    cfg = cfg or PaperMLPConfig()

    @tags.party("server")
    def server_loss(server, c_all, y_batch):
        return tabular.xent(tabular.server_forward(server, c_all), y_batch)

    @tags.party("client")
    def client_lanes(client_m, u_stack, mu, x_m):
        w, b = client_m["w"], client_m["b"]
        if use_pallas_lanes:
            clean, pert = zoo_dual_matmul_stacked(x_m, w, u_stack["w"], mu,
                                                  b=b, ub=u_stack["b"])
        else:
            y = x_m @ w
            y_hat = y[None] + mu * jnp.einsum("bf,qfe->qbe", x_m,
                                              u_stack["w"])
            clean = jax.nn.relu(y + b)
            pert = jax.nn.relu(
                y_hat + (b[None] + mu * u_stack["b"])[:, None, :])
        return jnp.concatenate([clean[None], pert], axis=0)

    return ModelAdapter(
        name="tabular-pallas" if use_pallas_lanes else "tabular",
        client_forward=tabular.client_forward,
        server_loss=server_loss,
        param_specs=lambda: tabular.param_specs(cfg),
        client_lanes=client_lanes,
        table_logical=("clients", None, None),
    )


def example_engine_args(adapter: ModelAdapter, cfg: PaperMLPConfig, *,
                        n_rows: int = 16, batch: int = 4, block: int = 1,
                        seed: int = 0):
    """Small concrete engine-step arguments for jaxpr tracing.

    Builds the ``(params, table, m_blk, idx, key, x_parts, y)`` tuple a
    train-step closure takes (``Federation.traceable_train_step``), sized
    off the tabular protocol config — the certifier
    (``repro.analysis.certify``) traces the step over these with
    ``jax.make_jaxpr``; nothing is executed beyond zero-filled
    materialization, so no data or hardware is needed. ``params`` keeps
    its ``{"clients": ..., "server": ...}`` key paths: that is how the
    certifier labels which inputs are server-held."""
    specs = adapter.param_specs()
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)), specs,
        is_leaf=common.is_spec)
    M = cfg.n_clients
    table = jnp.zeros((M, n_rows, cfg.client_embed), jnp.float32)
    m_blk = jnp.arange(block, dtype=jnp.int32)
    idx = jnp.zeros((batch,), jnp.int32)
    key = jax.random.key(seed)
    x_parts = jnp.zeros((M, n_rows, cfg.features_per_client), jnp.float32)
    y = jnp.zeros((n_rows,), jnp.int32)
    return params, table, m_blk, idx, key, x_parts, y


# ======================================================== SwiGLU-MLP pair ==

@functools.lru_cache(maxsize=None)
def mlp_adapter(*, n_clients: int = 4, features: int = 32,
                client_embed: int = 32, d_ff: int = 64,
                server_embed: int = 64, n_classes: int = 4,
                act: str = "swiglu") -> ModelAdapter:
    """Non-tabular client/server pair built from ``repro.models.mlp``
    blocks: each client projects its feature slice and applies a residual
    SwiGLU MLP; the server does the same over the concatenated embeddings
    before a linear head. Exercises the engine with a model whose client
    partition is a multi-layer pytree (not one FC layer)."""
    acfg = ModelConfig(act=act, dtype="float32", param_dtype="float32")
    f_per = features // n_clients
    e, se = client_embed, server_embed

    def param_specs():
        client = {
            "w_in": ParamSpec((f_per, e), "float32", (None, None), "scaled"),
            "mlp": mlp.mlp_specs(acfg, e, d_ff),
        }
        return {
            "clients": common.stack_layer_specs(client, n_clients,
                                                axis_name="clients"),
            "server": {
                "w_in": ParamSpec((n_clients * e, se), "float32",
                                  (None, None), "scaled"),
                "mlp": mlp.mlp_specs(acfg, se, 2 * d_ff),
                "head": ParamSpec((se, n_classes), "float32", (None, None),
                                  "scaled"),
            },
        }

    def _rms(h):
        # parameter-free rms norm keeps the residual stack well-conditioned
        # regardless of feature scale (ZOO loses to exploding logits fast)
        return h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1,
                                          keepdims=True) + 1e-6)

    @tags.party("client")
    def client_forward(client_m, x_m):
        h = _rms(x_m @ client_m["w_in"])
        return _rms(h + mlp.mlp_apply(acfg, client_m["mlp"], h[:, None, :])[:, 0])

    @tags.party("server")
    def server_loss(server, c_all, y_batch):
        M, B, _ = c_all.shape
        h = _rms(c_all.transpose(1, 0, 2).reshape(B, M * e) @ server["w_in"])
        h = _rms(h + mlp.mlp_apply(acfg, server["mlp"], h[:, None, :])[:, 0])
        return tabular.xent(h @ server["head"], y_batch)

    return ModelAdapter(name=f"mlp-{act}", client_forward=client_forward,
                        server_loss=server_loss, param_specs=param_specs,
                        table_logical=("clients", None, None))


# ================================================= ModelConfig bridge =====

# top-level param keys forming the ZOO client partition of an LM config
# (matches model_api.Model.client_keys for the supported families)
LM_CLIENT_KEYS = ("embed",)


@functools.lru_cache(maxsize=None)
def from_model_config(cfg: ModelConfig, *, n_clients: int = 2,
                      seq_len: int = 32,
                      active_rows: bool = True) -> ModelAdapter:
    """Derive a :class:`ModelAdapter` for ANY decoder ``ModelConfig``.

    The vertical split follows the paper's LM experiments: each of the M
    client parties owns a disjoint span of ``seq_len / M`` token positions
    plus its own copy of the embedding table (the bottom layer), and the
    server owns the full transformer/MoE/SSM backbone, final norm and LM
    head. A client's uplink "embedding" is its span's token embeddings
    flattened to one ``(batch, span·d_model)`` vector, so the engine's
    (M, n, e) table, staleness bookkeeping and wire accounting all apply
    unchanged; the server loss folds the M spans back into a (batch, S,
    d_model) sequence and runs the exact post-embedding half of
    ``model_api.build_model(cfg).loss_fn``.

    ``active_rows=True`` (default) attaches a :attr:`ModelAdapter.row_mask`
    hook restricting each client's ZOO perturbation to the embedding rows
    its batch actually touches — the ``active_rows``-style dimension
    reduction of ``repro.core.zoo`` at engine scale.

    ``x_parts`` for the engine are int32 token spans,
    ``data.vertical_partition(tokens, M)``; ``y`` is the full (n, S) label
    array. Use :func:`lm_engine_params` to map a global ``build_model``
    parameter tree into the engine's {"clients", "server"} layout.

    Limitations: encoder-decoder and VLM configs need a modality frontend
    on the wire and are rejected; the DeepSeek MTP head consumes raw
    tokens (which never reach the server under this protocol) and is
    dropped from the server partition.
    """
    from repro.models import model_api, transformer
    from repro.models.layers import apply_norm, embed_lookup, unembed
    from repro.sharding.rules import shard_constraint

    if cfg.is_encoder_decoder or cfg.family == "vlm":
        raise ValueError(
            f"from_model_config supports decoder-only families; "
            f"{cfg.arch_id!r} (family={cfg.family!r}, "
            f"encoder_decoder={cfg.is_encoder_decoder}) needs a modality "
            "frontend that never crosses the VFL wire")
    if n_clients < 1 or seq_len % n_clients:
        raise ValueError(
            f"seq_len={seq_len} must split evenly over "
            f"n_clients={n_clients} token spans")

    model = model_api.build_model(cfg, max_seq=seq_len)
    client_spec, server_spec = split_params(model.param_specs,
                                            LM_CLIENT_KEYS)
    server_spec = {k: v for k, v in server_spec.items() if k != "mtp"}
    span = seq_len // n_clients
    d = cfg.d_model

    @tags.party("client")
    def client_forward(client_m, x_m):
        """x_m: (bs, span) int32 token slice -> (bs, span·d) embedding."""
        e = embed_lookup(client_m["embed"], x_m, iota=cfg.iota_embed)
        return e.reshape(x_m.shape[0], span * d)

    @tags.party("client")
    def client_lanes(client_m, u_stack, mu, x_m):
        """Fused clean + q perturbed fan-out. Embedding lookup is linear
        in the table, so the q perturbed forwards are one gather into the
        stacked direction tables instead of q re-embeddings of a perturbed
        copy — bitwise equal to perturb-then-lookup (gather commutes with
        the elementwise w + μu and the dtype round-trip)."""
        clean = client_forward(client_m, x_m)                   # (bs, e)
        u_rows = jax.vmap(
            lambda u: embed_lookup(u["embed"], x_m))(u_stack)   # (q,bs,span,d)
        pert = (clean[None].astype(jnp.float32)
                + mu * u_rows.reshape(u_rows.shape[0], x_m.shape[0],
                                      span * d)).astype(clean.dtype)
        return jnp.concatenate([clean[None], pert], axis=0)

    @tags.party("server")
    def server_loss(server, c_all, y_batch):
        """c_all: (M, bs, span·d) client spans -> scalar LM loss.

        Mirrors the post-embedding half of ``transformer.lm_loss`` (same
        ops, same order) so ``global_loss`` matches ``model.loss_fn``
        exactly when every client holds the same embedding table."""
        M, bs, _ = c_all.shape
        x = (c_all.reshape(M, bs, span, d)
             .transpose(1, 0, 2, 3).reshape(bs, seq_len, d))
        positions = jnp.arange(seq_len)
        if "pos_embed" in server:
            pos_table = server["pos_embed"]
            pe = jnp.take(pos_table,
                          jnp.clip(positions, 0, pos_table.shape[0] - 1),
                          axis=0)
            x = x + pe.astype(x.dtype)
        x = shard_constraint(x, ("batch", None, "embed_act"))
        h, _, aux, _ = transformer.backbone_apply(cfg, server, x,
                                               positions=positions)
        h = apply_norm(cfg, server["final_norm"], h)
        logits = unembed(server["lm_head"], h)
        logits = shard_constraint(logits, ("batch", None, "vocab_act"))
        ce = transformer.softmax_xent(logits[:, :-1], y_batch[:, 1:],
                                      cfg.padded_vocab)
        return jnp.mean(ce) + aux

    def param_specs():
        return {"clients": common.stack_layer_specs(client_spec, n_clients,
                                                    axis_name="clients"),
                "server": server_spec}

    def row_mask(client_m, x_m):
        return {"embed": {"table": zoo.embedding_row_mask(
            x_m, client_m["embed"]["table"].shape[0])}}

    # ---- serve plane: split inference with the training party split ----
    # The owning client embeds the current token (its span of positions);
    # the server runs pos-embed + backbone + head against its caches —
    # the exact post-embedding half of ``transformer.forward``'s decode
    # path, so split decode is bitwise-equal to global decode.

    @tags.party("client")
    def client_embed(client_m, tokens):
        """tokens (bs, S) int32 -> (bs, S, d) — the serve-time uplink.
        S=1 per decode step; S=chunk for a whole prompt span (chunked
        prefill uploads the span in one batched embed call)."""
        return embed_lookup(client_m["embed"], tokens, iota=cfg.iota_embed)

    def _decode_tail(server, x, caches, cur_pos, positions):
        if "pos_embed" in server:
            pos_table = server["pos_embed"]
            pe = jnp.take(pos_table,
                          jnp.clip(positions, 0, pos_table.shape[0] - 1),
                          axis=0)
            x = x + pe.astype(x.dtype)
        x = shard_constraint(x, ("batch", None, "embed_act"))
        h, new_caches, _, stats = transformer.backbone_apply(
            cfg, server, x, positions=positions, caches=caches,
            cur_pos=cur_pos)
        h = apply_norm(cfg, server["final_norm"], h)
        logits = unembed(server["lm_head"], h)
        logits = shard_constraint(logits, ("batch", None, "vocab_act"))
        return logits, new_caches, stats

    @tags.party("server")
    def server_decode(server, x, caches, cur_pos):
        logits, new_caches, _ = _decode_tail(server, x, caches, cur_pos,
                                             jnp.asarray(cur_pos)[None])
        return logits, new_caches

    @tags.party("server")
    def server_prefill(server, x, caches, t0):
        """x (bs, chunk, d): one party's whole span upload, consumed in a
        single compiled pass — same post-embedding ops as ``server_decode``
        per position, so chunked and per-token prefill agree token-for-
        token (float reassociation only on the recurrent-state families)."""
        positions = jnp.asarray(t0) + jnp.arange(x.shape[1])
        return _decode_tail(server, x, caches, t0, positions)

    @tags.party("server")
    def server_decode_paged(server, x, caches, tables, cur_pos, active,
                            page_size):
        """Batched paged decode: x (n_slots, 1, d) — every slot advances
        one token at its OWN position. Sequence cache leaves are shared
        page pools addressed through ``tables``; per-row positions drive
        RoPE/pos-embed and the attention mask, so each active row
        computes exactly what the B=1 ``server_decode`` would."""
        positions = cur_pos[:, None]                       # (n_slots, 1)
        paging_ctx = common.PageContext(tables=tables, active=active,
                                        page_size=page_size)
        if "pos_embed" in server:
            pos_table = server["pos_embed"]
            pe = jnp.take(pos_table,
                          jnp.clip(positions, 0, pos_table.shape[0] - 1),
                          axis=0)
            x = x + pe.astype(x.dtype)
        x = shard_constraint(x, ("batch", None, "embed_act"))
        h, new_caches, _, stats = transformer.backbone_apply(
            cfg, server, x, positions=positions, caches=caches,
            cur_pos=cur_pos, paging=paging_ctx)
        h = apply_norm(cfg, server["final_norm"], h)
        logits = unembed(server["lm_head"], h)
        logits = shard_constraint(logits, ("batch", None, "vocab_act"))
        return logits, new_caches, stats

    def cache_specs(batch, max_seq):
        return model_api.build_cache_specs(cfg, batch, max_seq)

    return ModelAdapter(
        name=f"lm-{cfg.arch_id}-m{n_clients}-s{seq_len}",
        client_forward=client_forward,
        server_loss=server_loss,
        param_specs=param_specs,
        client_lanes=client_lanes,
        table_logical=("clients", None, None),
        row_mask=row_mask if active_rows else None,
        client_embed=client_embed,
        server_decode=server_decode,
        server_prefill=server_prefill,
        cache_specs=cache_specs,
        server_decode_paged=server_decode_paged,
    )


def lm_engine_params(global_params, n_clients: int):
    """Map a global ``build_model`` parameter tree into the engine layout.

    Every client party receives the same copy of the embedding table (the
    replicated bottom layer), stacked along a leading (M,) clients axis;
    the server keeps everything else (minus the token-consuming MTP head).
    With this layout ``from_model_config(...).global_loss`` equals the
    global model's ``loss_fn`` — the bridge's equivalence anchor.
    """
    client, server = split_params(global_params, LM_CLIENT_KEYS)
    clients = jax.tree.map(
        lambda w: jnp.repeat(w[None], n_clients, axis=0), client)
    server = {k: v for k, v in server.items() if k != "mtp"}
    return {"clients": clients, "server": server}
