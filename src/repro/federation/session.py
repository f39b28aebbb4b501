"""The ``Federation`` session: one party-scoped lifecycle API.

``Federation.build(model_cfg, vfl_cfg, engine_cfg)`` resolves the three
orthogonal choices every entry point used to wire by hand —

* the MODEL plane: a :class:`repro.core.adapters.ModelAdapter` (given
  directly, derived from a ``PaperMLPConfig``, or derived from any
  registered LM-scale ``ModelConfig`` via ``adapters.from_model_config``),
* the WIRE: a :class:`repro.federation.Transport` (canonical method name,
  ledger ownership, optional DP noise channel on the loss downlink),
* the EXECUTION substrate: the device-sharded client mesh, picked from
  ``engine_cfg.mesh_shards`` instead of a loose ``mesh=`` kwarg —

and the whole lifecycle runs off the same session object:

* TRAIN — :meth:`run` (asynchronous engine: staleness semantics, one
  jitted ``lax.scan``) and :meth:`sync_step` (jitted cascade/baseline
  step factories the ``launch/train.py`` driver pumps batches through);
* CHECKPOINT/RESUME — :meth:`save` writes one directory per PARTY
  (``fed.parties``: the server's directory contains zero client leaves
  and vice versa) plus the session state (step, optimizer state, wire
  ledger totals, spent DP budget); :meth:`restore` rebuilds the session
  and state so a resumed run continues allclose to an uninterrupted one
  with ledger and (ε, δ) totals exactly continued;
* SERVE — :meth:`serve_step` / :meth:`decode` run split inference with
  the SAME party split as training (clients embed their token spans,
  the server owns backbone + head + caches), routed through the
  ``Transport`` so serve-time wire traffic lands in the ledger.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.checkpoint.io import atomic_write, load_tree, save_checkpoint
from repro.configs.base import ModelConfig, VFLConfig
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core import async_engine, cascade
from repro.core.adapters import (ModelAdapter, from_model_config,
                                 lm_engine_params, tabular_adapter)
from repro.core.methods import canonical_method
from repro.core.partition import merge_params, split_params
from repro.core.privacy import GaussianLossChannel, Ledger
from repro.federation import serving
from repro.federation.parties import (ClientParty, Parties, ServerParty,
                                      is_engine_layout)
from repro.federation.transport import Transport
from repro.launch.mesh import make_client_mesh
from repro.models import model_api
from repro.sharding.rules import PARAM_RULES, resolve_spec

ModelLike = Union[ModelAdapter, ModelConfig, PaperMLPConfig]

SESSION_MANIFEST = "session.json"
CHECKPOINT_VERSION = 1


@dataclasses.dataclass
class SessionState:
    """The non-parameter state a checkpoint carries: everything a resumed
    run needs to continue EXACTLY (not just approximately) — the step
    clock, the optimizer/schedule state, the Transport ledger totals, and
    the DP accountant's release count."""
    step: int = 0
    opt_state: Optional[Any] = None
    ledger: Ledger = dataclasses.field(default_factory=Ledger)
    dp_releases: int = 0
    # the population engine's full mutable state (embedding table, delay
    # counters, activity clock, fault counters) — set when the checkpoint
    # was taken mid-``run_population``, so the resumed wire run replays
    # the remaining rounds bitwise (see async_engine.AsyncPlaneState)
    async_state: Optional[async_engine.AsyncPlaneState] = None
    # the serve plane's full mutable state (admission queue, slot/block
    # tables, page-pool free list, gen buffers, per-request ledgers, RNG
    # streams) — set when the checkpoint was taken mid-drain, so
    # ``fed.serve(params, state=...)`` resumes the drain bitwise (a
    # ``scheduler.SchedulerState``; typed Any to keep the scheduler
    # import lazy)
    serve_state: Optional[Any] = None
    # the free-form metadata the saver passed to ``fed.save`` (driver
    # knobs like batch/seed/schedule live here, not in the session)
    metadata: dict = dataclasses.field(default_factory=dict)

    def dp_spent(self, transport: Transport) -> Tuple[float, float]:
        return transport.privacy_spent(self.dp_releases)


@dataclasses.dataclass
class Federation:
    """A built training session; construct via :meth:`build`."""
    vfl: VFLConfig
    engine: async_engine.EngineConfig
    transport: Transport
    mesh: Optional[Mesh] = None
    # set for ModelConfig-built sessions (the sync-driver plane)
    model_cfg: Optional[ModelConfig] = None
    n_clients: int = 2
    seq_len: int = 32
    _adapter: Optional[ModelAdapter] = None
    _model: Optional[model_api.Model] = None

    # ----------------------------------------------------------- build ----
    @classmethod
    def build(cls, model_cfg: ModelLike,
              vfl_cfg: Optional[VFLConfig] = None,
              engine_cfg: Optional[async_engine.EngineConfig] = None, *,
              noise: Optional[GaussianLossChannel] = None,
              transport: Optional[Transport] = None,
              mesh: Optional[Mesh] = None,
              n_clients: int = 2, seq_len: int = 32,
              model: Optional[model_api.Model] = None) -> "Federation":
        """One constructor for every entry point.

        ``model_cfg`` may be a ready :class:`ModelAdapter`, the paper's
        ``PaperMLPConfig`` (tabular protocol), or any ``ModelConfig`` from
        the arch registry (clients own the embedding, server owns the
        backbone; ``n_clients``/``seq_len`` size the vertical token
        split). ``noise`` plugs a DP channel into the transport's loss
        downlink. ``mesh`` is normally derived from
        ``engine_cfg.mesh_shards``; passing an explicit ``Mesh`` is the
        back-compat escape hatch ``async_engine.run`` uses. ``model``
        injects a pre-built :class:`model_api.Model` for a ModelConfig
        session (the dry-run's hook for window/remat/decode variants the
        default ``build_model`` call would not select).
        """
        vfl = vfl_cfg if vfl_cfg is not None else VFLConfig()
        engine = (engine_cfg if engine_cfg is not None
                  else async_engine.EngineConfig())
        if transport is None:
            transport = Transport(engine.method, noise=noise)
        elif noise is not None:
            raise ValueError("pass noise= or a full transport=, not both")
        if canonical_method(engine.method) != transport.method:
            raise ValueError(
                f"engine_cfg.method {engine.method!r} and transport method "
                f"{transport.method!r} disagree")
        if mesh is not None and engine.mesh_shards:
            raise ValueError(
                f"both an explicit mesh= and engine_cfg.mesh_shards="
                f"{engine.mesh_shards} were given; set one (mesh_shards is "
                "the session-native spelling)")
        if mesh is None and engine.mesh_shards:
            mesh = make_client_mesh(engine.mesh_shards)

        adapter = cfg = None
        if isinstance(model_cfg, ModelAdapter):
            adapter = model_cfg
        elif isinstance(model_cfg, PaperMLPConfig):
            adapter = tabular_adapter(model_cfg)
            n_clients = model_cfg.n_clients
        elif isinstance(model_cfg, ModelConfig):
            cfg = model_cfg
        else:
            raise TypeError(
                f"model_cfg must be a ModelAdapter, PaperMLPConfig or "
                f"ModelConfig, got {type(model_cfg).__name__}")
        if model is not None and cfg is None:
            raise ValueError("model= injection needs a ModelConfig session")
        return cls(vfl=vfl, engine=engine, transport=transport, mesh=mesh,
                   model_cfg=cfg, n_clients=n_clients,
                   seq_len=seq_len, _adapter=adapter, _model=model)

    # ------------------------------------------------------- model plane --
    @property
    def adapter(self) -> ModelAdapter:
        """The session's ModelAdapter (derived lazily for ModelConfig
        sessions — families without an async bridge, e.g. encoder-decoder,
        can still drive the sync path). ``vfl.active_rows_only`` gates the
        active-row ZOO mask, matching the sync plane's semantics; the
        derivation is re-resolved per access (``from_model_config`` is
        lru-cached) so a ``fed.vfl`` update never serves a stale mask."""
        if self._adapter is not None:
            return self._adapter
        return from_model_config(
            self.model_cfg, n_clients=self.n_clients, seq_len=self.seq_len,
            active_rows=self.vfl.active_rows_only)

    @property
    def model(self) -> Optional[model_api.Model]:
        """The global model (sync-driver plane); built lazily so
        async-only sessions never construct it."""
        if self._model is None and self.model_cfg is not None:
            self._model = model_api.build_model(self.model_cfg,
                                                max_seq=self.seq_len)
        return self._model

    def init_params(self, key):
        """Engine-layout params ({"clients": (M, ...), "server": ...})."""
        return self.adapter.init_params(key)

    def params_from_global(self, global_params):
        """Replicate a global ``build_model`` param tree into the engine
        layout (each client party gets the same embedding table)."""
        if self.model_cfg is None:
            raise ValueError("params_from_global needs a ModelConfig-built "
                             "session (tabular/adapter sessions already use "
                             "the engine layout)")
        return lm_engine_params(global_params, self.n_clients)

    # ------------------------------------------------------ async driver --
    def run(self, params, x_parts, y, *, probs=None
            ) -> async_engine.EngineResult:
        """Asynchronous protocol simulation (staleness, blocks, sharding).

        ``x_parts``: (M, n, f) vertically partitioned features — token
        spans (int32) for LM sessions; ``y``: (n,) labels, or (n, S)
        next-token labels for LM sessions."""
        return async_engine._session_run(
            self.adapter, self.transport, self.vfl, self.engine,
            params, x_parts, y, probs=probs, mesh=self.mesh)

    def run_population(self, params, x_parts, y, *, probs=None,
                       fault_plan=None, population=None, channels=None,
                       state=None, ledger: Optional[Ledger] = None,
                       dp_releases: int = 0, until: Optional[int] = None,
                       stop_workers: bool = True
                       ) -> "async_engine.PopulationResult":
        """The asynchronous protocol over the REAL wire (``repro.wire``).

        Same schedule/RNG/staleness semantics as :meth:`run` — with
        ``FaultPlan.none()`` the two are bitwise-identical — but every
        client sits behind a wire backend (in-proc loopback by default;
        ``channels={m: backend}`` places party m behind e.g. a connected
        socket whose worker process runs ``ClientWorker.serve``), frames
        are genuinely serialized and metered at their actual byte size,
        and ``fault_plan`` injects deterministic drops/latency.
        ``state``/``until``/``ledger``/``dp_releases`` continue a
        checkpointed run exactly (see :meth:`save`'s ``async_state``)."""
        return async_engine.run_population(
            self.adapter, self.transport, self.vfl, self.engine,
            params, x_parts, y, probs=probs, fault_plan=fault_plan,
            population=population, channels=channels, state=state,
            ledger=ledger, dp_releases=dp_releases, until=until,
            stop_workers=stop_workers)

    # ------------------------------------------------------- sync driver --
    def sync_step(self, optimizer, *, vocab: Optional[int] = None):
        """Jitted cascade/baseline step over the GLOBAL model's loss —
        the ``launch/train.py`` plane. Requires a ModelConfig session."""
        if self.model_cfg is None:
            raise ValueError(
                "sync_step drives a global-model loss; build the session "
                "from a ModelConfig (tabular/adapter sessions train through "
                "Federation.run)")
        vocab = self.model_cfg.padded_vocab if vocab is None else vocab
        return cascade.make_step_for_method(
            self.transport.method, self.model.loss_fn,
            self.model.client_keys, self.vfl, optimizer, vocab=vocab,
            transport=self.transport)

    # -------------------------------------------------- certifier plane ---
    def boundary_meta(self) -> dict:
        """Boundary metadata for the jaxpr certifier
        (``repro.analysis.certify``): everything the information-flow
        rules need to size the legal bottleneck — method, q, block,
        whether a DP channel is configured — read off the session instead
        of asserted by the caller."""
        return {
            "method": self.transport.method,
            "sync": self.transport.sync,
            "zoo_wire": self.transport.zoo_wire,
            "dp": self.transport.noise is not None,
            "zoo_queries": self.vfl.zoo_queries,
            "block": 1 if self.transport.sync else self.engine.block_size,
            "batch": self.engine.batch_size,
            "n_clients": self.n_clients,
            "use_lanes": self.engine.use_lanes,
            "mesh_shards": self.engine.mesh_shards,
        }

    def traceable_train_step(self, *, table_shape=None):
        """The EXACT step closure the jitted scan body runs — sync,
        async, or device-sharded per the engine config — returned
        untraced so ``jax.make_jaxpr`` can walk it (the async round
        with its table transposed from the scan's sample-major carry
        to ``(M, n, e)`` and back, nothing else added). Signature:
        ``step(params, table, m_blk, idx, key, x_parts, y) ->
        (params, table, h)``. The sharded variant needs ``table_shape``
        (the (M, n, e) embedding-table shape) to resolve the table's
        partition spec the same way ``run`` does."""
        if self.transport.sync:
            return async_engine._make_sync_step(
                self.adapter, self.transport, self.vfl)
        if self.mesh is not None:
            if table_shape is None:
                raise ValueError("the sharded step needs table_shape= to "
                                 "resolve the table partition spec")
            table_spec = resolve_spec(self.mesh, tuple(table_shape),
                                      self.adapter.table_logical,
                                      PARAM_RULES)
            return async_engine._make_sharded_step(
                self.adapter, self.transport, self.vfl,
                self.engine.use_lanes, self.mesh, self.engine.block_size,
                table_spec)
        return async_engine._make_async_step(
            self.adapter, self.transport, self.vfl, self.engine.use_lanes)

    def traceable_population_fns(self):
        """The population engine's jitted server-side pair
        ``(server_update, losses_fn)`` (see
        ``async_engine._population_fns``) — ``losses_fn`` is the
        server→client downlink closure the certifier traces: its whole
        output is client-bound."""
        return async_engine._population_fns(self.adapter, self.transport,
                                            self.vfl)

    # ------------------------------------------------------ party plane ---
    @property
    def client_keys(self) -> Tuple[str, ...]:
        """Top-level GLOBAL-layout keys forming the client partition."""
        if self.model_cfg is not None:
            return self.model.client_keys
        return ("clients",)

    @property
    def parties(self) -> Parties:
        """Typed party handles — the one way any plane addresses state.

        ``parties.server`` owns the backbone/head partition,
        ``parties.clients[m]`` owns client m's slice; both resolve against
        either param layout (engine ``{"clients", "server"}`` or the
        global ``build_model`` tree)."""
        keys = self.client_keys
        return Parties(
            server=ServerParty(client_keys=keys),
            clients=tuple(ClientParty(index=m, client_keys=keys)
                          for m in range(self.n_clients)))

    # ------------------------------------------------------ serve plane ---
    def serve_step(self):
        """Jitted one-token split-inference step (see
        :func:`repro.federation.serving.make_serve_step`): the client
        owning the current position embeds the token, the server decodes
        against its caches. Requires a ModelConfig-built session."""
        return serving.make_serve_step(self.adapter, self.n_clients,
                                       self.seq_len)

    def decode(self, params, prompts, *, gen_len: int,
               temperature: float = 0.0, seed: int = 0, key=None,
               ledger: Optional[Ledger] = None, use_scan: bool = True,
               chunked_prefill: bool = True) -> serving.ServeResult:
        """Split inference with the training party split.

        ``params`` may be the engine layout or a global ``build_model``
        tree (replicated into the engine layout via
        :meth:`params_from_global`). ``prompts``: (B, prompt_len) int32;
        ``prompt_len + gen_len`` must fit the session ``seq_len`` (the
        span split is sized to it). Serve-time wire traffic is logged
        through the Transport — pass ``ledger`` to extend a training
        run's totals instead of starting a fresh one.

        Decode runs as one compiled ``lax.scan`` (on-device sampling, one
        host transfer) over a chunk-prefilled cache by default;
        ``use_scan=False`` / ``chunked_prefill=False`` select the
        per-token oracle loops."""
        if self.model_cfg is None:
            raise ValueError(
                "decode needs a ModelConfig-built session (tabular/adapter "
                "sessions have no serve plane)")
        if not is_engine_layout(params):
            params = self.params_from_global(params)
        if key is None:
            key = jax.random.key(seed)
        return serving.run_decode(
            self.adapter, self.transport, n_clients=self.n_clients,
            seq_len=self.seq_len, embed_dim=self.model_cfg.d_model,
            vocab_size=self.model_cfg.vocab_size, params=params,
            prompts=prompts, gen_len=gen_len, temperature=temperature,
            key=key, ledger=ledger, use_scan=use_scan,
            chunked_prefill=chunked_prefill)

    def serve(self, params, *, max_batch: int = 4,
              temperature: float = 0.0, page_size: Optional[int] = None,
              n_pages: Optional[int] = None,
              max_queue: Optional[int] = None, preempt: bool = False,
              state: Optional[Any] = None):
        """A continuous-batching serve session over the split plane.

        Returns a :class:`repro.federation.scheduler.ServeScheduler`:
        ``submit(prompt, gen_len=...)`` queues requests, ``run()`` drains
        them through ``max_batch`` fixed slots — new requests are admitted
        as slots free up mid-flight, compiled multi-step decode blocks
        serve the churning mix, and each request gets its own exact wire
        ledger. Slot caches live in a shared page pool (``page_size``
        must divide ``seq_len``; ``n_pages`` caps pool memory and
        admission-gates requests on free pages when set below the
        ``max_batch`` worst case).

        Failure policy: ``max_queue`` bounds admission (``submit`` raises
        ``QueueFull`` past it) and ``preempt=True`` lets a page-starved
        queue head evict the in-flight request with the fewest tokens
        remaining (bitwise-exact resume). Pass a restored
        ``SessionState.serve_state`` as ``state`` to resume a mid-drain
        snapshot exactly — the scheduler's shape/pool config then comes
        from the snapshot, not from the keyword defaults."""
        from repro.federation.scheduler import ServeScheduler
        if self.model_cfg is None:
            raise ValueError(
                "serve needs a ModelConfig-built session (tabular/adapter "
                "sessions have no serve plane)")
        if not is_engine_layout(params):
            params = self.params_from_global(params)
        if state is not None:
            cfg = state.meta["config"]
            max_batch = int(cfg["max_batch"])
            temperature = float(cfg["temperature"])
            page_size = int(cfg["page_size"])
            n_pages = int(cfg["n_pages"])
            max_queue = cfg["max_queue"]
            preempt = bool(cfg["preempt"])
        srv = ServeScheduler(
            self.adapter, self.transport, params=params,
            n_clients=self.n_clients, seq_len=self.seq_len,
            embed_dim=self.model_cfg.d_model,
            vocab_size=self.model_cfg.vocab_size, max_batch=max_batch,
            temperature=temperature, page_size=page_size, n_pages=n_pages,
            max_queue=max_queue, preempt=preempt)
        if state is not None:
            srv._load_state(state)
        return srv

    # ------------------------------------------------- checkpoint plane ---
    def save(self, path: str, params, *, step: int = 0,
             opt_state: Optional[Any] = None,
             ledger: Optional[Ledger] = None, dp_releases: int = 0,
             async_state: Optional[async_engine.AsyncPlaneState] = None,
             serve_state: Optional[Any] = None,
             metadata: Optional[dict] = None) -> str:
        """Party-scoped checkpoint: one directory per party + session state.

        Layout::

            path/
              session.json     step, configs, ledger totals, DP releases
              server/          server party's leaves ONLY
              client_00/ ...   per-client slices   (engine layout), or
              clients/         the client partition (global layout)
              opt_server/, opt_clients/   optimizer state, split on the
                                          same party boundary (optional)
              async_plane/     the population engine's table/delay/clock
                               state (optional — mid-``run_population``
                               checkpoints; makes the resume bitwise)
              serve_plane/     the serve scheduler's full state (optional
                               — mid-drain checkpoints via
                               ``srv.snapshot()``; makes the resumed
                               drain's tokens and ledgers bitwise)

        The isolation is structural (:mod:`repro.federation.parties`):
        the server handle cannot address a client leaf, so its directory
        provably contains none — and vice versa. Returns ``path`` (the
        token ``Federation.restore`` consumes)."""
        os.makedirs(path, exist_ok=True)
        parties = self.parties
        engine_layout = is_engine_layout(params)
        if engine_layout:
            rows = jax.tree.leaves(params["clients"])[0].shape[0]
            if rows != len(parties.clients):
                raise ValueError(
                    f"params stack {rows} client parties but the session "
                    f"was built with n_clients={len(parties.clients)} — a "
                    "per-party save would silently drop rows; pass "
                    f"n_clients={rows} to Federation.build")
            save_checkpoint(os.path.join(path, parties.server.name),
                            parties.server.owned(params), step=step)
            for party in parties.clients:
                save_checkpoint(os.path.join(path, party.name),
                                party.owned(params), step=step)
        else:
            save_checkpoint(os.path.join(path, "server"),
                            parties.server.owned(params), step=step)
            save_checkpoint(os.path.join(path, "clients"),
                            parties.clients[0].owned(params), step=step)
        if opt_state is not None:
            opt_c, opt_s = self._split_opt_state(opt_state, engine_layout)
            save_checkpoint(os.path.join(path, "opt_server"), opt_s,
                            step=step)
            save_checkpoint(os.path.join(path, "opt_clients"), opt_c,
                            step=step)
        if async_state is not None:
            async_state.save(os.path.join(path, "async_plane"))
        if serve_state is not None:
            serve_state.save(os.path.join(path, "serve_plane"))

        ledger = ledger if ledger is not None else Ledger()
        eps, delta = self.transport.privacy_spent(dp_releases)
        manifest = {
            "version": CHECKPOINT_VERSION,
            "step": int(step),
            "layout": "engine" if engine_layout else "global",
            "has_opt_state": opt_state is not None,
            "model": self._model_manifest(),
            "vfl": dataclasses.asdict(self.vfl),
            "engine": dataclasses.asdict(self.engine),
            "noise": (None if self.transport.noise is None
                      else dataclasses.asdict(self.transport.noise)),
            "n_clients": self.n_clients,
            "seq_len": self.seq_len,
            "ledger_counts": ledger.to_counts(),
            "dp_releases": int(dp_releases),
            "dp_spent": [eps if math.isfinite(eps) else None, delta],
            "async_plane": async_state is not None,
            "serve_plane": serve_state is not None,
            "metadata": metadata or {},
        }
        # atomic + last: a session.json on disk always certifies complete
        # party/plane directories next to it
        atomic_write(os.path.join(path, SESSION_MANIFEST),
                     lambda f: json.dump(manifest, f, indent=2), mode="w")
        return path

    @classmethod
    def restore(cls, path: str, model_cfg: Optional[ModelLike] = None,
                ) -> Tuple["Federation", Any, SessionState]:
        """Rebuild (session, params, state) from a :meth:`save` directory.

        The session's configs (model, vfl, engine, DP channel) come from
        ``session.json``; only adapter-built sessions — whose model plane
        is an arbitrary callable bundle — need the caller to pass the
        ``model_cfg`` (the adapter) back in. ``state.step``/``opt_state``/
        ``ledger``/``dp_releases`` continue a training run exactly:
        re-drive the same batches from ``state.step`` and the trajectory
        is allclose to one that never stopped."""
        with open(os.path.join(path, SESSION_MANIFEST)) as f:
            manifest = json.load(f)
        if manifest["version"] != CHECKPOINT_VERSION:
            raise ValueError(
                f"checkpoint version {manifest['version']} != "
                f"{CHECKPOINT_VERSION}")

        model = cls._model_from_manifest(manifest["model"], model_cfg)
        vfl_d = dict(manifest["vfl"])
        if vfl_d.get("activation_probs") is not None:
            vfl_d["activation_probs"] = tuple(vfl_d["activation_probs"])
        noise_d = manifest["noise"]
        fed = cls.build(
            model, VFLConfig(**vfl_d),
            async_engine.EngineConfig(**manifest["engine"]),
            noise=None if noise_d is None else GaussianLossChannel(**noise_d),
            n_clients=manifest["n_clients"], seq_len=manifest["seq_len"])

        server_tree, _, _ = load_tree(os.path.join(path, "server"))
        if manifest["layout"] == "engine":
            client_trees = [
                load_tree(os.path.join(path, party.name))[0]
                for party in fed.parties.clients]
            params = fed.parties.assemble(server_tree, client_trees)
        else:
            client_tree, _, _ = load_tree(os.path.join(path, "clients"))
            params = fed.parties.merge_global(server_tree, client_tree)

        opt_state = None
        if manifest["has_opt_state"]:
            opt_s, _, _ = load_tree(os.path.join(path, "opt_server"))
            opt_c, _, _ = load_tree(os.path.join(path, "opt_clients"))
            opt_state = fed._merge_opt_state(
                opt_c, opt_s, manifest["layout"] == "engine")

        async_state = None
        if manifest.get("async_plane"):
            async_state = async_engine.AsyncPlaneState.load(
                os.path.join(path, "async_plane"))
        serve_state = None
        if manifest.get("serve_plane"):
            from repro.federation.scheduler import SchedulerState
            serve_state = SchedulerState.load(
                os.path.join(path, "serve_plane"))

        state = SessionState(
            step=manifest["step"], opt_state=opt_state,
            ledger=Ledger.from_counts(manifest["ledger_counts"]),
            dp_releases=manifest["dp_releases"],
            async_state=async_state, serve_state=serve_state,
            metadata=manifest.get("metadata", {}))
        return fed, params, state

    # ----------------------------------------------- checkpoint helpers ---
    def _model_manifest(self) -> dict:
        if self.model_cfg is not None:
            return {"kind": "model_config",
                    "data": dataclasses.asdict(self.model_cfg)}
        if (self._adapter is not None
                and self._adapter.name.startswith("tabular")):
            # a tabular adapter is fully determined by its PaperMLPConfig;
            # reconstruct it from the stacked client/server spec shapes
            spec = self._adapter.param_specs()
            M, f, e = spec["clients"]["w"].shape
            se, C = spec["server"]["w2"].shape
            return {"kind": "paper_mlp",
                    "data": dataclasses.asdict(PaperMLPConfig(
                        n_features=M * f, n_classes=C, n_clients=M,
                        client_embed=e, server_embed=se))}
        return {"kind": "adapter", "data": self.adapter.name}

    @staticmethod
    def _model_from_manifest(m: dict, model_cfg: Optional[ModelLike]):
        if model_cfg is not None:
            return model_cfg
        if m["kind"] == "model_config":
            return ModelConfig(**m["data"])
        if m["kind"] == "paper_mlp":
            return PaperMLPConfig(**m["data"])
        raise ValueError(
            f"checkpoint was saved from an adapter-built session "
            f"({m['data']!r}); pass the adapter back via "
            "Federation.restore(path, model_cfg=adapter)")

    def _split_opt_state(self, opt_state, engine_layout: bool):
        """Split optimizer state on the party boundary: per-parameter
        trees (momentum, adam moments) mirror the param layout and split
        like params; the step clock lives with the server (the session's
        round counter is server-side in the protocol)."""
        opt_c, opt_s = {}, {}
        for k, v in opt_state.items():
            if k == "step":
                opt_s[k] = v
            elif engine_layout:
                opt_c[k] = v["clients"]
                opt_s[k] = v["server"]
            else:
                opt_c[k], opt_s[k] = split_params(v, self.client_keys)
        return opt_c, opt_s

    def _merge_opt_state(self, opt_c, opt_s, engine_layout: bool):
        out = {}
        for k, v in opt_s.items():
            if k == "step":
                out[k] = jnp.asarray(v)
            elif engine_layout:
                out[k] = {"clients": opt_c[k], "server": v}
            else:
                out[k] = merge_params(opt_c.get(k, {}), v)
        return out
