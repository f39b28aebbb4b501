"""Asynchronous VFL engine (paper §III-C / Alg. 1) — host-level protocol
simulation with exact staleness semantics, compiled as one jitted
``lax.scan``.

Per global round t (matching Fig. 2):
  * a block of clients {m_t} is activated (schedule drawn from p_m,
    assumption IV.6; ``block_size=1`` recovers the paper's one-client
    rounds, larger blocks vmap several concurrent activations per round
    for many-client scaling studies)
  * each picks a sample batch i_t, computes c/ĉ and "uploads" them
  * the server evaluates h/ĥ against its *embedding table* — the latest
    (stale, delay τ_{i,m}) embeddings of all other clients (assumption IV.7)
  * the server does one local FOO step (ours/VAFL) or ZOO step (ZOO-VFL)
  * each activated client does one ZOO step (ours/ZOO-VFL) or FOO step
    (VAFL); concurrent clients see each other's STALE embeddings only
  * table rows (m, i_t) refresh; delay counters update per §III-C

In the compiled round the table write comes before the server's stale
read: the write touches only the activated clients' rows, and every
consumer replaces those rows with the fresh embeddings anyway (a block
of R > 1 reads its own rows first and puts them back, so concurrent
clients still see each other stale). So the read is exactly the stale
one, and XLA updates the carried table in place; the scan carries it
sample-major, ``(n, M, e)``, the layout the round's row gather wants.

The model plane is abstracted behind :class:`repro.core.adapters.ModelAdapter`,
so the same scan body drives arbitrary ``repro.models`` client/server
pairs — the paper's tabular MLP, or any LM-scale ``ModelConfig`` via
``adapters.from_model_config``. The wire plane is abstracted behind
:class:`repro.federation.Transport`, which owns the ledger, canonical
method names, and the optional DP noise hook applied to every scalar loss
crossing the downlink (``EngineResult`` then reports the spent (ε, δ)).
The scan body is jitted once per (adapter, transport, vfl, block, mesh)
and cached, so repeated runs (benchmark sweeps) skip retracing.

:func:`run` is the back-compat entry: it wraps a
``repro.federation.Federation`` session (the canonical constructor) and
is bitwise-identical to the pre-session engine at noise=0.

Device-sharded client block (``mesh=`` path)
--------------------------------------------
Passing a ``("data",)`` mesh (see :func:`repro.launch.mesh.make_client_mesh`)
shard_maps the round's client block across devices: each device hosts
``block_size / D`` of the activated clients plus ``M / D`` rows of the
embedding table (partitioned via the "clients" logical axis of
``repro.sharding.rules``). Per round, the only cross-device traffic is

  * an ``all_gather`` of the per-shard stale table slices and fresh block
    embeddings at the server-loss boundary (the wire of Fig. 2), and
  * a ``psum`` replicating the block's sparse client-parameter updates
    (activated clients are distinct, so shard contributions are disjoint
    and the sum is float-exact).

Every client's ZOO fan-out — the q× forward passes that dominate a round —
runs on its own shard with per-row RNG derived by ``fold_in`` on the
GLOBAL row index, so the sharded engine draws the exact perturbation
directions of the single-device engine: block_size=1 on a 1-shard mesh is
bitwise identical, larger blocks agree to float-reassociation.

Synchronous baselines (Split-Learning, Syn-ZOO-VFL) activate *all* clients
every round with fresh embeddings (no table staleness).

Population plane (``run_population``)
-------------------------------------
:func:`run_population` runs the SAME protocol over a real wire
(``repro.wire``): every client party lives behind a
:class:`~repro.wire.backend.WireBackend` endpoint (in-proc loopback by
default; a TCP socket puts it in another process), messages are genuinely
serialized, and the ledger meters actual frame bytes. A
:class:`~repro.wire.faults.FaultPlan` injects per-party drops/latency in
deterministic virtual time, and :class:`PopulationConfig` adds
straggler admission and bounded-staleness forcing on top of the sampled
activation schedule. With ``FaultPlan.none()`` the run is
bitwise-identical to the in-process engine; the engine's full mutable
state is an :class:`AsyncPlaneState` that checkpoints and resumes
exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import marks, tags
from repro.configs.base import VFLConfig
from repro.core import zoo
from repro.core.adapters import ModelAdapter, tabular_adapter
from repro.core.methods import SYNC_METHODS
from repro.core.privacy import Ledger, Message
from repro.sharding.rules import PARAM_RULES, resolve_spec
from repro.utils import spans

CLIENT_AXIS = "data"        # mesh axis the client block shards over


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    method: str = "cascaded"   # any spelling in repro.core.methods
    steps: int = 1000
    batch_size: int = 64
    seed: int = 0
    # >1 activates several clients per round (drawn without replacement)
    # and runs their updates as one vmapped block
    block_size: int = 1
    # route the client's clean+perturbed fan-out through the adapter's
    # fused lanes hook (e.g. the zoo_dual_matmul Pallas kernel)
    use_lanes: bool = False
    # >0 shards the client block + table rows over that many devices
    # (Federation builds the ("data",) mesh via launch.mesh.make_client_mesh;
    # must divide both block_size and the client count)
    mesh_shards: int = 0


@dataclasses.dataclass
class EngineResult:
    params: dict
    losses: np.ndarray          # (T,)
    max_delay_seen: int
    mean_delay: float
    # wire accounting (q-aware privacy ledger owned by the Transport)
    wire_bytes: int = 0
    transmits_gradients: bool = False
    ledger: Optional[Ledger] = None
    # DP budget spent on the loss downlink ((inf, 0) without a noise
    # channel: structurally safe wire, no formal guarantee)
    epsilon: float = math.inf
    delta: float = 0.0
    # the server's (M, n, e) embedding table after the last round; on a
    # mesh run its rows stay sharded over the mesh's devices
    table: Optional[jax.Array] = None


def make_schedule(key, steps: int, n_clients: int,
                  probs: Optional[Tuple[float, ...]] = None,
                  block_size: int = 1):
    """Activation sequence m_t — independent draws (assumption IV.6).

    block_size > 1 draws that many DISTINCT clients per round; returns
    (steps,) for block_size == 1, else (steps, block_size)."""
    p = (jnp.ones(n_clients) / n_clients if probs is None
         else jnp.asarray(probs))
    if block_size == 1:
        return jax.random.choice(key, n_clients, (steps,), p=p)
    keys = jax.random.split(key, steps)
    return jax.vmap(
        lambda k: jax.random.choice(k, n_clients, (block_size,),
                                    replace=False, p=p))(keys)


def _validate_mesh(mesh: Mesh, sync: bool, method: str, block: int, M: int):
    if sync:
        raise ValueError(
            f"mesh sharding only applies to asynchronous methods, not "
            f"{method!r} (sync rounds have no client block to shard)")
    if CLIENT_AXIS not in mesh.shape:
        raise ValueError(
            f"engine mesh needs a {CLIENT_AXIS!r} axis, got "
            f"{dict(mesh.shape)} (use repro.launch.mesh.make_client_mesh)")
    D = mesh.shape[CLIENT_AXIS]
    if block % D:
        raise ValueError(
            f"block_size={block} not divisible by the mesh "
            f"{CLIENT_AXIS!r} axis ({D} shards)")
    if M % D:
        raise ValueError(
            f"n_clients={M} not divisible by the mesh {CLIENT_AXIS!r} "
            f"axis ({D} shards): the embedding table rows cannot split")


def run(cfg_engine: EngineConfig, vfl: VFLConfig, params, x_parts, y,
        *, probs=None, adapter: Optional[ModelAdapter] = None,
        mesh: Optional[Mesh] = None) -> EngineResult:
    """Back-compat wrapper over the ``repro.federation`` session API.

    x_parts: (M, n, f) vertically partitioned features; y: (n,) labels.
    ``mesh``: optional ``("data",)`` mesh — new callers set
    ``EngineConfig.mesh_shards`` instead and let the session build it.
    Bitwise-identical to ``Federation.build(...).run(...)`` at noise=0
    (there is no noise knob here; DP runs go through the session)."""
    from repro.federation import Federation
    fed = Federation.build(
        adapter if adapter is not None else tabular_adapter(),
        vfl, cfg_engine, mesh=mesh)
    return fed.run(params, x_parts, y, probs=probs)


def _session_run(adapter: ModelAdapter, transport, vfl: VFLConfig,
                 cfg_engine: EngineConfig, params, x_parts, y,
                 *, probs=None, mesh: Optional[Mesh] = None) -> EngineResult:
    """The engine proper, driven by a ``Federation`` session.

    ``transport`` (a ``repro.federation.Transport``) supplies the
    canonical method, the wire ledger, and the downlink noise hook; the
    session supplies the adapter and the (already-built) mesh.

    Spans (:mod:`repro.utils.spans`): ``vfl.engine.prepare`` from entry
    to the scan's dispatch (schedule, sample indices, keys, the eager
    ``table0``); ``vfl.engine.scan``, the runner call; and
    ``vfl.engine.collect``, the reads of the losses and delays and the
    transport's accounting. The device has nothing of the engine queued
    in ``prepare`` and after ``collect``'s last blocking read: both
    stretches are recorded as ``vfl.engine.drained``
    (``after=entry|collect``)."""
    t_entry = time.time_ns()
    with spans.span("vfl.engine.prepare"):
        runner, args, embed, n_active = _prepare(
            adapter, transport, vfl, cfg_engine, params, x_parts, y,
            probs, mesh)
    spans.record("vfl.engine.drained", t_entry, time.time_ns(),
                 after="entry")
    with spans.span("vfl.engine.scan"):
        (params, table, delays), (losses, maxd) = runner(*args)
    T, bs = cfg_engine.steps, cfg_engine.batch_size
    with spans.span("vfl.engine.collect"):
        losses = np.asarray(losses)
        max_delay_seen = int(jnp.max(maxd))
        mean_delay = float(jnp.mean(delays))
        t_read = time.time_ns()
        # the Transport owns the q-gating (queries only fan out on ZOO
        # wires)
        ledger = transport.account(batch=bs, embed=embed,
                                   zoo_queries=vfl.zoo_queries,
                                   n_clients=n_active, n_rounds=T)
        eps, delta = transport.privacy_spent(transport.releases(
            n_rounds=T, n_clients=n_active, zoo_queries=vfl.zoo_queries))
        res = EngineResult(params=params, losses=losses,
                           max_delay_seen=max_delay_seen,
                           mean_delay=mean_delay,
                           wire_bytes=ledger.total_bytes,
                           transmits_gradients=ledger.transmits_gradients,
                           ledger=ledger, epsilon=eps, delta=delta,
                           table=table)
    spans.record("vfl.engine.drained", t_read, time.time_ns(),
                 after="collect")
    return res


def _prepare(adapter: ModelAdapter, transport, vfl: VFLConfig,
             cfg_engine: EngineConfig, params, x_parts, y, probs,
             mesh: Optional[Mesh]):
    """Everything before the scan: checks, the round schedule, sample
    indices and keys, the initial table and the compiled runner. Returns
    ``(runner, its arguments, embedding width, clients a round)``."""
    method = transport.method
    M, n, f = x_parts.shape
    T, bs = cfg_engine.steps, cfg_engine.batch_size
    sync = method in SYNC_METHODS
    if sync and cfg_engine.use_lanes:
        raise ValueError(
            f"use_lanes only applies to asynchronous ZOO-client methods, "
            f"not {method!r} (the sync step has no per-client "
            "fan-out to route through the fused kernel)")
    if sync and cfg_engine.block_size != 1:
        raise ValueError(
            f"block_size={cfg_engine.block_size} has no meaning for the "
            f"synchronous method {method!r} (every client is "
            "activated every round)")
    block = 1 if sync else cfg_engine.block_size
    if mesh is not None:
        _validate_mesh(mesh, sync, method, block, M)
    key = jax.random.key(cfg_engine.seed)
    k_sched, k_idx, k_zoo = jax.random.split(key, 3)

    schedule = make_schedule(k_sched, T, M, probs, block)
    if schedule.ndim == 1:
        schedule = schedule[:, None]                     # (T, 1)
    sample_idx = jax.random.randint(k_idx, (T, bs), 0, n)
    zoo_keys = jax.random.split(k_zoo, T)

    # server-side table of latest client embeddings per sample (Fig. 2)
    table0 = jax.vmap(adapter.client_forward)(params["clients"],
                                              x_parts)   # (M, n, e)
    delays0 = jnp.zeros((M, n), jnp.int32)
    table_spec = None
    if mesh is not None:
        # partition the table rows via the "clients" logical axis rule
        table_spec = resolve_spec(mesh, table0.shape, adapter.table_logical,
                                  PARAM_RULES)
        table0 = jax.device_put(table0, NamedSharding(mesh, table_spec))

    runner = _make_runner(adapter, transport, vfl, sync, block,
                          cfg_engine.use_lanes, mesh, table_spec)
    args = (params, table0, delays0, schedule, sample_idx, zoo_keys,
            x_parts, y)
    return runner, args, int(table0.shape[-1]), M if sync else block


# ------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _make_runner(adapter: ModelAdapter, transport, vfl: VFLConfig,
                 sync: bool, block: int, use_lanes: bool,
                 mesh: Optional[Mesh] = None, table_spec: Optional[P] = None):
    """Build + jit the full scan for one (adapter, transport, vfl, block,
    mesh).

    lru-cached so benchmark sweeps that re-enter ``run`` with the same
    protocol reuse the compiled executable instead of retracing (the
    Transport is a frozen value object, so a noise-channel change is a
    cache miss and a no-noise Transport hashes like any other key).

    The single-device async scan carries the table sample-major,
    ``(n, M, e)``: a round's row gather and row write then both index
    the table's major axis, and the layout the gather wants is the
    carry's own (in ``(M, n, e)`` the TPU copied the whole table into
    that layout every round). It is transposed once on entry and once
    on exit, so callers see ``(M, n, e)``."""
    sample_major = not sync and mesh is None
    if sync:
        step_fn = _make_sync_step(adapter, transport, vfl)
    elif mesh is not None:
        step_fn = _make_sharded_step(adapter, transport, vfl, use_lanes,
                                     mesh, block, table_spec)
    else:
        step_fn = _make_sample_major_step(adapter, transport, vfl,
                                          use_lanes)

    def scan_all(params, table0, delays0, schedule, sample_idx, zoo_keys,
                 x_parts, y):
        if sample_major:
            table0 = jnp.swapaxes(table0, 0, 1)            # (n, M, e)

        def body(carry, t_in):
            params, table, delays = carry
            m_blk, idx, k = t_in
            params, table, loss = step_fn(params, table, m_blk, idx, k,
                                          x_parts, y)
            # delay bookkeeping (§III-C): activated (m,i) resets, others +1
            delays = delays + 1
            if sync:
                delays = delays * 0
            else:
                delays = delays.at[m_blk[:, None], idx[None, :]].set(0)
            return (params, table, delays), (loss, jnp.max(delays))

        (params, table, delays), outs = jax.lax.scan(
            body, (params, table0, delays0), (schedule, sample_idx, zoo_keys))
        if sample_major:
            table = jnp.swapaxes(table, 0, 1)              # (M, n, e)
        return (params, table, delays), outs

    return jax.jit(scan_all)


def _row_keys(key, rows):
    """Per-client-row RNG: fold the round key on the GLOBAL row index, so
    a block row draws the same directions no matter which device shard it
    lands on (single-device and sharded engines agree bitwise)."""
    k = jax.random.fold_in(key, 2)
    return jax.vmap(lambda r: jax.random.fold_in(k, r))(rows)


def _make_client_grad_fns(adapter: ModelAdapter, transport,
                          vfl: VFLConfig, use_lanes: bool):
    """Per-activated-client gradient closures shared by the single-device
    and sharded async steps (both vmap them over their block rows).

    Every scalar loss the client consumes passes through
    ``transport.downlink`` — the identity for a bare wire (same jaxpr as
    the pre-Transport engine), clip+noise under a DP channel. Adapters
    with a ``row_mask`` hook (active-row embedding clients) restrict the
    ZOO perturbation to the rows the batch touches."""
    if use_lanes and adapter.client_lanes is None:
        raise ValueError(
            f"adapter {adapter.name!r} has no client_lanes hook; "
            "run with use_lanes=False")
    if transport.noise is not None and vfl.zoo_unrolled_oracle:
        raise ValueError(
            "the DP loss channel requires the stacked lane path "
            "(vfl.zoo_unrolled_oracle=False); the unrolled per-query loop "
            "is a noise-free numerical test oracle")

    def _row_mask(client_m, x_m):
        return (adapter.row_mask(client_m, x_m)
                if adapter.row_mask is not None else None)

    @tags.wire("up", accounted_by="Transport.account", kind="embedding",
               reason="ZOO uplink: clean + q perturbed embeddings; the "
                      "loss downlink is sanitized via transport.downlink")
    def client_zoo_grad(server, c_stale, m, client_m, x_m, yb, key):
        """ZOO (ours / zoo-vfl): only losses cross the wire."""
        mask = _row_mask(client_m, x_m)
        if use_lanes:
            # stacked fan-out through the adapter's fused dual-pass (the
            # zoo_dual_matmul Pallas kernel for the tabular client)
            u_stack, d_eff = zoo.sample_directions(
                key, client_m, vfl.zoo_queries, vfl.zoo_dist, mask)
            phi = zoo.phi_factor(vfl.zoo_dist, d_eff)
            c_lanes = marks.wire_boundary(
                adapter.client_lanes(client_m, u_stack, vfl.mu, x_m),
                kind="emb", direction="up")
            losses = jax.vmap(
                lambda cf: adapter.server_loss(server, c_stale.at[m].set(cf),
                                               yb))(c_lanes)
            losses = transport.downlink(losses, key)
            return zoo.grad_from_losses(u_stack, losses[1:], losses[0],
                                        vfl.mu, phi)

        def c_loss(cm):
            cf = marks.wire_boundary(adapter.client_forward(cm, x_m),
                                     kind="emb", direction="up")
            cb = c_stale.at[m].set(cf)
            return adapter.server_loss(server, cb, yb)

        if transport.noise is None:
            # the downlink is identity on a bare wire; routing the stacked
            # losses through it anyway anchors the (1+q,) bottleneck in
            # the jaxpr (the unrolled oracle stays unmarked by design)
            g, _, _ = zoo.zoo_gradient(key, c_loss, client_m, vfl.mu,
                                       vfl.zoo_dist, vfl.zoo_queries,
                                       row_mask=mask,
                                       unrolled=vfl.zoo_unrolled_oracle,
                                       loss_transform=(
                                           None if vfl.zoo_unrolled_oracle
                                           else lambda losses:
                                           transport.downlink(losses, key)))
            return g
        # noised wire: evaluate the (1+q) lanes explicitly so the noise
        # lands on the transmitted losses, not inside the oracle (same
        # direction draws as zoo_gradient's stacked path at a fixed key)
        u_stack, d_eff = zoo.sample_directions(
            key, client_m, vfl.zoo_queries, vfl.zoo_dist, mask)
        phi = zoo.phi_factor(vfl.zoo_dist, d_eff)
        lanes = zoo.stack_lanes(client_m, u_stack, vfl.mu)
        losses = jax.vmap(c_loss)(lanes)
        losses = transport.downlink(losses, key)
        return zoo.grad_from_losses(u_stack, losses[1:], losses[0],
                                    vfl.mu, phi)

    @tags.wire("up", accounted_by="Transport.account", kind="embedding",
               reason="FOO uplink: one clean embedding per round")
    @tags.wire("down", accounted_by="Transport.account",
               kind="partial_derivative",
               reason="VAFL baseline is DECLARED leaky: the server returns "
                      "dL/dc_m and the ledger reports "
                      "transmits_gradients=True for it (paper §V contrast)")
    def client_foo_grad(server, c_stale, m, client_m, x_m, yb):
        """VAFL (privacy-leaky): server sends ∂L/∂c_m; client backprops."""
        def c_loss(cm):
            cb = c_stale.at[m].set(adapter.client_forward(cm, x_m))
            return adapter.server_loss(server, cb, yb)
        # grad_mark: these ARE first-order cotangents crossing client-ward;
        # certifying vafl must fail IF301 (the negative control)
        return marks.grad_mark(jax.grad(c_loss)(client_m))

    return client_zoo_grad, client_foo_grad


def _server_update(adapter: ModelAdapter, method: str, vfl: VFLConfig,
                   server, c_batch, yb, key):
    """One server step on the round's (stale + fresh-block) embeddings.

    Returns (new_server, h). FOO methods backprop locally (Eq. 4);
    zoo-vfl estimates with the same q-point two-point oracle the client
    uses (vfl.zoo_queries — the server is a ZOO party too)."""
    if method in ("cascaded", "vafl"):
        h, g_server = jax.value_and_grad(adapter.server_loss)(
            server, jax.lax.stop_gradient(c_batch), yb)
        # the engine's one sanctioned server-FOO point: mark the
        # cotangents so the certifier (IF301) can prove nothing derived
        # from them reaches a client-bound output except through the
        # scalar-loss bottleneck
        g_server = marks.grad_mark(g_server)
    else:  # zoo-vfl: server trains itself with ZOO too
        def s_loss(s):
            return adapter.server_loss(s, c_batch, yb)
        g_server, h, _ = zoo.zoo_gradient(
            jax.random.fold_in(key, 1), s_loss, server, vfl.mu,
            vfl.zoo_dist, vfl.zoo_queries,
            unrolled=vfl.zoo_unrolled_oracle)
    server = jax.tree.map(
        lambda w, g: (w - vfl.lr_server * g).astype(w.dtype), server,
        g_server)
    return server, h


def _make_async_step(adapter: ModelAdapter, transport, vfl: VFLConfig,
                     use_lanes: bool):
    """One asynchronous round for the activated client block {m_t}, over
    the server's ``(M, n, e)`` embedding table: the round of
    :func:`_make_sample_major_step`, with the table transposed in and
    out (the runner carries it sample-major and skips both). Within the
    round the table write comes before the stale read; the read stays
    the stale one, as that function's docstring shows."""
    step_rows = _make_sample_major_step(adapter, transport, vfl, use_lanes)

    def step(params, table, m_blk, idx, key, x_parts, y):
        params, rows, h = step_rows(params, jnp.swapaxes(table, 0, 1),
                                    m_blk, idx, key, x_parts, y)
        return params, jnp.swapaxes(rows, 0, 1), h

    return step


def _make_sample_major_step(adapter: ModelAdapter, transport,
                            vfl: VFLConfig, use_lanes: bool):
    """One asynchronous round over the sample-major ``(n, M, e)`` table.

    Within the round the table write comes BEFORE the stale read: the
    block's fresh embeddings go into rows ``(i_t, m)`` first, then the
    batch's rows of every client are read back. The read is still the
    stale one the protocol prescribes. The write changes only the
    activated clients' rows, so every other client's rows read the same
    before or after it, and every consumer of ``c_stale`` replaces the
    activated client's rows with its fresh embedding before using them
    (``c_batch`` below, ``c_stale.at[m].set`` in the client gradients),
    whatever the write left there, repeated sample indices included. A
    block of R > 1 clients must see each other's rows stale, so it
    reads its own R rows before the write and puts them back. Reading
    after the write lets XLA update the carried table in place instead
    of copying the whole table every round
    (``tests/test_async_sharded.py`` checks the compiled loop)."""
    method = transport.method
    client_zoo_grad, client_foo_grad = _make_client_grad_fns(
        adapter, transport, vfl, use_lanes)

    def step(params, table, m_blk, idx, key, x_parts, y):
        clients, server = params["clients"], params["server"]
        yb = y[idx]
        client_blk = jax.tree.map(lambda a: a[m_blk], clients)   # (R, ...)
        x_blk = x_parts[m_blk[:, None], idx[None, :]]            # (R, bs, f)

        # fresh embeddings per block; stale ones of all clients for this
        # batch, read after the table write (see the docstring)
        c_fresh = jax.vmap(adapter.client_forward)(client_blk, x_blk)
        block_stale = None
        if m_blk.shape[0] > 1:
            # the block's own rows, read first; the barrier keeps XLA
            # from fusing this read past the write (which would copy)
            block_stale, table = jax.lax.optimization_barrier(
                (table[idx[None, :], m_blk[:, None]], table))  # (R, bs, e)
        # refresh the table with the block's (pre-update) fresh embeddings
        with jax.named_scope("engine.table_write"):
            table = table.at[idx[None, :], m_blk[:, None]].set(c_fresh)
        c_stale = jnp.swapaxes(table[idx], 0, 1)                 # (M, bs, e)
        if block_stale is not None:
            c_stale = c_stale.at[m_blk].set(block_stale)
        c_batch = c_stale.at[m_blk].set(c_fresh)

        # ---- server update (sees every activated client fresh) ----------
        with jax.named_scope("engine.server_step"):
            server, h = _server_update(adapter, method, vfl, server,
                                       c_batch, yb, key)

        # ---- client updates (concurrent: each sees others STALE) --------
        with jax.named_scope("engine.client_zoo"):
            keys = _row_keys(key, jnp.arange(m_blk.shape[0]))
            if method == "vafl":
                g_blk = jax.vmap(
                    lambda m, cm, xm: client_foo_grad(server, c_stale, m,
                                                      cm, xm, yb)
                )(m_blk, client_blk, x_blk)
            else:
                g_blk = jax.vmap(
                    lambda m, cm, xm, k: client_zoo_grad(server, c_stale, m,
                                                         cm, xm, yb, k)
                )(m_blk, client_blk, x_blk, keys)
            new_client_blk = jax.tree.map(
                lambda cm, g: (cm - vfl.lr_client * g).astype(cm.dtype),
                client_blk, g_blk)
            clients = jax.tree.map(
                lambda all_, new: all_.at[m_blk].set(new), clients,
                new_client_blk)
        return {"clients": clients, "server": server}, table, h

    return step


def _make_sharded_step(adapter: ModelAdapter, transport, vfl: VFLConfig,
                       use_lanes: bool, mesh: Mesh, block: int,
                       table_spec: P):
    """Device-sharded asynchronous round: the block's R activated clients
    split R/D per device, the (M, n, e) table splits M/D rows per device,
    and cross-device traffic happens only at the server-loss boundary
    (all_gather) plus one float-exact psum replicating the sparse client
    updates. See module docstring for the equivalence guarantees."""
    method = transport.method
    client_zoo_grad, client_foo_grad = _make_client_grad_fns(
        adapter, transport, vfl, use_lanes)
    D = mesh.shape[CLIENT_AXIS]
    rows_local = block // D

    def shard_body(clients, server, table_l, m_blk_l, idx, key, x_parts, y):
        shard = jax.lax.axis_index(CLIENT_AXIS)
        rows_table = table_l.shape[0]                    # M / D
        yb = y[idx]
        # local block rows gather from the REPLICATED client param stack
        client_blk = jax.tree.map(lambda a: a[m_blk_l], clients)
        x_blk = x_parts[m_blk_l[:, None], idx[None, :]]  # (R/D, bs, f)

        # ---- server-loss boundary: the only gather of the round ---------
        # each shard contributes its table rows' stale embeddings and its
        # block rows' fresh embeddings; shard order == global row order
        c_stale = jax.lax.all_gather(table_l[:, idx], CLIENT_AXIS,
                                     axis=0, tiled=True)          # (M, bs, e)
        c_fresh = jax.vmap(adapter.client_forward)(client_blk, x_blk)
        c_fresh_all = jax.lax.all_gather(c_fresh, CLIENT_AXIS,
                                         axis=0, tiled=True)      # (R, bs, e)
        m_all = jax.lax.all_gather(m_blk_l, CLIENT_AXIS,
                                   axis=0, tiled=True)            # (R,)
        c_batch = c_stale.at[m_all].set(c_fresh_all)

        # ---- server update: replicated compute, identical per shard -----
        # (tiny vs the q× client fan-outs, which stay fully sharded — the
        # FOO step overlaps the other shards' fan-outs instead of
        # serializing a parameter broadcast behind them)
        server, h = _server_update(adapter, method, vfl, server, c_batch,
                                   yb, key)

        # ---- client updates: each shard fans out ONLY its block rows ----
        keys = _row_keys(key, shard * rows_local + jnp.arange(rows_local))
        if method == "vafl":
            g_blk = jax.vmap(
                lambda m, cm, xm: client_foo_grad(server, c_stale, m, cm,
                                                  xm, yb)
            )(m_blk_l, client_blk, x_blk)
        else:
            g_blk = jax.vmap(
                lambda m, cm, xm, k: client_zoo_grad(server, c_stale, m, cm,
                                                     xm, yb, k)
            )(m_blk_l, client_blk, x_blk, keys)
        new_client_blk = jax.tree.map(
            lambda cm, g: (cm - vfl.lr_client * g).astype(cm.dtype),
            client_blk, g_blk)

        # replicate the sparse update: activated clients are DISTINCT, so
        # each global row is written by exactly one shard and the psum of
        # one value plus zeros is float-exact (bitwise == .at[].set)
        mask = jax.lax.psum(
            jnp.zeros((_stack_rows(clients),), jnp.float32)
            .at[m_blk_l].set(1.0), CLIENT_AXIS)

        def replicate_rows(all_, new):
            buf = jax.lax.psum(
                jnp.zeros_like(all_).at[m_blk_l].set(new), CLIENT_AXIS)
            m = mask.reshape((-1,) + (1,) * (all_.ndim - 1))
            return jnp.where(m > 0, buf, all_)

        clients = jax.tree.map(replicate_rows, clients, new_client_blk)

        # ---- local table refresh: keep only rows this shard owns --------
        # (out-of-range scatter indices are dropped by JAX's default mode)
        local_m = m_all - shard * rows_table
        safe_m = jnp.where((local_m >= 0) & (local_m < rows_table),
                           local_m, rows_table)
        table_l = table_l.at[safe_m[:, None], idx[None, :]].set(c_fresh_all)
        return clients, server, table_l, h

    sharded = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(), table_spec, P(CLIENT_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P(), table_spec, P()),
        check_vma=False)

    def step(params, table, m_blk, idx, key, x_parts, y):
        clients, server, table, h = sharded(
            params["clients"], params["server"], table, m_blk, idx, key,
            x_parts, y)
        return {"clients": clients, "server": server}, table, h

    return step


def _stack_rows(clients) -> int:
    """Leading (M) axis of the stacked client parameter pytree."""
    return jax.tree.leaves(clients)[0].shape[0]


def _make_sync_step(adapter: ModelAdapter, transport, vfl: VFLConfig):
    """Synchronous rounds: Split-Learning (FOO) / Syn-ZOO-VFL."""
    method = transport.method

    def step(params, table, m_blk, idx, key, x_parts, y):
        xb = x_parts[:, idx, :]                          # (M, bs, f)
        yb = y[idx]

        if method == "split":
            h, grads = jax.value_and_grad(adapter.global_loss)(params, xb,
                                                               yb)
            # Split-Learning backprops THROUGH the boundary: its client
            # grads are cotangents (declared leaky; certifying it must
            # fail IF301 — the FOO negative control)
            grads = marks.grad_mark(grads)
        else:  # syn-zoo: every party (server + each client) does ZOO
            # the shared global draw's (1+q,) losses are what every party
            # consumes — route them through the downlink so the sync
            # simulation carries the same jaxpr bottleneck anchor as the
            # async methods (identity: sync methods reject noise)
            grads, h, _ = zoo.zoo_gradient(
                key, lambda p: adapter.global_loss(p, xb, yb), params,
                vfl.mu, vfl.zoo_dist, vfl.zoo_queries,
                unrolled=vfl.zoo_unrolled_oracle,
                loss_transform=(None if vfl.zoo_unrolled_oracle
                                else lambda losses:
                                transport.downlink(losses, key)))
        params = jax.tree.map(
            lambda w, g: (w - vfl.lr_server * g).astype(w.dtype), params,
            grads)
        return params, table, h

    return step


# ===================================================== population plane ====

@dataclasses.dataclass(frozen=True)
class PopulationConfig:
    """Population-scale knobs on top of the sampled activation schedule.

    ``admission_ms``: a delivered uplink slower than this virtual budget
    is a straggler — the round proceeds without that client (its stale
    table row serves instead; it retries at its next activation).
    ``staleness_bound``: a registered client whose table rows are older
    than this many rounds is force-activated, replacing sampled block
    members from the end (VAFL's bounded-delay assumption, enforced by
    admission instead of assumed)."""
    admission_ms: Optional[float] = None
    staleness_bound: Optional[int] = None


@dataclasses.dataclass
class AsyncPlaneState:
    """The async engine's FULL mutable state between rounds — everything
    a checkpoint must carry for a killed run to resume bitwise: the
    embedding table, the delay counters, the per-client activity clock
    for bounded-staleness forcing, the virtual wall clock, and the fault
    counters. The RNG needs no state: every stream (schedule, batches,
    directions, noise, faults) is a pure function of (seed, round)."""
    step: int
    table: np.ndarray
    delays: np.ndarray
    last_active: np.ndarray
    clock_ms: float = 0.0
    max_delay_seen: int = 0
    counters: dict = dataclasses.field(default_factory=dict)
    seed: int = 0

    def save(self, path: str) -> None:
        from repro.checkpoint.io import save_checkpoint
        save_checkpoint(path, {"table": np.asarray(self.table),
                               "delays": np.asarray(self.delays),
                               "last_active": np.asarray(self.last_active)},
                        step=self.step,
                        metadata={"clock_ms": float(self.clock_ms),
                                  "max_delay_seen": int(self.max_delay_seen),
                                  "counters": dict(self.counters),
                                  "seed": int(self.seed)})

    @classmethod
    def load(cls, path: str) -> "AsyncPlaneState":
        from repro.checkpoint.io import load_tree
        tree, step, meta = load_tree(path)
        return cls(step=int(step),
                   table=np.asarray(tree["table"]),
                   delays=np.asarray(tree["delays"]),
                   last_active=np.asarray(tree["last_active"]),
                   clock_ms=float(meta["clock_ms"]),
                   max_delay_seen=int(meta["max_delay_seen"]),
                   counters=dict(meta["counters"]),
                   seed=int(meta["seed"]))


@dataclasses.dataclass
class PopulationResult(EngineResult):
    """:class:`EngineResult` plus the wire plane's measurements."""
    state: Optional[AsyncPlaneState] = None
    serialized_bytes: int = 0      # measured frame bytes (§V data plane)
    overhead_bytes: int = 0        # serialization overhead over payloads
    control_bytes: int = 0         # act/skip/collect/params frames
    dp_releases: int = 0
    stats: dict = dataclasses.field(default_factory=dict)


@functools.lru_cache(maxsize=64)
def _population_fns(adapter: ModelAdapter, transport, vfl: VFLConfig):
    """Jitted server-side compute for the population engine, cached per
    protocol (the worker side lives in ``repro.wire.worker``). The math
    is the legacy scan body's, split at the wire: the server consumes
    UPLOADED embedding lanes instead of running ``client_forward``."""
    method = transport.method

    def server_update(server, c_stale, c_fresh, m_adm, yb, key):
        c_batch = c_stale.at[m_adm].set(c_fresh)
        return _server_update(adapter, method, vfl, server, c_batch, yb,
                              key)

    def losses_fn(server, c_stale, m, emb_lanes, yb, key):
        # the lanes arrived as "emb" wire frames — anchor the uplink
        emb_lanes = marks.wire_boundary(emb_lanes, kind="emb",
                                        direction="up")
        losses = jax.vmap(
            lambda cf: adapter.server_loss(server, c_stale.at[m].set(cf),
                                           yb))(emb_lanes)
        return transport.downlink(losses, key)

    return jax.jit(server_update), jax.jit(losses_fn)


def _fresh_counters() -> dict:
    return {"rounds": 0, "activations": 0, "admitted": 0,
            "uplink_drops": 0, "stragglers": 0, "downlink_drops": 0,
            "forced": 0, "degraded_rounds": 0, "retransmit_frames": 0,
            "dead_parties": 0}


def run_population(adapter: ModelAdapter, transport, vfl: VFLConfig,
                   cfg_engine: EngineConfig, params, x_parts, y, *,
                   probs=None, fault_plan=None,
                   population: Optional[PopulationConfig] = None,
                   channels: Optional[dict] = None,
                   state: Optional[AsyncPlaneState] = None,
                   ledger: Optional[Ledger] = None, dp_releases: int = 0,
                   until: Optional[int] = None,
                   stop_workers: bool = True,
                   wire_timeout_s: Optional[float] = None
                   ) -> PopulationResult:
    """The asynchronous protocol over a REAL wire with fault injection.

    Every registered client (M = ``x_parts.shape[0]``) sits behind a
    ``repro.wire`` endpoint — in-proc :class:`LoopbackBackend` workers by
    default; pass ``channels={m: backend}`` to place party m behind an
    already-connected endpoint (e.g. a :class:`SocketBackend` whose
    worker process runs ``ClientWorker.serve``). Per round the sampled
    block is activated over the wire (act -> 1+q embedding frames up ->
    1+q loss frames down), the ledger meters each frame's ACTUAL
    serialized bytes (``Message.wired``; payload formula kept as the
    cross-check), and ``fault_plan`` decides drops/latency/retries in
    deterministic virtual time. Graceful degradation: a dropped or
    straggling client simply misses the round (its stale embeddings
    serve; the server still steps), so a 20% dropout rate slows
    convergence instead of hanging the round.

    ``state``/``until`` make the plane durable: ``until=k`` stops after
    round k and returns the full :class:`AsyncPlaneState`; passing that
    state back (with the SAME configs/seed and the collected params)
    continues bitwise — both halves replay the identical schedule, RNG
    and fault streams. ``ledger``/``dp_releases`` extend a restored
    run's accounting the same way.

    With ``FaultPlan.none()`` and no population knobs the result is
    bitwise-identical to :func:`run` (losses, params, table, delays).

    CRASH SEMANTICS for remote (``channels``-placed) parties: a party
    whose wire dies mid-round — the process was ``kill -9``'d, the frame
    stream corrupted, or ``wire_timeout_s`` elapsed without a frame — is
    DECLARED DEAD after the backend's own retry budget (a
    ``SocketBackend`` connected with ``self_heal=True`` reconnects with
    backoff underneath first). A dead party then degrades gracefully
    exactly like a permanent dropout: it misses every later activation
    (its stale embeddings keep serving), the round never hangs, and at
    collect time its parameter row falls back to the initial params the
    engine holds. ``counters["dead_parties"]`` reports the toll; a
    replacement process can rejoin a LATER run via
    ``ClientWorker.from_checkpoint``. Loopback parties never take this
    path — their failures are real bugs and stay fail-fast.
    """
    from repro.wire import codec
    from repro.wire.backend import (LoopbackBackend, WireClosed,
                                    WireTimeout)
    from repro.wire.codec import FrameCorruption
    from repro.wire.faults import FaultPlan
    from repro.wire.worker import ClientWorker

    method = transport.method
    if method in SYNC_METHODS or method == "vafl":
        raise ValueError(
            f"run_population drives the asynchronous ZOO wire; {method!r} "
            "is synchronous or sends gradients down (use run())")
    if cfg_engine.use_lanes:
        raise ValueError(
            "use_lanes routes the fan-out through a fused server-side "
            "kernel; the wire worker computes its own lanes")
    if cfg_engine.mesh_shards:
        raise ValueError("the population engine shards by PROCESS, not by "
                         "device mesh; set mesh_shards=0")
    if vfl.zoo_unrolled_oracle:
        raise ValueError("the wire protocol speaks the stacked lane path; "
                         "zoo_unrolled_oracle is the in-process test oracle")

    plan = fault_plan if fault_plan is not None else FaultPlan.none()
    pop = population if population is not None else PopulationConfig()
    M, n, _ = x_parts.shape
    T, bs = cfg_engine.steps, cfg_engine.batch_size
    block = cfg_engine.block_size
    q = vfl.zoo_queries

    key = jax.random.key(cfg_engine.seed)
    k_sched, k_idx, k_zoo = jax.random.split(key, 3)
    schedule = make_schedule(k_sched, T, M, probs, block)
    if schedule.ndim == 1:
        schedule = schedule[:, None]
    schedule_h = np.asarray(schedule)                     # (T, block)
    idx_h = np.asarray(jax.random.randint(k_idx, (T, bs), 0, n))
    zoo_keys = jax.random.split(k_zoo, T)

    server = params["server"]
    if state is None:
        table = jax.vmap(adapter.client_forward)(params["clients"],
                                                 x_parts)  # (M, n, e)
        delays = np.zeros((M, n), np.int32)
        last_active = np.zeros((M,), np.int32)
        clock_ms, maxd, start = 0.0, 0, 0
        counters = _fresh_counters()
    else:
        if state.seed != cfg_engine.seed:
            raise ValueError(
                f"resume state was produced under seed {state.seed}, "
                f"engine runs seed {cfg_engine.seed} — the schedule/RNG "
                "streams would diverge from the saved run")
        table = jnp.asarray(state.table)
        delays = np.array(state.delays, np.int32)
        last_active = np.array(state.last_active, np.int32)
        clock_ms, maxd = float(state.clock_ms), int(state.max_delay_seen)
        counters = {**_fresh_counters(), **state.counters}
        start = int(state.step)
    stop_at = T if until is None else min(int(until), T)
    if not start <= stop_at:
        raise ValueError(f"resume step {start} is past until={stop_at}")
    ledger = ledger if ledger is not None else Ledger()
    control_bytes = int(counters.pop("control_bytes", 0))
    noise_on = transport.noise is not None

    # ---- wire up the population: loopback workers for unplaced parties --
    channels = dict(channels or {})
    remote = frozenset(channels)    # parties that can actually die
    dead: set = set()
    local_workers: dict = {}
    for m in range(M):
        if m not in channels:
            eng_end, wk_end = LoopbackBackend.pair()
            local_workers[m] = ClientWorker(
                adapter, vfl,
                jax.tree.map(lambda a: a[m], params["clients"]),
                x_parts[m], m, wk_end)
            channels[m] = eng_end

    # failures a dying REMOTE party can surface through its channel;
    # anything else (protocol bugs, engine errors) stays fail-fast
    _WIRE_DEATH = (WireClosed, WireTimeout, FrameCorruption,
                   ConnectionError, OSError)

    def _mark_dead(m):
        dead.add(m)
        counters["dead_parties"] += 1

    def _pump(m):
        if m in local_workers:
            local_workers[m].pump()

    def _send_control(m, msg):
        nonlocal control_bytes
        control_bytes += channels[m].send(msg)
        _pump(m)

    def _recv(m):
        if m in remote and wire_timeout_s is not None:
            return channels[m].recv(timeout=wire_timeout_s)
        return channels[m].recv()

    server_update, losses_fn = _population_fns(adapter, transport, vfl)
    losses_out = []

    for t in range(start, stop_at):
        m_blk = [int(m) for m in schedule_h[t]]
        idx = idx_h[t]
        kt = zoo_keys[t]
        counters["rounds"] += 1

        # ---- bounded-staleness forcing: overdue clients preempt the ----
        # ---- sampled block (most-stale first, replacing from the end) --
        if pop.staleness_bound is not None:
            in_blk = set(m_blk)
            overdue = sorted(
                ((t - int(last_active[m]), m) for m in range(M)
                 if m not in in_blk
                 and t - int(last_active[m]) > pop.staleness_bound),
                key=lambda sm: (-sm[0], sm[1]))
            for i, (_, m) in enumerate(overdue[:len(m_blk)]):
                m_blk[len(m_blk) - 1 - i] = m
            counters["forced"] += min(len(overdue), len(m_blk))

        keys_r = _row_keys(kt, jnp.arange(len(m_blk)))

        # ---- phase 1: activate the block, collect uplinked lanes --------
        admitted = []               # (r, m, emb_lanes host arrays)
        emb_meter: list = [[] for _ in m_blk]   # (Message, copies)
        loss_meter: list = [[] for _ in m_blk]
        round_ms = 0.0
        for r, m in enumerate(m_blk):
            counters["activations"] += 1
            if m in dead:
                # declared dropout: the party misses the round outright —
                # no frames, no metering, stale embeddings keep serving
                counters["uplink_drops"] += 1
                continue
            kd = np.asarray(jax.random.key_data(keys_r[r]))
            lanes = []
            try:
                _send_control(m, codec.WireMessage(
                    "act", "server", t, {"party": m},
                    {"idx": idx, "key": kd}))
                for _ in range(1 + q):
                    msg, nb = _recv(m)
                    if msg.tag != "emb":  # pragma: no cover - protocol
                        raise ValueError(
                            f"expected emb frame, got {msg.tag!r}")
                    arr = msg.payload["c"]
                    lanes.append(arr)
                    up = plan.delivery(t, m, "up")
                    emb_meter[r].append((Message(
                        "client", "embedding", tuple(arr.shape),
                        str(arr.dtype), wired=nb), up.attempts))
            except _WIRE_DEATH:
                if m not in remote:
                    raise       # loopback failures are bugs, not churn
                _mark_dead(m)
                counters["uplink_drops"] += 1
                emb_meter[r] = []   # nothing usable arrived — meter none
                continue
            counters["retransmit_frames"] += (up.attempts - 1) * (1 + q)
            client_ms = up.elapsed_ms
            if not up.ok:
                counters["uplink_drops"] += 1
                _send_control(m, codec.WireMessage(
                    "skip", "server", t, {"reason": "drop"}))
            elif (pop.admission_ms is not None
                  and up.elapsed_ms > pop.admission_ms):
                counters["stragglers"] += 1
                _send_control(m, codec.WireMessage(
                    "skip", "server", t, {"reason": "straggler"}))
            else:
                admitted.append((r, m, lanes))
            round_ms = max(round_ms, client_ms)

        # ---- phase 2: server step on stale table + admitted fresh -------
        c_stale = table[:, idx]
        e = int(table.shape[-1])
        if admitted:
            m_adm = jnp.asarray([m for _, m, _ in admitted], jnp.int32)
            c_fresh = jnp.stack([jnp.asarray(l[0]) for _, _, l in admitted])
        else:
            counters["degraded_rounds"] += 1
            m_adm = jnp.zeros((0,), jnp.int32)
            c_fresh = jnp.zeros((0, bs, e), table.dtype)
        server, h = server_update(server, c_stale, c_fresh, m_adm,
                                  y[idx], kt)
        losses_out.append(np.asarray(h))

        # ---- phase 3: loss downlinks to admitted clients ----------------
        for r, m, lanes in admitted:
            emb_lanes = jnp.stack([jnp.asarray(a) for a in lanes])
            losses = losses_fn(server, c_stale, m, emb_lanes, y[idx],
                               keys_r[r])
            down = plan.delivery(t, m, "down")
            losses_h = np.asarray(losses)
            try:
                for lane in range(1 + q):
                    nb = channels[m].send(codec.WireMessage(
                        "loss", "server", t,
                        {"lane": lane, "delivered": bool(down.ok)},
                        {"h": losses_h[lane]}))
                    loss_meter[r].append((Message(
                        "server", "loss", (), str(losses_h.dtype),
                        wired=nb), down.attempts))
            except _WIRE_DEATH:
                # died between uplink and downlink: the server already
                # consumed its fresh embeddings (that's fine — they were
                # real), the client just never gets this round's losses
                if m not in remote:
                    raise
                _mark_dead(m)
                counters["downlink_drops"] += 1
                continue
            _pump(m)
            counters["retransmit_frames"] += (down.attempts - 1) * (1 + q)
            if noise_on:
                dp_releases += 1 + q
            if not down.ok:
                counters["downlink_drops"] += 1
            round_ms = max(round_ms, plan.delivery(t, m, "up").elapsed_ms
                           + down.elapsed_ms)

        # ---- ledger: per client in block order, uplinks then downlinks --
        # (matches the legacy per-client round_messages grouping)
        for r in range(len(m_blk)):
            for msg_rec, copies in emb_meter[r] + loss_meter[r]:
                transport.account_wire(msg_rec, copies=copies,
                                       ledger=ledger)
        counters["admitted"] += len(admitted)

        # ---- phase 4: table/delay/clock bookkeeping ---------------------
        delays += 1
        if admitted:
            adm_rows = np.asarray([m for _, m, _ in admitted])
            table = table.at[jnp.asarray(adm_rows)[:, None],
                             jnp.asarray(idx)[None, :]].set(c_fresh)
            delays[adm_rows[:, None], idx[None, :]] = 0
            last_active[adm_rows] = t
        maxd = max(maxd, int(delays.max()))
        clock_ms += round_ms

    # ---- collect the population's parameters back over the wire --------
    rows = []
    for m in range(M):
        fallback = jax.tree.map(lambda a: a[m], params["clients"])
        if m in dead:
            rows.append(fallback)   # best knowledge: the initial row
            continue
        try:
            _send_control(m, codec.WireMessage("collect", "server",
                                               stop_at))
            msg, nb = _recv(m)
            if msg.tag != "params":  # pragma: no cover - protocol error
                raise ValueError(f"expected params frame, got {msg.tag!r}")
            control_bytes += nb
            rows.append(jax.tree.map(jnp.asarray,
                                     codec.unflatten_tree(msg.payload)))
        except _WIRE_DEATH:
            if m not in remote:
                raise
            _mark_dead(m)
            rows.append(fallback)
    clients = jax.tree.map(lambda *rs: jnp.stack(rs), *rows)
    if stop_workers:
        for m in range(M):
            if m in dead:
                continue
            try:
                _send_control(m, codec.WireMessage("stop", "server",
                                                   stop_at))
            except _WIRE_DEATH:
                if m not in remote:
                    raise
                _mark_dead(m)

    counters["control_bytes"] = control_bytes
    out_state = AsyncPlaneState(
        step=stop_at, table=np.asarray(table), delays=delays,
        last_active=last_active, clock_ms=clock_ms, max_delay_seen=maxd,
        counters=counters, seed=cfg_engine.seed)
    eps, delta = transport.privacy_spent(dp_releases)
    executed = stop_at - start
    formula = transport.account(batch=bs, embed=int(table.shape[-1]),
                                zoo_queries=q, n_clients=block,
                                n_rounds=executed)
    stats = {
        "rounds_executed": executed,
        "virtual_ms": clock_ms,
        "formula_bytes": formula.total_bytes,
        "participation": (counters["admitted"]
                          / max(counters["activations"], 1)),
        **{k: counters[k] for k in ("uplink_drops", "stragglers",
                                    "downlink_drops", "forced",
                                    "degraded_rounds",
                                    "retransmit_frames",
                                    "dead_parties")},
    }
    return PopulationResult(
        params={"clients": clients, "server": server},
        losses=np.asarray(losses_out), max_delay_seen=maxd,
        mean_delay=float(delays.mean()), wire_bytes=ledger.total_bytes,
        transmits_gradients=ledger.transmits_gradients, ledger=ledger,
        epsilon=eps, delta=delta, state=out_state,
        serialized_bytes=ledger.serialized_bytes,
        overhead_bytes=ledger.overhead_bytes, control_bytes=control_bytes,
        dp_releases=dp_releases, stats=stats)
