"""The paper's contribution: cascaded hybrid optimization (Alg. 1).

One SPMD train step =
  1. client forward, clean + perturbed:  c = F_m(w_m;x),  ĉ = F_m(w_m+μu;x)
  2. server losses  h = L(F_0(w_0, c), y),  ĥ = L(F_0(w_0, ĉ), y)
     (only c/ĉ go up the wire, only h/ĥ come down — the privacy ledger in
     ``repro.core.privacy`` accounts for exactly these)
  3. client ZOO grad   ∇̂_{w_m} = φ(d_m)/μ (ĥ − h) u         (Eq. 3)
  4. server FOO grad   ∇_{w_0} = ∂[L + λg(w_0)]/∂w_0          (Eq. 4, local
     backprop — never transmitted)
  5. SGD updates on both partitions.

The server backward never differentiates through the client partition
(stop_gradient on the boundary embeddings), exactly matching the protocol:
the server cannot form ∂L/∂w_m because it does not know F_m.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import VFLConfig
from repro.core import zoo
from repro.core.methods import canonical_method
from repro.core.partition import merge_params, split_params


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StepOutput:
    loss: jnp.ndarray
    loss_perturbed: jnp.ndarray
    grad_client_norm: jnp.ndarray
    grad_server_norm: jnp.ndarray


def _maybe_row_mask(cfg_vfl: VFLConfig, client, batch, vocab: int):
    """Active-row perturbation mask tree for the embedding table."""
    if not cfg_vfl.active_rows_only:
        return None
    mask_tree = jax.tree.map(
        lambda w: jnp.ones((w.shape[0],), jnp.float32), client)
    if "embed" in client and "tokens" in batch:
        m = zoo.embedding_row_mask(batch["tokens"], vocab)
        mask_tree = dict(mask_tree)
        mask_tree["embed"] = {"table": m}
    return mask_tree


def make_cascaded_step(loss_fn: Callable, client_keys: Tuple[str, ...],
                       vfl: VFLConfig, optimizer,
                       vocab: int = 0, transport=None) -> Callable:
    """Build the jittable cascaded hybrid step.

    loss_fn(params, batch) -> (loss, aux).  optimizer: repro.optim object
    with ``init(params)`` / ``update(grads, state, params)``.
    Returns step(params, opt_state, batch, key) -> (params, opt_state, StepOutput).

    ``transport`` (a ``repro.federation.Transport``) optionally noises the
    scalar losses the CLIENT receives over the downlink before it forms
    its ZOO gradient (Eq. 3); the server's FOO step keeps the exact local
    loss — only the wire is perturbed, matching the async engine.
    """
    if transport is not None and transport.noise is not None \
            and not vfl.fused_dual:
        raise ValueError(
            "the DP loss channel requires the fused lane path "
            "(vfl.fused_dual=True); the unrolled per-query loop is a "
            "noise-free numerical test oracle")

    def step(params, opt_state, batch, key):
        client, server = split_params(params, client_keys)
        row_mask = _maybe_row_mask(vfl, client, batch, vocab)

        if vfl.fused_dual:
            # ---- default path: vectorized fan-out. ALL q directions are
            # drawn as stacked leaves, so compile time / dispatch overhead
            # are constant in q. The server backpropagates (Eq. 4) through
            # the clean lane alone; the q perturbed lanes run ONE vmapped
            # forward pass, with no residuals kept and no transpose, since
            # they only give back loss scalars for Eq. 3. The server
            # weights are unbatched inside the vmap; under FSDP they are
            # all-gathered for two passes a step (the clean lane's
            # forward/backward and the lanes' forward), not one per lane.
            # It computes what the unrolled oracle below computes.
            with jax.named_scope("cascade.client_lanes"):
                u_stack, d_eff = zoo.sample_directions(
                    key, client, vfl.zoo_queries, vfl.zoo_dist, row_mask)
                phi = zoo.phi_factor(vfl.zoo_dist, d_eff)
                lanes = zoo.stack_lanes(jax.lax.stop_gradient(client),
                                        u_stack, vfl.mu)
                clean = jax.tree.map(lambda a: a[0], lanes)
                perturbed = jax.tree.map(lambda a: a[1:], lanes)

            def server_loss(server_p):
                return loss_fn(merge_params(clean, server_p), batch)[0]

            with jax.named_scope("cascade.server_fwd_bwd"):
                loss_clean, g_server = jax.value_and_grad(server_loss)(
                    server)
            with jax.named_scope("cascade.server_lanes"):
                frozen = jax.lax.stop_gradient(server)
                losses_pert = jax.vmap(
                    lambda c: loss_fn(merge_params(c, frozen), batch)[0]
                )(perturbed)
            losses = jnp.concatenate([loss_clean[None], losses_pert])
            # the client builds Eq. 3 from the losses it RECEIVES — under
            # a DP transport those are the clipped+noised downlink values
            with jax.named_scope("cascade.client_update"):
                recv = (losses if transport is None
                        else transport.downlink(losses, key))
                g_client = zoo.grad_from_losses(u_stack, recv[1:], recv[0],
                                                vfl.mu, phi)
            loss_pert = losses[1]
        else:
            # ---- unrolled oracle (test-only): per-query Python loop,
            # separate server passes. Kept as the numerical reference for
            # the stacked path; never the production configuration.
            keys = jax.random.split(key, vfl.zoo_queries)
            us, d_effs = zip(*[zoo.sample_direction(k, client, vfl.zoo_dist,
                                                    row_mask) for k in keys])
            phis = [zoo.phi_factor(vfl.zoo_dist, d) for d in d_effs]

            # server FOO (Eq. 4): exact backprop on w_0 only
            def server_loss(server_p):
                loss, _ = loss_fn(
                    merge_params(jax.lax.stop_gradient(client), server_p),
                    batch)
                return loss

            loss_clean, g_server = jax.value_and_grad(server_loss)(server)
            lps = [loss_fn(merge_params(zoo.perturb(client, u, vfl.mu),
                                        server), batch)[0]
                   for u in us]

            # client ZOO (Eq. 2/3). The raw-loss feed is sanctioned here:
            # this branch is the noise-free numerical reference and the
            # engine rejects DP transports on it (ValueError above).
            # analysis: ignore[PB105] test-only oracle; DP transports are rejected on this path
            gs = [zoo.two_point_grad(u, lp, loss_clean, vfl.mu, phi)
                  for u, lp, phi in zip(us, lps, phis)]
            g_client = jax.tree.map(lambda *x: sum(x) / float(len(x)), *gs)
            loss_pert = lps[0]

        # ---- updates (separate lrs per party, paper §VI-A-d) -------------
        # one optimizer update applies the server's gradient and the
        # client's (Eq. 3's, rescaled to the client lr)
        with jax.named_scope("cascade.server_update"):
            grads = merge_params(
                jax.tree.map(lambda g: g * (vfl.lr_client / vfl.lr_server),
                             g_client),
                g_server)
            new_params, new_opt_state = optimizer.update(grads, opt_state,
                                                         params)

        out = StepOutput(
            loss=loss_clean, loss_perturbed=loss_pert,
            grad_client_norm=_norm(g_client), grad_server_norm=_norm(g_server))
        return new_params, new_opt_state, out

    return step


def make_step_for_method(method: str, loss_fn, client_keys, vfl: VFLConfig,
                         optimizer, vocab: int = 0, transport=None):
    """Factory covering the paper's five frameworks at step granularity.

    cascaded      : ZOO client + FOO server   (ours)
    vafl / split  : FOO client + FOO server   (privacy-leaky upper bound)
    zoo-vfl / syn-zoo : ZOO client + ZOO server
    (sync-vs-async semantics live in repro.core.async_engine; spellings
    normalize through repro.core.methods so the three modules agree).

    ``transport`` optionally carries the DP loss channel (cascaded only at
    step granularity; the other ZOO methods noise through the async
    engine)."""
    method = canonical_method(method)
    if transport is not None and transport.method != method:
        raise ValueError(f"transport method {transport.method!r} does not "
                         f"match step method {method!r}")
    if method == "cascaded":
        return make_cascaded_step(loss_fn, client_keys, vfl, optimizer,
                                  vocab, transport)
    if transport is not None and transport.noise is not None:
        raise NotImplementedError(
            f"the DP loss channel is wired into the cascaded step factory "
            f"and the async engine; for {method!r} run through "
            "Federation.run")
    if method in ("vafl", "split"):
        return make_foo_step(loss_fn, optimizer)
    assert method in ("zoo-vfl", "syn-zoo"), method
    return make_full_zoo_step(loss_fn, client_keys, vfl, optimizer, vocab)


def make_foo_step(loss_fn, optimizer):
    """First-order step on all parties (Split-Learning / VAFL)."""
    def step(params, opt_state, batch, key):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params,
                                                                     batch)
        new_params, new_opt_state = optimizer.update(grads, opt_state, params)
        out = StepOutput(loss=loss, loss_perturbed=loss,
                         grad_client_norm=_norm(grads),
                         grad_server_norm=_norm(grads))
        return new_params, new_opt_state, out
    return step


def make_full_zoo_step(loss_fn, client_keys, vfl: VFLConfig, optimizer,
                       vocab: int = 0):
    """ZOO on both partitions (ZOO-VFL baseline [42]): the server also
    estimates its gradient with a two-point query on its own parameters."""
    def step(params, opt_state, batch, key):
        client, server = split_params(params, client_keys)
        k_c, k_s = jax.random.split(key)

        def loss_of_client(c):
            return loss_fn(merge_params(c, server), batch)[0]

        def loss_of_server(s):
            return loss_fn(merge_params(client, s), batch)[0]

        g_client, loss_clean, _ = zoo.zoo_gradient(
            k_c, loss_of_client, client, vfl.mu, vfl.zoo_dist,
            vfl.zoo_queries, unrolled=vfl.zoo_unrolled_oracle)
        g_server, _, _ = zoo.zoo_gradient(
            k_s, loss_of_server, server, vfl.mu, vfl.zoo_dist,
            vfl.zoo_queries, unrolled=vfl.zoo_unrolled_oracle)

        grads = merge_params(
            jax.tree.map(lambda g: g * (vfl.lr_client / vfl.lr_server),
                         g_client),
            g_server)
        new_params, new_opt_state = optimizer.update(grads, opt_state, params)
        out = StepOutput(loss=loss_clean, loss_perturbed=loss_clean,
                         grad_client_norm=_norm(g_client),
                         grad_server_norm=_norm(g_server))
        return new_params, new_opt_state, out
    return step


def _norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))
