"""Jaxpr boundary anchors: identity primitives the certifier keys on.

The AST taint pass (``repro.analysis.boundary``) trusts source-level
``@tags`` annotations; the jaxpr certifier (``repro.analysis.ifc``)
instead proves the party boundary on the program JAX actually traces.
For that it needs *anchors in the jaxpr* — equations that mark where a
value legally crosses the wire, where DP noise is applied, and which
values are first-order cotangents of server parameters.

These marks are custom JAX primitives that are **identities at
runtime**: their MLIR lowering forwards the operand unchanged, so the
compiled HLO — and therefore every bitwise-equality guarantee the repo
makes (split == global decode, kill/resume == straight-through, wire
worker == in-proc) — is untouched. Each primitive carries batching,
JVP and transpose rules so it composes with ``vmap`` (the engine vmaps
client grad closures over block rows), ``scan``, ``jit`` and autodiff.

Anchors
-------
* :func:`wire_boundary` — the value crosses the party boundary here.
  ``kind`` names the payload (``"emb"``/``"loss"``/``"token"``, matching
  the wire plane's frame tags), ``direction`` is ``"up"`` (client →
  server) or ``"down"`` (server → client). Emitted by
  ``Transport.downlink`` (the ONE legal loss downlink), the engine's
  client-lane fan-outs, and the serve plane's embed/token hops.
* :func:`dp_noise` — the operand has just been Gaussian-noised by a
  configured ``GaussianLossChannel``. Emitted inside
  ``Transport.downlink`` between the noise add and the wire mark, so
  the certifier can check DP happens *before* the wire (IF303).
* :func:`grad_mark` — the operand is (derived from) a first-order
  cotangent of server parameters. Emitted at the engine's one
  sanctioned server-FOO point (``async_engine._server_update``); IF301
  proves this taint never reaches a client-bound output. The AST rule
  PB102 covers *textual* ``jax.grad`` calls outside the engine.
"""
from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

# Payload kinds a wire_boundary mark may carry. "emb" and "loss" mirror
# repro.wire.codec.DATA_TAGS (training-plane frames); "token" is the
# serve plane's per-step token downlink (metered by Transport.account_serve,
# not framed by the wire codec).
WIRE_KINDS: Tuple[str, ...] = ("emb", "loss", "token")
DIRECTIONS: Tuple[str, ...] = ("up", "down")


def _identity_primitive(name: str) -> Primitive:
    """A unary primitive that is the identity at runtime.

    impl/abstract_eval return the operand; the MLIR lowering forwards
    the SSA value itself (no op is emitted, compiled bytes identical);
    batching maps straight through; the primitive is linear, so JVP and
    transpose are identities too.
    """
    prim = Primitive(name)

    def _impl(x: Any, **_: Any) -> Any:
        return x

    def _abstract(x: Any, **_: Any) -> Any:
        return x

    def _lowering(ctx: Any, x: Any, **_: Any) -> Sequence[Any]:
        return [x]

    def _batch(args: Sequence[Any], dims: Sequence[Any],
               **params: Any) -> Tuple[Any, Any]:
        (x,), (d,) = args, dims
        return prim.bind(x, **params), d

    def _transpose(ct: Any, x: Any, **params: Any) -> Sequence[Any]:
        return [ct]

    prim.def_impl(_impl)
    prim.def_abstract_eval(_abstract)
    mlir.register_lowering(prim, _lowering)
    batching.primitive_batchers[prim] = _batch
    ad.deflinear2(prim, _transpose)
    return prim


wire_boundary_p = _identity_primitive("vfl_wire_boundary")
dp_noise_p = _identity_primitive("vfl_dp_noise")
grad_mark_p = _identity_primitive("vfl_grad_mark")


def wire_boundary(x: Any, *, kind: str, direction: str) -> Any:
    """Mark ``x`` (array or pytree) as crossing the party boundary."""
    if kind not in WIRE_KINDS:
        raise ValueError(f"unknown wire kind {kind!r}; expected {WIRE_KINDS}")
    if direction not in DIRECTIONS:
        raise ValueError(
            f"unknown direction {direction!r}; expected {DIRECTIONS}")
    return jax.tree_util.tree_map(
        lambda leaf: wire_boundary_p.bind(leaf, kind=kind,
                                          direction=direction), x)


def dp_noise(x: Any) -> Any:
    """Mark ``x`` as the output of a configured DP noise channel."""
    return jax.tree_util.tree_map(dp_noise_p.bind, x)


def grad_mark(x: Any) -> Any:
    """Mark ``x`` as derived from server-parameter cotangents."""
    return jax.tree_util.tree_map(grad_mark_p.bind, x)
