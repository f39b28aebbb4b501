"""Information-flow certifier over jaxprs (IF301–IF303).

The AST taint pass (``boundary.py``) checks the party boundary on the
*source text*: it trusts ``@tags`` annotations and cannot see through
closures, ``jit`` or adapter indirection. This pass proves the claim on
the *traced program*: ``jax.make_jaxpr`` on a real step closure, then a
forward taint/dataflow analysis over the jaxpr's equations — the same
equations XLA compiles — anchored on the identity primitives from
``marks.py``:

* ``vfl_wire_boundary[kind, direction]`` — the one legal crossing point
  (emitted by ``Transport.downlink``, the engine's uplink fan-outs, the
  serve plane's embed/token hops);
* ``vfl_dp_noise`` — a configured ``GaussianLossChannel`` just noised
  the operand;
* ``vfl_grad_mark`` — the operand derives from first-order cotangents
  of server parameters (the engine's one sanctioned server-FOO point).

Taint lattice: each var carries a set of labels from {``server``,
``grad``, ``dp``}. Inputs labelled ``server`` seed the analysis (the
caller maps pytree paths to parties); ``grad_mark`` adds ``grad``;
``dp_noise`` *replaces* taint with ``dp`` (the noised value is what DP
releases); ``wire_boundary`` records the crossing — payload kind,
direction, shape and dtype read off the jaxpr, plus the incoming taint —
and clears taint (whatever legally crossed is the sanctioned release).
Sub-jaxprs (``jit``/``scan``/``while``/``cond``/``custom_jvp_call``/…)
are walked recursively, loop carries to a fixed point; an unknown
higher-order primitive falls back to all-inputs-to-all-outputs, a sound
overapproximation.

Rules (evaluated by :func:`check_flows` on the analysis report):

* **IF301** — no client-bound output may carry ``grad`` taint: nothing
  derived from server-parameter cotangents reaches a client except
  through the wire bottleneck (which launders taint by construction).
* **IF302** — every server→client flow must factor through a
  ``wire_boundary`` crossing, and every *downlink* crossing must be the
  scalar bottleneck the paper claims: at most ``(1+q)·block`` loss
  scalars (or ``batch`` token ids for the serve plane) per round, shape
  read off the jaxpr, not asserted.
* **IF303** — when a DP channel is configured, every loss downlink
  crossing must be noise-dominated: its operand carries ``dp`` taint
  and no raw ``server`` taint (noise added *before* the wire).

IF304 (wire-plane cross-checks) lives in ``certify.py`` — it compares
the crossing inventory against what the wire plane serializes.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, FrozenSet, List, Mapping, Optional,
                    Sequence, Tuple)

import jax
from jax.extend import core as jax_core

from repro.analysis.findings import Finding

SERVER = "server"
GRAD = "grad"
DP = "dp"

Taint = FrozenSet[str]
_EMPTY: Taint = frozenset()


@dataclasses.dataclass(frozen=True)
class Crossing:
    """One ``wire_boundary`` equation encountered in the traced program."""
    kind: str              # "emb" | "loss" | "token"
    direction: str         # "up" | "down"
    shape: Tuple[int, ...]
    dtype: str
    taint: Taint           # taint of the operand AT the crossing

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.kind, "direction": self.direction,
                "shape": list(self.shape), "dtype": self.dtype,
                "elements": self.size, "taint": sorted(self.taint)}


@dataclasses.dataclass
class IFCReport:
    """Result of the taint pass over one traced closure."""
    out_taints: List[Taint]
    crossings: List[Crossing]
    n_dp_eqns: int

    def down(self, kind: Optional[str] = None) -> List[Crossing]:
        return [c for c in self.crossings if c.direction == "down"
                and (kind is None or c.kind == kind)]

    def up(self) -> List[Crossing]:
        return [c for c in self.crossings if c.direction == "up"]


# ------------------------------------------------------------ taint pass --

def _is_jaxpr(x: Any) -> bool:
    return isinstance(x, (jax_core.Jaxpr, jax_core.ClosedJaxpr))


def _as_open(j: Any) -> Tuple[jax_core.Jaxpr, int]:
    """(open jaxpr, number of consts) for either representation."""
    if isinstance(j, jax_core.ClosedJaxpr):
        return j.jaxpr, len(j.consts)
    return j, 0


class _Analyzer:
    """Forward taint propagation; one instance per top-level analysis."""

    def __init__(self) -> None:
        self.crossings: List[Crossing] = []
        self.n_dp_eqns = 0

    # -- var environment helpers ------------------------------------------
    @staticmethod
    def _read(env: Dict[Any, Taint], atom: Any) -> Taint:
        if isinstance(atom, jax_core.Literal):
            return _EMPTY
        return env.get(atom, _EMPTY)

    def run(self, jaxpr: jax_core.Jaxpr, in_taints: Sequence[Taint],
            record: bool = True) -> List[Taint]:
        """Propagate taint through ``jaxpr``; returns outvar taints.

        ``record=False`` runs a taint-only pass (used for loop fixpoint
        iterations so crossings are recorded exactly once)."""
        env: Dict[Any, Taint] = {}
        for v in jaxpr.constvars:
            env[v] = _EMPTY
        if len(jaxpr.invars) != len(in_taints):
            raise ValueError(
                f"jaxpr has {len(jaxpr.invars)} inputs, got "
                f"{len(in_taints)} taints")
        for v, t in zip(jaxpr.invars, in_taints):
            env[v] = t

        for eqn in jaxpr.eqns:
            self._eqn(env, eqn, record)

        return [self._read(env, v) for v in jaxpr.outvars]

    # -- one equation ------------------------------------------------------
    def _eqn(self, env: Dict[Any, Taint], eqn: Any, record: bool) -> None:
        name = eqn.primitive.name
        ins = [self._read(env, a) for a in eqn.invars]
        joined: Taint = frozenset().union(*ins) if ins else _EMPTY

        if name == "vfl_wire_boundary":
            if record:
                aval = eqn.invars[0].aval
                self.crossings.append(Crossing(
                    kind=eqn.params["kind"],
                    direction=eqn.params["direction"],
                    shape=tuple(int(d) for d in aval.shape),
                    dtype=str(aval.dtype),
                    taint=ins[0]))
            # the crossing IS the sanctioned release: taint is laundered
            env[eqn.outvars[0]] = _EMPTY
            return
        if name == "vfl_dp_noise":
            if record:
                self.n_dp_eqns += 1
            env[eqn.outvars[0]] = frozenset({DP})
            return
        if name == "vfl_grad_mark":
            env[eqn.outvars[0]] = ins[0] | frozenset({GRAD, SERVER})
            return

        handler = getattr(self, f"_h_{name}", None)
        if handler is not None:
            outs = handler(eqn, ins, record)
        else:
            outs = self._generic(eqn, ins, joined, record)
        for v, t in zip(eqn.outvars, outs):
            env[v] = t

    # -- structured higher-order primitives --------------------------------
    def _h_jit(self, eqn: Any, ins: List[Taint],
               record: bool) -> List[Taint]:
        inner, _ = _as_open(eqn.params["jaxpr"])
        return self.run(inner, ins, record)

    def _h_closed_call(self, eqn: Any, ins: List[Taint],
                       record: bool) -> List[Taint]:
        inner, _ = _as_open(eqn.params["call_jaxpr"])
        return self.run(inner, ins, record)

    def _h_remat2(self, eqn: Any, ins: List[Taint],
                  record: bool) -> List[Taint]:
        inner, _ = _as_open(eqn.params["jaxpr"])
        return self.run(inner, ins, record)

    def _h_custom_jvp_call(self, eqn: Any, ins: List[Taint],
                           record: bool) -> List[Taint]:
        inner, _ = _as_open(eqn.params["call_jaxpr"])
        if len(inner.invars) == len(ins):
            return self.run(inner, ins, record)
        return self._generic(eqn, ins, frozenset().union(*ins) if ins
                             else _EMPTY, record)

    def _h_custom_vjp_call(self, eqn: Any, ins: List[Taint],
                           record: bool) -> List[Taint]:
        return self._h_custom_jvp_call(eqn, ins, record)

    def _h_custom_vjp_call_jaxpr(self, eqn: Any, ins: List[Taint],
                                 record: bool) -> List[Taint]:
        inner, _ = _as_open(eqn.params["fun_jaxpr"])
        if len(inner.invars) == len(ins):
            return self.run(inner, ins, record)
        return self._generic(eqn, ins, frozenset().union(*ins) if ins
                             else _EMPTY, record)

    def _h_shard_map(self, eqn: Any, ins: List[Taint],
                     record: bool) -> List[Taint]:
        # per-shard body, invars 1:1; collectives inside are ordinary
        # elementwise-joining equations for taint purposes
        inner, _ = _as_open(eqn.params["jaxpr"])
        return self.run(inner, ins, record)

    def _h_scan(self, eqn: Any, ins: List[Taint],
                record: bool) -> List[Taint]:
        inner, _ = _as_open(eqn.params["jaxpr"])
        n_const = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        consts = list(ins[:n_const])
        carry = list(ins[n_const:n_const + n_carry])
        xs = list(ins[n_const + n_carry:])
        # fixed point over the carried taints (lattice is finite)
        while True:
            outs = self.run(inner, consts + carry + xs, record=False)
            new_carry = [carry[i] | outs[i] for i in range(n_carry)]
            if new_carry == carry:
                break
            carry = new_carry
        outs = self.run(inner, consts + carry + xs, record=record)
        return [carry[i] | outs[i] for i in range(n_carry)] + outs[n_carry:]

    def _h_while(self, eqn: Any, ins: List[Taint],
                 record: bool) -> List[Taint]:
        cond_j, _ = _as_open(eqn.params["cond_jaxpr"])
        body_j, _ = _as_open(eqn.params["body_jaxpr"])
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_c = list(ins[:cn])
        body_c = list(ins[cn:cn + bn])
        carry = list(ins[cn + bn:])
        while True:
            outs = self.run(body_j, body_c + carry, record=False)
            new_carry = [carry[i] | outs[i] for i in range(len(carry))]
            if new_carry == carry:
                break
            carry = new_carry
        self.run(body_j, body_c + carry, record=record)
        # control dependence: the loop predicate gates every output
        pred = self.run(cond_j, cond_c + carry, record=record)
        pred_t = pred[0] if pred else _EMPTY
        return [c | pred_t for c in carry]

    def _h_cond(self, eqn: Any, ins: List[Taint],
                record: bool) -> List[Taint]:
        pred_t = ins[0]
        ops = ins[1:]
        branch_outs = []
        for br in eqn.params["branches"]:
            inner, _ = _as_open(br)
            branch_outs.append(self.run(inner, ops, record))
        n_out = len(eqn.outvars)
        outs = []
        for i in range(n_out):
            t: Taint = pred_t
            for bo in branch_outs:
                t = t | bo[i]
            outs.append(t)
        return outs

    # -- fallback ----------------------------------------------------------
    def _generic(self, eqn: Any, ins: List[Taint], joined: Taint,
                 record: bool) -> List[Taint]:
        """Unknown primitive: all inputs flow to all outputs (sound). If
        it carries sub-jaxprs we still walk them — with every inner input
        given the joined outer taint — so crossings inside are seen."""
        sub = []
        for v in eqn.params.values():
            if _is_jaxpr(v):
                sub.append(v)
            elif isinstance(v, (tuple, list)):
                sub.extend(x for x in v if _is_jaxpr(x))
        out_t = joined
        for j in sub:
            inner, _ = _as_open(j)
            inner_outs = self.run(inner, [joined] * len(inner.invars),
                                  record)
            for t in inner_outs:
                out_t = out_t | t
        return [out_t] * len(eqn.outvars)


# ----------------------------------------------------------- entry points --

def analyze(closed: jax_core.ClosedJaxpr,
            in_taints: Sequence[Taint]) -> IFCReport:
    """Run the taint pass over a ClosedJaxpr with labelled inputs."""
    a = _Analyzer()
    outs = a.run(closed.jaxpr, list(in_taints), record=True)
    return IFCReport(out_taints=outs, crossings=a.crossings,
                     n_dp_eqns=a.n_dp_eqns)


def label_args(example_args: Sequence[Any],
               is_server: Optional[Callable[[str], bool]] = None
               ) -> List[Taint]:
    """Per-flat-leaf taints for ``example_args``, matching the invar
    order of ``jax.make_jaxpr(fn)(*example_args)``. A leaf whose pytree
    key-path contains ``server`` (default predicate) seeds SERVER."""
    pred = is_server if is_server is not None else (
        lambda p: "server" in p.lower())
    leaves = jax.tree_util.tree_flatten_with_path(tuple(example_args))[0]
    out = []
    for path, _leaf in leaves:
        p = jax.tree_util.keystr(path)
        out.append(frozenset({SERVER}) if pred(p) else _EMPTY)
    return out


def trace_and_analyze(fn: Callable[..., Any], example_args: Sequence[Any],
                      is_server: Optional[Callable[[str], bool]] = None
                      ) -> IFCReport:
    """``make_jaxpr`` + :func:`analyze`: certify ``fn``'s client-bound
    outputs (the closure must return ONLY client-held values)."""
    closed = jax.make_jaxpr(fn)(*example_args)
    return analyze(closed, label_args(example_args, is_server))


# ------------------------------------------------------------- the rules --

def check_flows(report: IFCReport, *, name: str, dp_configured: bool,
                down_limits: Mapping[str, int],
                path: str = "<certify>") -> List[Finding]:
    """Evaluate IF301–IF303 on one analysis report.

    ``down_limits`` maps downlink payload kinds to the maximum number of
    elements one crossing may carry per round (e.g. ``{"loss":
    (1+q)*block}``); a downlink crossing of any other kind is an IF302
    violation outright.

    Per-output precedence: an output carrying ``grad`` taint is IF301;
    one carrying only ``server`` taint is IF302 (flow bypassed the
    bottleneck) — so each seeded leak trips exactly one rule.
    """
    findings: List[Finding] = []

    grad_outs = [i for i, t in enumerate(report.out_taints) if GRAD in t]
    srv_outs = [i for i, t in enumerate(report.out_taints)
                if SERVER in t and GRAD not in t]
    if grad_outs:
        findings.append(Finding(
            "IF301", path, 0,
            f"{name}: client-bound output(s) {grad_outs} derive from "
            "server-parameter cotangents without passing the wire "
            "bottleneck (first-order gradient reaches a client)"))
    if srv_outs:
        findings.append(Finding(
            "IF302", path, 0,
            f"{name}: server->client flow bypasses the wire bottleneck "
            f"(server taint reaches client-bound output(s) {srv_outs} "
            "with no wire_boundary on the path)"))

    for c in report.down():
        limit = down_limits.get(c.kind)
        if limit is None:
            findings.append(Finding(
                "IF302", path, 0,
                f"{name}: unexpected downlink payload kind {c.kind!r} "
                f"(shape {list(c.shape)}); the protocol downlinks only "
                f"{sorted(down_limits)}"))
        elif c.size > limit:
            findings.append(Finding(
                "IF302", path, 0,
                f"{name}: downlink bottleneck is not scalar-shaped — "
                f"kind={c.kind} shape={list(c.shape)} carries {c.size} "
                f"elements > {limit} allowed ((1+q) scalars per "
                "activated client)"))

    if dp_configured:
        down_loss = report.down("loss")
        if not down_loss:
            findings.append(Finding(
                "IF303", path, 0,
                f"{name}: DP channel configured but no loss downlink "
                "crossing was traced (noise never reaches the wire)"))
        for c in down_loss:
            if DP not in c.taint or SERVER in c.taint:
                findings.append(Finding(
                    "IF303", path, 0,
                    f"{name}: DP channel configured but the downlink "
                    f"crossing is not noise-dominated (operand taint "
                    f"{sorted(c.taint)}; noise must be added BEFORE the "
                    "wire, as Transport.downlink does)"))

    return findings
