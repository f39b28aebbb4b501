"""Driver ``async_engine``: the paper's asynchronous protocol through
``Federation.run`` (one jitted ``lax.scan`` over T rounds), in chunks.

Each chunk is one call of ``fed.run`` from the previous chunk's params,
with the engine seed of that chunk drawn from the run's seed (a fresh
schedule of parties, rows and directions per chunk; the compiled scan is
the same). Set-up builds the session, the data and the weights, and
drives the first ``check_chunks`` chunks through that call; they are
what the reference (``bench/reference/tabular.py``) follows. The window
then runs further chunks until ``--seconds`` have passed.

What is compared, after the window (relative gaps; PERF.md gives the
readings behind each limit):

* ``first_loss_gap``: the first round's loss, before any update: the
  forward of clients and server at the configuration's precision;
* ``early_loss_gap``: the worst of the first ``EARLY`` rounds' losses;
* ``first_change_gap_median``: the parameters' change over the first
  chunk (the first gradients as the optimizer got them, summed), by the
  median leaf;
* ``change_gap_median``: the parameters' change over the check chunks,
  by the median leaf.

From the second round on, every number carries the client's noise: Eq. 3
scales each lane's loss difference by d/mu = 2.5e7, so float32 rounding
of the loss alone moves the client's change by 0.1-4 % over a chunk and
the later rounds' losses with it, seed by seed. So the precision below
the configuration's shows in the first round alone, and the later
numbers, by the median leaf, catch a step that drops part of its batch
or its update. The worst leaf's gaps and the chunks' mean losses are
logged beside them.
"""
from __future__ import annotations

import dataclasses
import math
import time

from bench import harness
from bench.harness import Check, log

CHECKS = ("first_loss_gap", "early_loss_gap", "first_change_gap_median",
          "change_gap_median")
EARLY = 16


@dataclasses.dataclass
class Setup:
    fed: object
    weights: harness.Weights
    x_parts: object
    y: object
    hp: dict
    T: int
    bs: int
    q: int
    check_chunks: int
    precision: str


def chunk_seed(seed: int, chunk: int) -> int:
    return harness.seed32(seed, 100 + chunk)


def build(ctx: dict) -> Setup:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import VFLConfig
    from repro.configs.paper_mlp import PaperMLPConfig
    from repro.core.adapters import tabular_adapter
    from repro.core.async_engine import EngineConfig
    from repro.data import make_classification, vertical_partition
    from repro.federation import Federation
    from repro.models import tabular
    from repro.models.common import is_spec

    wl, cfg = ctx["workload"], ctx["config"]
    mcfg = PaperMLPConfig(n_features=cfg["n_features"],
                          n_classes=cfg["n_classes"],
                          n_clients=cfg["n_clients"],
                          client_embed=cfg["client_embed"],
                          server_embed=cfg["server_embed"])
    d_client = mcfg.features_per_client * mcfg.client_embed \
        + mcfg.client_embed
    lr = float(wl["lr"])
    lr_client = lr / math.sqrt(d_client)
    vfl = VFLConfig(mu=float(wl["mu"]), zoo_dist=wl["zoo_dist"],
                    zoo_queries=int(wl["zoo_queries"]), lr_server=lr,
                    lr_client=lr_client)
    engine = EngineConfig(method="cascaded", steps=int(wl["rounds"]),
                          batch_size=int(wl["batch"]),
                          block_size=int(wl["block_size"]),
                          use_lanes=bool(wl["use_lanes"]))
    fed = Federation.build(tabular_adapter(mcfg), vfl, engine,
                           n_clients=mcfg.n_clients)
    X, y = make_classification(harness.seed32(ctx["seed"], 2),
                               int(cfg["assumed"]["rows"]),
                               mcfg.n_features, mcfg.n_classes)
    x_parts = jnp.asarray(vertical_partition(X, mcfg.n_clients))
    weights = harness.Weights(tabular.param_specs(mcfg), cfg["init"],
                              is_spec)
    hp = {"mu": np.float32(vfl.mu), "lr": np.float32(lr),
          "lr_client": np.float32(lr_client)}
    return Setup(fed, weights, x_parts, jnp.asarray(y), hp,
                 int(wl["rounds"]), int(wl["batch"]),
                 int(wl["zoo_queries"]), int(wl["check_chunks"]),
                 cfg["matmul_precision"])


def program_chunk(su: Setup, seed: int, run_fn=None):
    """``(c, params) -> (params, losses)``: chunk c through the timed call,
    ``fed.run`` at the configuration's precision."""
    import jax
    import numpy as np

    def one_chunk(c, params):
        su.fed.engine = dataclasses.replace(su.fed.engine,
                                            seed=chunk_seed(seed, c))
        with jax.profiler.TraceAnnotation("bench.dispatch"), \
                jax.default_matmul_precision(su.precision):
            res = (su.fed.run(params, su.x_parts, su.y) if run_fn is None
                   else run_fn(su.fed, params, su.x_parts, su.y))
        with jax.profiler.TraceAnnotation("bench.loss_fetch"):
            losses = np.asarray(res.losses)
        return res.params, losses
    return one_chunk


def check_chunks(su: Setup, seed: int, params, one_chunk):
    """Drive the check chunks from ``params``; the readings the program
    and the reference are compared by."""
    import numpy as np
    names = harness.leaf_names(params)
    out = {"chunk_loss": []}
    for c in range(su.check_chunks):
        params, losses = one_chunk(c, params)
        if c == 0:
            out["first_loss"] = float(losses[0])
            out["early_loss"] = [float(v) for v in losses[:EARLY]]
            out["losses0"] = [float(v) for v in losses]
            out["first_change"] = dict(zip(
                names, su.weights.change_norms(seed, params)))
        out["chunk_loss"].append(float(np.mean(losses)))
    out["change"] = dict(zip(names, su.weights.change_norms(seed, params)))
    return params, out


def run(ctx: dict) -> dict:
    import jax
    import numpy as np

    su = build(ctx)
    seed = ctx["seed"]
    one_chunk = program_chunk(su, seed, ctx.get("wrap_step"))
    params, check = check_chunks(su, seed, su.weights.all(seed), one_chunk)
    names = list(check["change"])
    jax.block_until_ready(params)
    setup_s = time.perf_counter() - ctx["t_start"]
    compiles_setup = ctx["clock"].count

    c = su.check_chunks
    win = harness.Window(ctx["seconds"])
    n_chunks, failed = 0, 0
    while True:
        params, losses = one_chunk(c, params)
        c += 1
        n_chunks += 1
        failed += int(np.sum(~np.isfinite(losses)))
        if not win.open():
            break
    window_s = win.close()
    compiles_window = ctx["clock"].count - compiles_setup

    trace = None
    if ctx["trace"]:
        from bench.trace import Tracer
        with Tracer(ctx["trace_dir"]) as tr:
            params, _ = one_chunk(c, params)
        trace = tr.result

    rounds = n_chunks * su.T
    log(f"window: {n_chunks} chunks of {su.T} rounds in {window_s!r} s; "
        f"compilations in the window: {compiles_window}; last chunk's "
        f"mean loss {float(np.mean(losses))!r}")
    mem = harness.memory_peak_bytes(ctx["chips"])
    del params

    r = compare(ctx, su, check, names)
    limits = ctx["workload"]["limits"]
    checks = [Check(k, r[k], math.inf if limits[k] is None else limits[k])
              for k in CHECKS]
    return {
        "attempted": rounds, "failed": failed,
        "e2e": {"tabular_rounds_per_s": rounds / window_s,
                "setup_s": setup_s},
        "memory_peak_bytes": mem,
        "window_s": window_s,
        "compiles_window": compiles_window,
        "trace": trace,
        "checks": checks,
        "counters": {"rounds": rounds, "chunks": n_chunks},
        "flops_args": {"batch": su.bs, "q": su.q},
    }


def reference_run(ctx: dict, su: Setup, *, dtype="float32",
                  dots="f32") -> dict:
    import jax
    import numpy as np

    from bench.reference import tabular as ref

    seed = ctx["seed"]

    def one_chunk(c, params):
        params, losses = ref.run_chunk(
            params, su.x_parts, su.y, jax.random.key(chunk_seed(seed, c)),
            su.hp, T=su.T, bs=su.bs, q=su.q, dtype=dtype, dots=dots)
        return params, np.asarray(losses)

    with jax.default_matmul_precision("highest"):
        return check_chunks(su, seed, su.weights.all(seed), one_chunk)[1]


def readings(prog: dict, ref_out: dict) -> dict:
    names = list(ref_out["change"])
    return {
        "first_loss_gap": harness.rel_gap(prog["first_loss"],
                                          ref_out["first_loss"]),
        "early_loss_gap": max(harness.rel_gap(a, b) for a, b in zip(
            prog["early_loss"], ref_out["early_loss"])),
        "first_change_gap_median": harness.median_leaf_gap(
            prog["first_change"], ref_out["first_change"], names),
        "change_gap_median": harness.median_leaf_gap(
            prog["change"], ref_out["change"], names),
        "chunk_loss_gap": max(harness.rel_gap(a, b) for a, b in zip(
            prog["chunk_loss"], ref_out["chunk_loss"])),
        "first_change_gap": harness.leaf_gap(
            prog["first_change"], ref_out["first_change"], names),
        "change_gap": harness.leaf_gap(prog["change"], ref_out["change"],
                                       names),
        # for reading limits: the worst loss gap over the first k rounds,
        # and each leaf's gap, after the first chunk and after all
        "loss_gap_upto": {k: max(harness.rel_gap(a, b) for a, b in zip(
            prog["losses0"][:k], ref_out["losses0"][:k]))
            for k in (1, 2, 4, 8, 16, 64, 256, 2000)},
        "leaf_first_change_gap": harness.leaf_gaps(
            prog["first_change"], ref_out["first_change"], names),
        "leaf_change_gap": harness.leaf_gaps(prog["change"],
                                             ref_out["change"], names),
    }


def compare(ctx: dict, su: Setup, check: dict, names) -> dict:
    import jax
    jax.clear_caches()
    ref_out = reference_run(ctx, su)
    r = readings(check, ref_out)
    log(f"reference first loss {ref_out['first_loss']!r} chunk losses "
        f"{ref_out['chunk_loss']!r}; program {check['first_loss']!r} "
        f"{check['chunk_loss']!r}; worst leaf's change gap: first chunk "
        f"{r['first_change_gap']!r}, all {r['change_gap']!r}")
    for k in names:
        log(f"leaf {k}: first chunk's change program "
            f"{check['first_change'][k]!r} reference "
            f"{ref_out['first_change'][k]!r}; all: program "
            f"{check['change'][k]!r} reference {ref_out['change'][k]!r}")
    return r


def check_readings(ctx: dict, variant: str) -> dict:
    """The compared numbers alone, with no window: ``program`` is the
    timed call against the reference; ``control`` one precision below the
    configuration's: for float32 at ``highest``, the program with its own
    precision set to ``high`` (three bf16 passes) where the backend has
    it, and on the CPU, which computes float32 whatever the precision,
    the reference with the TPU's ``high`` written out in the program's
    place; for other float32, the reference in bfloat16 throughout;
    ``half_batch`` the program drawing half the batch (the mean taken
    over the rest)."""
    import jax

    su = build(ctx)
    seed = ctx["seed"]
    if variant == "control" and su.precision == "highest":
        if jax.default_backend() == "cpu":
            prog = reference_run(ctx, su, dots="bf16x3")
        else:
            su.precision = "high"
            variant = "program"
    elif variant == "control":
        prog = reference_run(ctx, su, dtype="bfloat16")
    if variant in ("program", "half_batch"):
        if variant == "half_batch":
            su.fed.engine = dataclasses.replace(su.fed.engine,
                                                batch_size=su.bs // 2)
        prog = check_chunks(su, seed, su.weights.all(seed),
                            program_chunk(su, seed))[1]
    elif variant != "control":
        raise ValueError(variant)
    jax.clear_caches()
    return readings(prog, reference_run(ctx, su))
