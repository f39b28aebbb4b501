"""Driver ``train_sync``: the cascaded step of ``Federation.sync_step``.

Set-up builds the session, the jitted step (``donate_argnums=(0, 1)``, as
``launch/train.py`` jits it), the weights from the seed and the optimizer
state, then drives that same step through its first ``check_steps``
steps on batches from ``repro.data.lm_token_batches``. Those steps are
the ones the reference follows; the same step, params and state then go
on into the window. The loop is ``train()``'s: one loss fetch a step.

What is compared, after the window has closed and the program's state
is freed (each a relative gap; PERF.md gives the limits' readings):

* ``loss_gap``: the worst of the check steps' clean losses;
* ``grad_gap``: the first gradient as the optimizer got it,
  (p0 - p1) / lr, by the worst leaf;
* ``change_gap``: the parameters' change after the check steps,
  p3 - p0, by the worst leaf.
"""
from __future__ import annotations

import dataclasses
import math
import time

from bench import harness
from bench.harness import Check, log

CHECKS = ("loss_gap", "grad_gap", "change_gap")


@dataclasses.dataclass
class Plan:
    batch: int
    seq: int
    q: int
    mu: float
    lr: float
    check_steps: int


def plan_of(wl: dict) -> Plan:
    return Plan(batch=wl["batch"], seq=wl["seq"], q=wl["zoo_queries"],
                mu=float(wl["mu"]), lr=float(wl["lr"]),
                check_steps=int(wl["check_steps"]))


@dataclasses.dataclass
class Setup:
    fed: object
    opt: object
    step_fn: object
    weights: harness.Weights
    key: object
    hp: dict
    plan: Plan
    vocab: int


def build(ctx: dict) -> Setup:
    """The program's session, step and optimizer, and the weight maker."""
    import jax
    import numpy as np
    from repro.configs import VFLConfig
    from repro.core.async_engine import EngineConfig
    from repro.federation import Federation
    from repro.launch.train import _normalized_lr_client
    from repro.models.common import is_spec
    from repro.optim import make_schedule, sgd

    wl, cfg = ctx["workload"], ctx["config"]
    pl = plan_of(wl)
    mcfg = harness.load_arch(cfg).model_config(cfg)
    vfl = VFLConfig(mu=pl.mu, lr_server=pl.lr, lr_client=pl.lr,
                    zoo_queries=pl.q, zoo_dist=wl["zoo_dist"])
    fed = Federation.build(mcfg, vfl,
                           EngineConfig(method="cascaded", steps=1,
                                        batch_size=pl.batch),
                           n_clients=wl["n_clients"], seq_len=pl.seq)
    lr_client = _normalized_lr_client(fed, pl.lr)
    fed.vfl = dataclasses.replace(vfl, lr_client=lr_client)
    opt = sgd(make_schedule("constant", pl.lr, total_steps=1))
    step_fn = fed.sync_step(opt)
    if ctx.get("wrap_step") is not None:
        step_fn = ctx["wrap_step"](step_fn)
    weights = harness.Weights(fed.model.param_specs, cfg["init"], is_spec,
                              zero_rows={"lm_head/table": mcfg.vocab_size})
    key = jax.random.key(harness.seed32(ctx["seed"], 3))
    hp = {"mu": np.float32(pl.mu), "lr": np.float32(pl.lr),
          "lr_client": np.float32(lr_client)}
    return Setup(fed, opt, step_fn, weights, key, hp, pl, mcfg.vocab_size)


def batches(seed: int, su: Setup):
    from repro.data import lm_token_batches
    return lm_token_batches(harness.seed32(seed, 2), su.vocab,
                            su.plan.batch, su.plan.seq)


def program_step(su: Setup, seed: int, diag: dict = None):
    """``(i, params, opt_state) -> (params, opt_state, loss, tokens)``: step
    i through the timed call, jitted as ``launch/train.py`` jits it, on
    the feed's next batch; one loss fetch a step. ``diag`` collects the
    lane diagnostic: whether lane 1's loss equals the clean loss bitwise,
    and the client update's norm."""
    import jax
    import jax.numpy as jnp
    data = batches(seed, su)
    jit_step = jax.jit(su.step_fn, donate_argnums=(0, 1))

    def one_step(i, params, opt_state):
        nb = next(data)
        with jax.profiler.TraceAnnotation("bench.feed"):
            b = {k: jnp.asarray(v) for k, v in nb.items()}
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            params, opt_state, out = jit_step(
                params, opt_state, b, jax.random.fold_in(su.key, i))
        with jax.profiler.TraceAnnotation("bench.loss_fetch"):
            loss = float(out.loss)
        if diag is not None:
            diag["lane_equal"].append(float(out.loss_perturbed) == loss)
            diag["client_norm"].append(float(out.grad_client_norm))
        return params, opt_state, loss, nb["tokens"]
    return one_step


def program_check_steps(su: Setup, seed: int, params, opt_state, one_step):
    """Drive the step through the check steps; the readings the reference
    is compared with."""
    names = harness.leaf_names(params)
    pl = su.plan
    out = {"loss": [], "tokens": []}
    for i in range(pl.check_steps):
        params, opt_state, loss, toks = one_step(i, params, opt_state)
        out["loss"].append(loss)
        out["tokens"].append(toks)
        if i == 0:
            out["grad"] = dict(zip(names, (
                v / pl.lr for v in su.weights.change_norms(seed, params))))
    out["change"] = dict(zip(names, su.weights.change_norms(seed, params)))
    return params, opt_state, out


def run(ctx: dict) -> dict:
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh

    su = build(ctx)
    pl = su.plan
    params = su.weights.all(ctx["seed"])
    opt_state = su.opt.init(params)
    diag = {"lane_equal": [], "client_norm": []}
    one_step = program_step(su, ctx["seed"], diag)

    with make_host_mesh():
        params, opt_state, check = program_check_steps(
            su, ctx["seed"], params, opt_state, one_step)
        jax.block_until_ready(params)
        setup_s = time.perf_counter() - ctx["t_start"]
        compiles_setup = ctx["clock"].count

        step = pl.check_steps
        win = harness.Window(ctx["seconds"])
        losses = []
        while True:
            params, opt_state, loss, _ = one_step(step, params, opt_state)
            losses.append(loss)
            step += 1
            if not win.open():
                break
        window_s = win.close()
        n_win = len(losses)
        compiles_window = ctx["clock"].count - compiles_setup

        trace = None
        if ctx["trace"]:
            from bench.trace import Tracer
            with Tracer(ctx["trace_dir"]) as tr:
                for _ in range(3):
                    params, opt_state, _, _ = one_step(step, params,
                                                       opt_state)
                    step += 1
            trace = tr.result

    failed = sum(not math.isfinite(v) for v in losses)
    tokens = n_win * pl.batch * pl.seq
    share_equal = float(np.mean(diag["lane_equal"]))
    log(f"lanes: share of steps whose first perturbed lane loss equals "
        f"the clean loss bitwise: {share_equal!r} of "
        f"{len(diag['lane_equal'])}; client update norm |g_c| first "
        f"{diag['client_norm'][0]!r} last {diag['client_norm'][-1]!r}")
    log(f"window: {n_win} steps in {window_s!r} s; compilations in the "
        f"window: {compiles_window}")
    mem = harness.memory_peak_bytes(ctx["chips"])
    del params, opt_state, one_step

    readings_ = compare(ctx, su, check)
    limits = ctx["workload"]["limits"]
    checks = [Check(k, readings_[k], math.inf if limits[k] is None
                    else limits[k]) for k in CHECKS]
    return {
        "attempted": n_win, "failed": failed,
        "e2e": {"train_tokens_per_s": tokens / window_s,
                "setup_s": setup_s},
        "memory_peak_bytes": mem,
        "window_s": window_s,
        "compiles_window": compiles_window,
        "trace": trace,
        "checks": checks,
        "counters": {"tokens": tokens, "steps": n_win,
                     "lane_equal_share": share_equal},
        "flops_args": {"batch": pl.batch, "seq": pl.seq, "q": pl.q},
    }


def reference_run(ctx: dict, su: Setup, token_batches, *, mode="f32",
                  batch_rows=None) -> dict:
    """The reference's check steps from the same seed: its losses, its
    first gradient as the optimizer gets it and its change, per leaf."""
    import jax
    import jax.numpy as jnp

    from bench.reference import lm

    cfg = ctx["config"]
    pl = su.plan
    params = su.weights.all(ctx["seed"])
    names = harness.leaf_names(params)
    items = harness.load_reference(cfg).items(cfg)
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i, toks in enumerate(token_batches):
            t = jnp.asarray(toks if batch_rows is None
                            else toks[:batch_rows])
            params, h0, _ = lm.cascaded_step(
                params, t, jax.random.fold_in(su.key, i), su.hp,
                arch=harness.model_type(cfg), cfg_items=items, mode=mode,
                q=pl.q)
            out["loss"].append(float(h0))
            if i == 0:
                out["grad"] = dict(zip(names, (
                    v / pl.lr for v in su.weights.change_norms(
                        ctx["seed"], params))))
    out["change"] = dict(zip(names, su.weights.change_norms(ctx["seed"],
                                                           params)))
    return out


def readings(prog: dict, ref_out: dict) -> dict:
    counted = harness.counted_leaves(ref_out["grad"])
    return {
        "loss_gap": max(harness.rel_gap(a, b)
                        for a, b in zip(prog["loss"], ref_out["loss"])),
        "grad_gap": harness.leaf_gap(prog["grad"], ref_out["grad"],
                                     counted),
        "change_gap": harness.leaf_gap(prog["change"], ref_out["change"],
                                       counted),
        "grad_gap_median": harness.median_leaf_gap(
            prog["grad"], ref_out["grad"], counted),
        "change_gap_median": harness.median_leaf_gap(
            prog["change"], ref_out["change"], counted),
        "leaves_counted": len(counted),
    }


def compare(ctx: dict, su: Setup, check: dict) -> dict:
    import jax
    jax.clear_caches()
    ref_out = reference_run(ctx, su, check["tokens"])
    r = readings(check, ref_out)
    log(f"reference losses {ref_out['loss']!r}, program {check['loss']!r};"
        f" leaves counted {r['leaves_counted']} of {len(ref_out['grad'])}")
    for k in ref_out["grad"]:
        log(f"leaf {k}: grad program {check['grad'][k]!r} reference "
            f"{ref_out['grad'][k]!r}; change program {check['change'][k]!r}"
            f" reference {ref_out['change'][k]!r}")
    return r


def check_readings(ctx: dict, variant: str) -> dict:
    """The compared numbers alone, with no window: ``program`` is the
    timed step against the reference; ``control`` the reference in fp8
    in the program's place; ``half_batch`` the reference fed half of each
    batch (the mean over the rest) in the program's place."""
    import jax
    from repro.launch.mesh import make_host_mesh

    su = build(ctx)
    data = batches(ctx["seed"], su)
    toks = [next(data)["tokens"] for _ in range(su.plan.check_steps)]
    if variant == "program":
        params = su.weights.all(ctx["seed"])
        with make_host_mesh():
            prog = program_check_steps(
                su, ctx["seed"], params, su.opt.init(params),
                program_step(su, ctx["seed"]))[2]
        del params
    elif variant == "control":
        prog = reference_run(ctx, su, toks, mode="fp8")
    elif variant == "half_batch":
        prog = reference_run(ctx, su, toks, batch_rows=su.plan.batch // 2)
    else:
        raise ValueError(variant)
    jax.clear_caches()
    return readings(prog, reference_run(ctx, su, toks))
