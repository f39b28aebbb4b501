"""Driver ``serve_closed``: continuous split serving through
``Federation.serve`` under a closed loop of callers.

Set-up builds the session and the weights from the seed, then warms
every program the traffic uses through the scheduler itself: for each
wave width 1..``slots`` it submits that many requests of the cell's
prompt length at once and drains them (one prefill and one install
program per width, the retire fetch per width, and every decode block
length up to ``steps_per_call``).

The window: each of ``callers`` callers keeps one request in flight;
the loop calls ``run(max_steps=steps_per_call)`` and, after each return,
every caller whose request retired submits its next one. Output lengths
come from a fixed grid of quantiles of the cell's distribution, the same
multiset for every seed, in an order drawn from the seed; prompts are
tokens drawn from the seed. When the window closes the loop stops
submitting and drains what is in flight.

``serve_tokens_per_s``: tokens generated by requests that retired in the
window, over the window. ``serve_latency_p95_ms``: p95 of submit to
result over every request submitted in the window (the drained ones with
their whole latency; a failed request counts as beyond any limit).

What is compared (after the window, the program's state freed): a
sample drawn from the seed of the finished requests, the longest among
them, is run once through the plain reference (``reference/lm.py`` over
the configuration's ``reference/<model_type>.py``) over prompt + served
tokens; ``logit_gap`` is the widest gap by which a served token's
reference logit lies below the reference's best at its position (greedy
decoding).
"""
from __future__ import annotations

import math
import time

from bench import harness
from bench.harness import Check, log

CHECKS = ("logit_gap",)


def out_lengths(wl: dict):
    """The fixed multiset of output lengths: ``grid`` quantiles of a
    lognormal with the cell's median and sigma, clipped."""
    from statistics import NormalDist
    g = wl["gen"]
    n = int(g["grid"])
    if g["dist"] == "lognormal":
        z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        vals = [g["median"] * math.exp(g["sigma"] * zi) for zi in z]
    elif g["dist"] == "uniform":
        vals = [g["min"] + (g["max"] - g["min"]) * (i + 0.5) / n
                for i in range(n)]
    else:
        raise ValueError(g["dist"])
    return [int(min(max(round(v), g["min"]), g["max"])) for v in vals]


class Traffic:
    """Prompts and output lengths drawn from the seed."""

    def __init__(self, wl: dict, vocab: int, seed: int) -> None:
        import numpy as np
        self.rng = np.random.default_rng(harness.seed32(seed, 4))
        self.grid = out_lengths(wl)
        self.prompt_len = int(wl["prompt_len"])
        self.vocab = vocab
        self._order = []

    def next(self):
        if not self._order:
            self._order = list(self.rng.permutation(self.grid))
        gen = int(self._order.pop())
        prompt = self.rng.integers(0, self.vocab, self.prompt_len,
                                   dtype="int32")
        return prompt, gen


def build(ctx: dict):
    from repro.configs import VFLConfig
    from repro.core.async_engine import EngineConfig
    from repro.federation import Federation
    from repro.models.common import is_spec

    wl, cfg = ctx["workload"], ctx["config"]
    mcfg = harness.load_arch(cfg).model_config(cfg)
    seq_len = int(wl["prompt_len"]) + int(wl["gen"]["max"])
    fed = Federation.build(mcfg, VFLConfig(), EngineConfig(),
                           n_clients=int(wl["n_clients"]), seq_len=seq_len)
    weights = harness.Weights(fed.model.param_specs, cfg["init"], is_spec,
                              zero_rows={"lm_head/table": mcfg.vocab_size})
    return fed, weights, mcfg, seq_len


def warm_up(srv, wl: dict, traffic: Traffic) -> None:
    """Every wave width at the cell's prompt length, each request long
    enough that its blocks run every power of two up to the call's
    budget."""
    steps = int(wl["steps_per_call"])
    gen = min(2 * steps - 1, int(wl["gen"]["max"]))
    for w in range(1, int(wl["slots"]) + 1):
        for _ in range(w):
            prompt, _ = traffic.next()
            srv.submit(prompt, gen)
        while srv.pending or srv.active:
            srv.run(max_steps=steps)


def run(ctx: dict) -> dict:
    import jax
    import numpy as np

    wl = ctx["workload"]
    fed, weights, mcfg, seq_len = build(ctx)
    params = weights.all(ctx["seed"])
    srv = fed.serve(params, max_batch=int(wl["slots"]), temperature=0.0)
    if ctx.get("wrap_step") is not None:
        srv = ctx["wrap_step"](srv)
    del params
    traffic = Traffic(wl, mcfg.vocab_size, ctx["seed"])
    warm_up(srv, wl, traffic)
    jax.block_until_ready(srv._gen_buf_st)
    setup_s = time.perf_counter() - ctx["t_start"]
    compiles_setup = ctx["clock"].count
    steps = int(wl["steps_per_call"])

    submitted = {}          # rid -> (submit time, prompt, gen)
    done = {}               # rid -> (latency s, tokens, status, finish t)
    win = harness.Window(ctx["seconds"])
    steps0, gen0 = srv.steps, srv.generated_tokens

    def submit():
        prompt, gen = traffic.next()
        rid = srv.submit(prompt, gen)
        submitted[rid] = (time.perf_counter(), prompt, gen)

    def serve_call(resubmit: bool) -> None:
        """One ``run`` call; each caller whose request retired sends its
        next one when ``resubmit``."""
        with jax.profiler.TraceAnnotation("bench.run_call"):
            results = srv.run(max_steps=steps)
        now = time.perf_counter()
        for r in results:
            t_sub = submitted[r.rid][0]
            done[r.rid] = (now - t_sub, np.asarray(r.tokens), r.status, now)
            if resubmit:
                submit()

    for _ in range(int(wl["callers"])):
        submit()
    while win.open():
        serve_call(True)
    window_s = win.close()
    win_end = win.t0 + window_s
    steps_win = srv.steps - steps0
    gen_win = srv.generated_tokens - gen0

    trace = None
    if ctx["trace"]:
        # a stretch of the same closed loop, callers still submitting
        from bench.trace import Tracer
        with Tracer(ctx["trace_dir"]) as tr:
            for _ in range(int(wl["trace_calls"])):
                serve_call(True)
        trace = tr.result
    while srv.pending or srv.active:
        serve_call(False)
    compiles_window = ctx["clock"].count - compiles_setup

    in_window = [rid for rid, (t, _, _) in submitted.items()
                 if t < win_end]
    lat = [done[r][0] if done[r][2] == "ok" else math.inf
           for r in in_window]
    failed = sum(done[r][2] != "ok" for r in in_window)
    retired = [r for r in in_window if done[r][3] <= win_end]
    tokens = sum(len(done[r][1]) for r in retired)
    prompt_len = int(wl["prompt_len"])
    flops_reqs = [(prompt_len, len(done[r][1])) for r in retired]
    log(f"window: {len(in_window)} requests submitted, {len(retired)} "
        f"retired in {window_s!r} s, {tokens} tokens; scheduler steps "
        f"{steps_win}, tokens counted by the scheduler {gen_win}; "
        f"compilations in the window: {compiles_window}")
    mem = harness.memory_peak_bytes(ctx["chips"])
    del srv

    seqs = sample(ctx, submitted, done, in_window)
    r = compare(ctx, weights, seqs, prompt_len, seq_len)
    limits = wl["limits"]
    checks = [Check(k, r[k], math.inf if limits[k] is None else limits[k])
              for k in CHECKS]
    return {
        "attempted": len(in_window), "failed": failed,
        "e2e": {"serve_tokens_per_s": tokens / window_s,
                "serve_latency_p95_ms": 1e3 * float(
                    np.percentile(lat, 95)),
                "setup_s": setup_s},
        "memory_peak_bytes": mem,
        "window_s": window_s,
        "compiles_window": compiles_window,
        "trace": trace,
        "checks": checks,
        "counters": {"steps": steps_win, "generated_tokens": gen_win,
                     "slots": int(wl["slots"]), "requests": len(retired)},
        "flops_args": {"requests": flops_reqs},
        "seqs": seqs,
    }


def sample(ctx, submitted, done, rids):
    """The longest finished request and others drawn from the seed, as
    (prompt, served tokens) pairs: ``check_requests`` in all."""
    import numpy as np
    n = int(ctx["workload"]["check_requests"])
    ok = [r for r in rids if done[r][2] == "ok"]
    longest = max(ok, key=lambda r: (len(done[r][1]), -r))
    rest = [r for r in ok if r != longest]
    rng = np.random.default_rng(harness.seed32(ctx["seed"], 5))
    pick = [longest] + list(rng.choice(rest, min(n - 1, len(rest)),
                                       replace=False))
    return [(submitted[r][1], done[r][1]) for r in pick]


def reference_logits(ctx, weights, seqs, seq_len, mode="f32"):
    """The reference's logits over each prompt + served tokens, padded
    to ``seq_len`` (causal: the padding never reaches a served position).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.reference import lm

    arch = harness.model_type(ctx["config"])
    cfg = dict(harness.load_reference(ctx["config"]).items(ctx["config"]))
    params = weights.all(ctx["seed"])
    toks = np.zeros((len(seqs), seq_len), np.int32)
    for i, (p, g) in enumerate(seqs):
        s = np.concatenate([p, g])
        toks[i, :len(s)] = s

    @jax.jit
    def fwd(params, toks):
        return lm.logits_of(arch, cfg, lm.make_dot(mode), params,
                            params["embed"]["table"], toks)

    with jax.default_matmul_precision("highest"):
        out = []
        for i in range(len(seqs)):
            out.append(np.asarray(fwd(params, jnp.asarray(toks[i:i + 1])))[0])
    return out


def gaps(ref32, seqs, prompt_len, chosen=None):
    """Per request, the widest gap below the best at each served
    position: of the served token, or of ``chosen``'s top token."""
    import numpy as np
    worst = 0.0
    for i, (p, g) in enumerate(seqs):
        lg = ref32[i][prompt_len - 1:prompt_len - 1 + len(g)]
        best = lg.max(-1)
        tok = (g if chosen is None else
               chosen[i][prompt_len - 1:prompt_len - 1 + len(g)].argmax(-1))
        worst = max(worst, float(np.max(best - lg[np.arange(len(g)), tok])))
    return worst


def compare(ctx, weights, seqs, prompt_len, seq_len) -> dict:
    import jax
    jax.clear_caches()
    ref32 = reference_logits(ctx, weights, seqs, seq_len)
    r = {"logit_gap": gaps(ref32, seqs, prompt_len),
         "tokens_compared": sum(len(g) for _, g in seqs)}
    log(f"reference: {len(seqs)} requests, {r['tokens_compared']} served "
        f"tokens compared; widest gap {r['logit_gap']!r}")
    return r


def check_readings(ctx: dict, variant: str) -> dict:
    """The compared number alone, from a window at the cell's load:
    ``program`` the served tokens against the reference; ``control`` the
    token the reference in fp8 puts first, at the same positions."""
    if variant not in ("program", "control"):
        raise ValueError(variant)
    out = run(dict(ctx, trace=False))
    if variant == "program":
        return {"logit_gap": out["checks"][0].value}
    _, weights, _, seq_len = build(ctx)
    seqs, prompt_len = out["seqs"], int(ctx["workload"]["prompt_len"])
    ref32 = reference_logits(ctx, weights, seqs, seq_len)
    low = reference_logits(ctx, weights, seqs, seq_len, mode="fp8")
    return {"logit_gap": gaps(ref32, seqs, prompt_len, chosen=low),
            "program_logit_gap": out["checks"][0].value}
