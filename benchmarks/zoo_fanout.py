"""Vectorized vs unrolled ZOO query fan-out (the tentpole speed claim).

For q ∈ {1, 4, 16} and both cascade code paths —
  * ``unrolled`` — the per-query Python-loop oracle (fused_dual=False):
    q separate server passes, trace size and dispatch linear in q
  * ``stacked``  — the vectorized lane path (fused_dual=True): ALL q
    directions drawn as stacked leaves, ONE vmapped server pass over
    the perturbed lanes beside the clean lane's forward/backward
— this records the one-time compile wall clock and the steady-state
per-round wall clock of the cascaded step. The acceptance claim is that
the stacked path's per-round time grows SUBLINEARLY in q (the unrolled
path is the linear baseline, and its compile time grows with q too).

Run: PYTHONPATH=src python -m benchmarks.zoo_fanout [--full]
(also exposed as ``--only zoo_fanout`` in benchmarks.run)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import VFLConfig
from repro.core import cascade
from repro.optim import sgd

QS = (1, 4, 16)


def _toy(vocab: int = 512, d: int = 64, classes: int = 32,
         batch: int = 64, seed: int = 0):
    """Embedding-client / linear-head-server split LM at bench scale."""
    key = jax.random.key(seed)
    params = {
        "embed": {"w": jax.random.normal(key, (vocab, d), jnp.float32) * 0.1},
        "head": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                        (d, classes), jnp.float32) * 0.1},
    }
    x = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0, vocab)
    y = jax.random.randint(jax.random.fold_in(key, 3), (batch,), 0, classes)

    def loss_fn(p, b):
        h = jnp.take(p["embed"]["w"], b["x"], axis=0)
        logits = h @ p["head"]["w"]
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, b["y"][:, None], -1)[:, 0]
        return jnp.mean(lse - gold), {}

    return params, {"x": x, "y": y}, loss_fn


def bench_zoo_fanout(fast: bool = True, row=None, qs=QS):
    """Emit name,us_per_call,derived rows; returns {(path, q): us}."""
    if row is None:
        def row(name, us, derived):
            print(f"{name},{us:.1f},{derived}", flush=True)

    params, batch, loss_fn = _toy()
    n_rounds = 20 if fast else 100
    results = {}
    for fused in (False, True):
        label = "stacked" if fused else "unrolled"
        for q in qs:
            vfl = VFLConfig(mu=1e-3, zoo_queries=q, fused_dual=fused)
            opt = sgd(0.01)
            step = jax.jit(cascade.make_cascaded_step(
                loss_fn, ("embed",), vfl, opt))
            opt_state = opt.init(params)
            key = jax.random.key(1)

            t0 = time.perf_counter()
            p, s, out = step(params, opt_state, batch, key)
            jax.block_until_ready(out.loss)
            compile_s = time.perf_counter() - t0

            t0 = time.perf_counter()
            for i in range(n_rounds):
                p, s, out = step(p, s, batch, jax.random.fold_in(key, i))
            jax.block_until_ready(out.loss)
            us = (time.perf_counter() - t0) / n_rounds * 1e6

            results[(label, q)] = us
            row(f"zoo_fanout_{label}_q{q}", us, f"compile_s={compile_s:.2f}")

    for label in ("unrolled", "stacked"):
        lo, hi = results[(label, qs[0])], results[(label, qs[-1])]
        growth = hi / max(lo, 1e-9)
        row(f"zoo_fanout_{label}_scaling", 0.0,
            f"round_time_growth_q{qs[0]}->q{qs[-1]}={growth:.2f}x;"
            f"linear_would_be={qs[-1] / qs[0]:.0f}x;"
            f"sublinear={growth < qs[-1] / qs[0]}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", dest="fast", action="store_false", default=True)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    bench_zoo_fanout(args.fast)


if __name__ == "__main__":
    main()
