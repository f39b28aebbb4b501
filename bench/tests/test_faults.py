"""A run with the timed path broken underneath must come out not correct:
once for each fault the cell can have. Training cells can return their
state unchanged, or leave out half of each batch and take the mean over
the rest; serving cells can alter a token where it is produced. No cell
has an exchange between chips (one chip each)."""
import dataclasses
import time

import pytest

from bench import run
from bench.tests.sizes import TINY

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def unchanged_step(step_fn):
    def step(params, opt_state, batch, key):
        _, _, out = step_fn(params, opt_state, batch, key)
        return params, opt_state, out
    return step


def half_batch_step(step_fn):
    def step(params, opt_state, batch, key):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step_fn(params, opt_state, half, key)
    return step


def unchanged_run(fed, params, x_parts, y):
    return dataclasses.replace(fed.run(params, x_parts, y), params=params)


def half_batch_run(fed, params, x_parts, y):
    engine = fed.engine
    fed.engine = dataclasses.replace(engine,
                                     batch_size=engine.batch_size // 2)
    try:
        return fed.run(params, x_parts, y)
    finally:
        fed.engine = engine


def altered_token(srv):
    run_call = srv.run

    def run_altered(*args, **kwargs):
        results = run_call(*args, **kwargs)
        for r in results:
            r.tokens = r.tokens.copy()
            r.tokens[len(r.tokens) // 2] = (r.tokens[len(r.tokens) // 2]
                                            + 1) % srv.vocab_size
        return results
    srv.run = run_altered
    return srv


FAULTS = [("phi3-serve-decode", altered_token),
          ("phi3-serve-prefill", altered_token),
          ("phi3-train-q4", unchanged_step), ("phi3-train-q4",
                                              half_batch_step),
          ("mlp-async", unchanged_run), ("mlp-async", half_batch_run)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = run.run_cell(cell, 31, 0.3, False, device=CPU,
                       overrides=TINY[cell], wrap_step=fault,
                       t_start=time.perf_counter())
    assert res["correct"] is False, res["checks"]
