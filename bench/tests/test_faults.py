"""A run with the timed path broken underneath must come out not correct:
once for each fault the cell can have. Training cells can return their
state unchanged, or leave out half of each batch and take the mean over
the rest; serving cells can alter a token where it is produced. No cell
has an exchange between chips (one chip each). The faults are listed by
driver, so that a new cell on a driver gets its driver's faults."""
import dataclasses
import time

import pytest

from bench import harness, run
from bench.tests.sizing import sizes

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


FAULTS = {"serve_closed": [altered_token],
          "train_sync": [unchanged_step, half_batch_step],
          "async_engine": [unchanged_run, half_batch_run]}
CELLS = [(w["name"], harness.load_workload(w["name"])["driver"])
         for w in harness.benchmark_spec()["workloads"]]
CASES = [(cell, fault) for cell, driver in CELLS
         for fault in FAULTS.get(driver, [])]


@pytest.mark.parametrize("cell,driver", CELLS)
def test_every_driver_has_faults(cell, driver):
    assert FAULTS.get(driver), f"no faults listed for driver {driver!r}"


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = run.run_cell(cell, 31, 0.3, False, device=CPU,
                       overrides=sizes(cell).TINY, wrap_step=fault,
                       t_start=time.perf_counter())
    assert res["correct"] is False, res["checks"]
