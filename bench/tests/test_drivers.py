"""Each driver end to end at a small size on the CPU, past the harness's
look for a chip; and ``bench/run.py`` itself, which must refuse the CPU."""
import json
import math
import os
import subprocess
import sys
import time

import pytest

from bench import harness, run
from bench.tests.sizing import sizes

SPEC = harness.benchmark_spec()
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("traced", [False, True])
def test_driver_end_to_end(cell, traced):
    res = run.run_cell(cell, 2 ** 40 + 11, 0.5, traced, device=CPU,
                       overrides=sizes(cell).TINY, t_start=time.perf_counter())
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    for c in res["checks"].values():
        assert math.isfinite(c["value"])
    want = {m["name"] for m in harness.metrics_for(
        SPEC, cell, "per_layer" if traced else "end_to_end")}
    if not traced:
        assert set(res["metrics"]) == want
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # the CPU writes no /device:TPU plane: device-trace metrics are
        # left out, and busy/window read nothing
        assert set(res["metrics"]) <= want
        assert res["device"]["busy_s"] is None
    json.dumps(res)


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_device_kind_missing_from_peaks_is_refused(monkeypatch):
    monkeypatch.setattr(harness, "peaks", lambda: {"TPU v9": {}})

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.BenchError, match="not in"):
        harness.device_info(1)
