"""Every name in BENCHMARK.json resolves to its own file, and every such
file is shaped as the harness reads it."""
import os

import pytest

from bench import harness

SPEC = harness.benchmark_spec()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_workload_finds_config_driver_and_flops(cell):
    wl = harness.load_workload(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert wl["config"] == entry["config"]
    cfg = harness.load_config(wl["config"])
    assert "source" in cfg and "init" in cfg
    driver = harness.load_module("drivers", wl["driver"])
    assert callable(driver.run)
    assert set(wl["limits"]) == set(driver.CHECKS)
    harness.load_module("flops", wl["config"])


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    mod = harness.load_module("metrics", metric)
    assert callable(mod.compute)
    m = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    e2e = {e["name"] for e in SPEC["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m.get("workloads", []):
        assert cell in {w["name"] for w in SPEC["workloads"]}


def test_config_files_are_named_in_benchmark():
    for c in SPEC["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert c["file"] == f"bench/configs/{c['name']}.json"


def test_reader_returns_nothing_when_nothing_to_read():
    rec = {"out": {"trace": None, "e2e": {}}, "config": {}}
    for m in SPEC["per_layer"]:
        assert harness.load_module("metrics", m["name"]).compute(rec) is None


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.load_workload("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such_metric")


def test_seed_of_any_size_gives_distinct_31_bit_seeds():
    big = 2 ** 40 + 7
    a, b = harness.seed32(big), harness.seed32(big + 1)
    assert a != b and 0 <= a < 2 ** 31 and 0 <= b < 2 ** 31
    assert harness.seed32(big) == a
