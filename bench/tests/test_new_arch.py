"""A second LM architecture enters the benchmark as new files only.

A scratch copy of the bench tree gets the fixture in ``data/new_arch``:
a dense decoder with grouped KV heads (``model_type`` ``fixture_gqa``),
its ``arch`` and ``reference`` modules, its flops, a train and a serve
workload with their CPU sizes, and entries added to BENCHMARK.json. No
file of the copy is edited but BENCHMARK.json. Both cells then run end
to end on the CPU and come out correct, through the fixture's modules.
"""
import json
import os
import shutil
import time

from bench import harness, run
from bench.tests.sizing import sizes

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "new_arch")
ENTRIES = "benchmark_entries.json"
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def add_fixture(root) -> dict:
    """The fixture's files into ``root``'s bench tree, each new there,
    and its entries into ``root``'s BENCHMARK.json."""
    bench = os.path.join(root, "bench")
    for dirpath, _, files in os.walk(FIXTURE):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), FIXTURE)
            if rel == ENTRIES:
                continue
            dst = os.path.join(bench, rel)
            assert not os.path.exists(dst), dst
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copyfile(os.path.join(FIXTURE, rel), dst)
    entries = harness.load_json(os.path.join(FIXTURE, ENTRIES))
    path = os.path.join(root, "BENCHMARK.json")
    spec = harness.load_json(path)
    spec["configs"] += entries["configs"]
    spec["workloads"] += entries["workloads"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.setdefault("workloads", [])
        m["workloads"] += entries["metrics"].get(m["name"], [])
        if not m["workloads"]:
            del m["workloads"]
    with open(path, "w") as f:
        json.dump(spec, f)
    return entries


def spy(monkeypatch, mod, fn: str, calls: list) -> None:
    real = getattr(mod, fn)

    def called(*args, **kwargs):
        calls.append(f"{mod.__file__}:{fn}")
        return real(*args, **kwargs)
    monkeypatch.setattr(mod, fn, called)


def test_second_architecture_as_new_files_only(tmp_path, monkeypatch):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copyfile(os.path.join(harness.ROOT, "BENCHMARK.json"),
                    tmp_path / "BENCHMARK.json")
    entries = add_fixture(str(tmp_path))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path / "bench"))

    calls = []
    arch = harness.load_module("arch", "fixture_gqa")
    ref = harness.load_module("reference", "fixture_gqa")
    for mod, fn in ((arch, "model_config"), (ref, "items"),
                    (ref, "hidden")):
        spy(monkeypatch, mod, fn, calls)

    for w in entries["workloads"]:
        cell = w["name"]
        del calls[:]
        res = run.run_cell(cell, 2 ** 40 + 11, 0.5, True, device=CPU,
                           overrides=sizes(cell).TINY,
                           t_start=time.perf_counter())
        assert res["correct"] is True, res["checks"]
        assert res["attempted"] > 0 and res["failed"] == 0
        driver = harness.load_workload(cell)["driver"]
        mfu = {"train_sync": "train_mfu", "serve_closed": "serve_mfu"}
        assert res["metrics"][mfu[driver]]["value"] > 0
        assert {c.rsplit(":", 1)[1] for c in calls} == {
            "model_config", "items", "hidden"}, calls
        assert all(c.startswith(str(tmp_path)) for c in calls), calls
