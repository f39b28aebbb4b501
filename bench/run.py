"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/``) and its driver (``bench/drivers/``). The run loads,
warms up every shape it will use (set-up), measures for ``--seconds``,
then checks what the timed path produced against a plain reference
(``bench/reference/``). With ``--trace 1`` it also profiles a few steps
after the window and reports the cell's per-layer metrics
(``bench/metrics/<metric>.py``) instead of its end-to-end ones.

The last line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
last ``checks``: each number compared, with its limit. The same checks
are the last lines on stderr. Without a TPU whose kind is in
``bench/peaks.json``, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402
from bench.harness import BenchError, log  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             device: dict = None, overrides: dict = None,
             wrap_step=None, t_start: float = None) -> dict:
    """Drive one cell and build its result. ``device`` is the checked
    device (``harness.device_info``); tests pass their own together with
    ``overrides`` that shrink the sizes and ``wrap_step`` that breaks the
    timed path underneath."""
    spec = harness.benchmark_spec()
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise BenchError(f"{workload!r} is not a workload of BENCHMARK.json")
    wl = harness.load_workload(workload)
    cfg = harness.load_config(wl["config"])
    harness.apply_overrides(wl, cfg, overrides)
    driver = harness.load_module("drivers", wl["driver"])
    with harness.CompileClock() as clock, \
            tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        ctx = {"workload": wl, "config": cfg, "seed": seed,
               "seconds": seconds, "trace": trace, "chips": entry["chips"],
               "clock": clock, "trace_dir": tdir, "wrap_step": wrap_step,
               "t_start": T_START if t_start is None else t_start}
        out = driver.run(ctx)
    return result_line(spec, workload, wl, cfg, out, trace, device)


def is_correct(checks, failed: int) -> bool:
    """A run is correct when every compared number is within its limit
    and no request, step or round failed."""
    return all(c.ok for c in checks) and failed == 0


def result_line(spec, workload, wl, cfg, out, trace, device) -> dict:
    checks = out["checks"]
    correct = is_correct(checks, out["failed"])
    dev = dict(device)
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    res = {"correct": correct, "attempted": out["attempted"],
           "failed": out["failed"]}
    if not trace:
        metrics = {}
        for m in harness.metrics_for(spec, workload, "end_to_end"):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    else:
        metrics = per_layer(spec, workload, wl, cfg, out, device)
        tr = out["trace"] or {}
        dev["busy_s"] = tr.get("busy_s")
        dev["window_s"] = tr.get("window_s")
        if tr:
            res["breakdown"] = {"device_ops": tr["device_ops"],
                                "idle_gaps": tr["idle_gaps"]}
    res["metrics"] = metrics
    res["device"] = dev
    res["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return res


def per_layer(spec, workload, wl, cfg, out, device) -> dict:
    rec = {"workload": wl, "config": cfg, "out": out,
           "peak": harness.peaks()[device["kind"]],
           "flops": harness.load_module("flops", wl["config"])}
    metrics = {}
    for m in harness.metrics_for(spec, workload, "per_layer"):
        value = harness.load_module("metrics", m["name"]).compute(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.benchmark_spec()
        entry = next((w for w in spec["workloads"]
                      if w["name"] == args.workload), None)
        if entry is None:
            raise BenchError(f"unknown workload {args.workload!r}")
        device = harness.device_info(entry["chips"])
        log(f"device: {json.dumps(device)}")
        log(f"compile cache: {harness.enable_compile_cache()}")
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), device=device)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    for name, c in res["checks"].items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if ok else 'FAIL'}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
