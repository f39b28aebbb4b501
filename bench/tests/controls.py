"""The readings that a cell's limits are set from, for a cell at its own
size on the chip, or at a small size on the CPU (``test_controls.py``).

    python bench/tests/controls.py --workload <cell> --seeds 1,2,... \
        --variants program,control,half_batch [--out readings.jsonl]

For each seed and variant it prints one JSON line with the compared
numbers: ``program`` is the timed path against the plain reference (the
lower readings), ``control`` the reference one precision below the
configuration's in the program's place, ``half_batch`` a fault: half of
each batch left out and the mean taken over the rest. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def readings(cell: str, seed: int, variant: str,
             overrides: dict = None, seconds: float = 8.0) -> dict:
    wl = harness.load_workload(cell)
    cfg = harness.load_config(wl["config"])
    harness.apply_overrides(wl, cfg, overrides)
    driver = harness.load_module("drivers", wl["driver"])
    with harness.CompileClock() as clock:
        ctx = {"workload": wl, "config": cfg, "seed": seed,
               "seconds": seconds, "trace": False, "chips": 1,
               "clock": clock, "t_start": time.perf_counter(),
               "trace_dir": None}
        return driver.check_readings(ctx, variant)


def checks(cell: str, found: dict) -> list:
    """``found``'s compared numbers, each with the cell's limit."""
    wl = harness.load_workload(cell)
    driver = harness.load_module("drivers", wl["driver"])
    limits = wl["limits"]
    return [harness.Check(k, found[k], math.inf if limits[k] is None
                          else limits[k]) for k in driver.CHECKS]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control,half_batch")
    ap.add_argument("--out", default="")
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="window of the cells whose check needs one")
    ap.add_argument("--overrides", default="{}",
                    help='JSON {"config": {...}, "workload": {...}} laid '
                         "over the cell's files (a witness run)")
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    overrides = json.loads(args.overrides)
    out = open(args.out, "a") if args.out else None
    for variant in args.variants.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            r = readings(args.workload, seed, variant, overrides,
                         seconds=args.seconds)
            line = json.dumps({"workload": args.workload, "variant": variant,
                               "seed": seed, "overrides": overrides,
                               "seconds": time.perf_counter() - t0, **r})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
