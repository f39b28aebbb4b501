"""CPU sizes of phi3-serve-prefill (``bench/tests/sizing.py``)."""
from bench.tests.sizing import SERVE_CFG, TINY_SERVE_CFG

TINY = {
    "config": TINY_SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 20,
                 "gen": {"dist": "uniform", "min": 8, "max": 24,
                         "grid": 9},
                 "steps_per_call": 4, "check_requests": 6},
}
CONTROL = {
    "config": SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 40,
                 "gen": {"dist": "uniform", "min": 32, "max": 128,
                         "grid": 9},
                 "steps_per_call": 4, "check_requests": 8},
}
