"""CPU sizes of phi3-serve-decode (``bench/tests/sizing.py``)."""
from bench.tests.sizing import SERVE_CFG, TINY_SERVE_CFG

TINY = {
    "config": TINY_SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 8,
                 "gen": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                         "min": 4, "max": 24, "grid": 16},
                 "steps_per_call": 4, "check_requests": 3},
}
CONTROL = {
    "config": SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 16,
                 "gen": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                         "min": 16, "max": 96, "grid": 16},
                 "steps_per_call": 4, "check_requests": 6},
}
