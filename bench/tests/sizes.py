"""Sizes a CPU test run can hold; the cells' own sizes are in
bench/configs and bench/workloads."""

_TINY_CFG = {"hidden_size": 64, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "intermediate_size": 128, "vocab_size": 512}
TINY = {
    # the train step in float32: the CPU rounds bf16 products unlike the
    # chip, whose fusions keep their float32 accumulations, and at this
    # size the bf16 step reads a first-gradient gap of 3e-3 to 7e-3, the
    # chip's 1.45e-4 at the cell's size; in float32 it reads 3e-5, so the
    # limits set on the chip still tell a sound step from a broken one
    "phi3-train-q4": {
        "config": {**_TINY_CFG,
                   "program": {"param_dtype": "float32",
                               "activation_dtype": "float32"}},
        "workload": {"batch": 2, "seq": 32},
    },
    "mlp-async": {
        "config": {"assumed": {"rows": 512}},
        "workload": {"rounds": 40, "batch": 32},
    },
}
# serving at hidden 64: weights ten times the published scale, so that
# the logits spread as phi3-mini's do at its width (a logit's deviation
# is ~1.1 there and 0.16 here at 0.02), and a served token altered reads
# several units below the best, as it would in the cell
_TINY_SERVE_CFG = {**_TINY_CFG, "init": {"std": 0.2}}
TINY["phi3-serve-decode"] = {
    "config": _TINY_SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 8,
                 "gen": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                         "min": 4, "max": 24, "grid": 16},
                 "steps_per_call": 4, "check_requests": 3},
}
TINY["phi3-serve-prefill"] = {
    "config": _TINY_SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 20,
                 "gen": {"dist": "uniform", "min": 8, "max": 24,
                         "grid": 9},
                 "steps_per_call": 4, "check_requests": 6},
}

# the controls' test: sizes at which the cells' limits, set from the chip
# readings at the cells' own sizes, still tell the control from the program
CONTROL = dict(TINY)
# the paper's own widths; a tenth of a chunk's rounds
CONTROL["mlp-async"] = {"workload": {"rounds": 200}}
# phi3-mini's width over its whole vocabulary, 2 layers, outputs long
# enough that a served token's widest gap reads the fp8 control's scale:
# at 3072 the control read 0.88-0.93 (decode) and 1.07 (prefill) here,
# the program 0.04-0.09; at 2048 with short outputs the control read
# 0.43-0.78
_SERVE_CFG = {"hidden_size": 3072, "num_hidden_layers": 2,
              "num_attention_heads": 32, "num_key_value_heads": 32,
              "intermediate_size": 8192}
CONTROL["phi3-serve-decode"] = {
    "config": _SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 16,
                 "gen": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                         "min": 16, "max": 96, "grid": 16},
                 "steps_per_call": 4, "check_requests": 6},
}
CONTROL["phi3-serve-prefill"] = {
    "config": _SERVE_CFG,
    "workload": {"slots": 4, "callers": 6, "prompt_len": 40,
                 "gen": {"dist": "uniform", "min": 32, "max": 128,
                         "grid": 9},
                 "steps_per_call": 4, "check_requests": 8},
}
