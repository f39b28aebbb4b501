"""A cell's sizes for the CPU tests, found by its name:
``sizes/<cell>.py`` defines ``TINY``, laid over the cell's files by the
drivers' and the faults' tests, and ``CONTROL``, by the controls' test:
sizes at which the cells' limits, set from the chip readings at the
cells' own sizes, still tell the control from the program. Each is
``{"config": {...}, "workload": {...}}``. The cells' own sizes
are in bench/configs and bench/workloads. Below, sizes that the phi3
cells share."""
from bench import harness


def sizes(cell: str):
    """``sizes/<cell>.py`` as a module; a :class:`harness.BenchError`
    that names the file where the cell has none."""
    return harness.load_module("tests/sizes", cell)


TINY_CFG = {"hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "intermediate_size": 128, "vocab_size": 512}
# serving at hidden 64: weights ten times the published scale, so that
# the logits spread as phi3-mini's do at its width (a logit's deviation
# is ~1.1 there and 0.16 here at 0.02), and a served token altered reads
# several units below the best, as it would in the cell
TINY_SERVE_CFG = {**TINY_CFG, "init": {"std": 0.2}}
# phi3-mini's width over its whole vocabulary, 2 layers, outputs long
# enough that a served token's widest gap reads the fp8 control's scale:
# at 3072 the control read 0.88-0.93 (decode) and 1.07 (prefill) here,
# the program 0.04-0.09; at 2048 with short outputs the control read
# 0.43-0.78
SERVE_CFG = {"hidden_size": 3072, "num_hidden_layers": 2,
             "num_attention_heads": 32, "num_key_value_heads": 32,
             "intermediate_size": 8192}
