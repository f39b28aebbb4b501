"""CPU sizes of phi3-train-q4 (``bench/tests/sizing.py``)."""
from bench.tests.sizing import TINY_CFG

# the train step in float32: the CPU rounds bf16 products unlike the
# chip, whose fusions keep their float32 accumulations, and at this
# size the bf16 step reads a first-gradient gap of 3e-3 to 7e-3, the
# chip's 1.45e-4 at the cell's size; in float32 it reads 3e-5, so the
# limits set on the chip still tell a sound step from a broken one
TINY = {
    "config": {**TINY_CFG,
               "program": {"param_dtype": "float32",
                           "activation_dtype": "float32"}},
    "workload": {"batch": 2, "seq": 32},
}
CONTROL = TINY
