"""CPU sizes of mlp-async (``bench/tests/sizing.py``)."""

TINY = {
    "config": {"assumed": {"rows": 512}},
    "workload": {"rounds": 40, "batch": 32},
}
# the paper's own widths; a tenth of a chunk's rounds
CONTROL = {"workload": {"rounds": 200}}
