"""Each cell's control at a size a CPU test run can hold: the reference
one precision below the configuration's, put in the program's place,
comes out not correct by the cell's own limits, where the program on the
same seed comes out correct. The limits were set from chip readings at
the cells' own sizes (PERF.md gives them); ``CONTROL`` in
``sizes/<cell>.py`` keeps the scale of the activations and logits that
those readings rest on. The CPU has no three-pass ``high``: there
mlp-async's control is the TPU's ``high`` written out
(``reference/tabular.py``'s ``bf16x3``)."""
import pytest

from bench import harness, run
from bench.tests import controls
from bench.tests.sizing import sizes

CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    control = sizes(cell).CONTROL
    prog = controls.readings(cell, 7, "program", control, seconds=1.0)
    ctl = controls.readings(cell, 7, "control", control, seconds=1.0)
    assert run.is_correct(controls.checks(cell, prog), 0), prog
    assert not run.is_correct(controls.checks(cell, ctl), 0), ctl
