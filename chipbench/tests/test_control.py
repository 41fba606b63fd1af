"""The control and the planted faults of each cell, from the float32
reference put in the program's place at a tiny size, come out not
correct under the cell's own limits."""
import pytest

from chipbench import control
from chipbench.tests import tiny

ONE_CHIP = ["xlstm125m.train.s2048b8", "stablelm16b.train.s4096b4"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(workload):
    recs = list(control.stand_in_readings(tiny.cell(workload)))
    assert {r["stand_in"] for r in recs} == {"fp8", "half_batch"}
    for r in recs:
        assert r["correct"] is False, r
