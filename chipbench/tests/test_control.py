"""The control comes out as not correct, at a size a test run can hold.

The control is the reference itself with every weight matmul taking
float8 e4m3 operands, the precision below the bf16 the configurations
state.  At a reduced width on the CPU each cell's program must pass the
cell's check and the control, the token it puts first at each served
position put through the same check, must fail it.  (On the chip, at the
cells' own sizes, ``chipbench/control.py`` reads the same two verdicts;
``PERF.md`` gives the readings the limits were set from.)
"""
import pytest

from chipbench.harness import load_limits
from chipbench.tests.helpers import SMALL, bench, rehearse, rehearsal_seconds

CELLS = [c["name"] for c in bench()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit_the_program_meets(cell):
    result, _, err = rehearse(cell, seed=2 ** 31 + 11, backend="xla",
                              sizes=SMALL,
                              seconds=rehearsal_seconds(cell, "small"),
                              control=True)
    limit = load_limits(cell)["logit_gap_max_sd"]
    assert result["checks"]["logit_gap_max_sd"]["value"] <= limit, err
    assert result["correct"] is True, err
    assert result["control"]["correct"] is False, err
    assert result["control"]["logit_gap_max_sd"] > limit, err
