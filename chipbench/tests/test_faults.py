"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of a cell on the CPU (the harness's look for
a chip skipped) with the runner under the engine broken in one of the
ways ``chipbench/faults.py`` plants, and sees ``correct`` come out false.
"""
import pytest

from chipbench.faults import FAULTS
from chipbench.tests.helpers import SMALL, bench, rehearse, rehearsal_seconds

CELLS = [c["name"] for c in bench()["workloads"]]


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    result, _, err = rehearse(cell, wrap=FAULTS[fault], backend="xla",
                              sizes=SMALL,
                              seconds=rehearsal_seconds(cell, "small"))
    assert result["correct"] is False, err
