"""The control and the planted faults each make a small run come out not
correct, while the same run without them is correct (see
``test_bench_rehearsal.test_cell_rehearsal``)."""
import pytest

import faults
from test_bench_rehearsal import small_run

#: the faults the cell can have: it solves one instance at a time, so no
#: batch can lose half its rows, and it crosses no chips
CASES = [("rlg.cold", "control"), ("rlg.cold", "unchanged"),
         ("rlg.cold", "altered")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(cell, fault):
    with getattr(faults, fault)():
        res = small_run(cell, seed=987654321, seconds=1.0)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
    assert any(v["value"] > v["limit"] for v in res["checks"].values())
