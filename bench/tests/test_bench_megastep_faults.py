"""``cifar10.async_fedbuff`` at its rehearsal size on the CPU: each
fault the cell can have is caught; those that leave every update's
staleness as it was are caught by the parameters alone."""
from __future__ import annotations

import pytest

from bench.tests.cells import result

STALENESS_INTACT = ("half_rows", "half_minibatch")


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "half_rows", "half_minibatch",
                                   "altered"])
def test_fault_is_caught(capsys, fault):
    line = result(capsys, "cifar10.async_fedbuff", "--fault", fault)
    assert not line["correct"], line["checks"]
    if fault in STALENESS_INTACT:
        failed = {n for n, c in line["checks"].items() if not c["value"] <= c["limit"]}
        assert line["checks"]["tau_sum_gap"]["value"] == 0.0
        assert failed & {"change_gap", "change_diff"}, line["checks"]
