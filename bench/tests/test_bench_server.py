"""``cifar100.server_drag`` at its rehearsal size on the CPU: a sound run
is correct; the bfloat16 control and each fault the cell can have are
caught."""
from __future__ import annotations

import pytest

from bench.tests.cells import result

CELL = "cifar100.server_drag"


def test_sound_run_is_correct(capsys):
    line = result(capsys, CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_bfloat16_control_is_caught(capsys):
    line = result(capsys, CELL, "--control")
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_caught(capsys, fault):
    line = result(capsys, CELL, "--fault", fault)
    assert not line["correct"], line["checks"]
