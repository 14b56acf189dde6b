"""``moonlight.sync_lora_brdrag`` at its rehearsal size on the CPU: a
sound run is correct; the bfloat16 control and the faults of the LoRA
round are caught by the adapters' change leaf by leaf: ``drop_routed``
and ``sync_half_minibatch`` by the difference of the changes, each
leaf's against the larger of its own change and the median leaf's;
``frozen_a`` (the ``a`` factors never move, a small share of the whole
change) by the gap of each leaf's norm against its own."""
from __future__ import annotations

import json

import pytest

from bench import fault_sync
from bench.tests.cells import result

CELL = "moonlight.sync_lora_brdrag"
CHECKS = {"change_diff_floor", "change_gap", "dod_gap"}
#: the check each fault fails
CAUGHT_BY = {"drop_routed": "change_diff_floor", "frozen_a": "change_gap",
             "sync_half_minibatch": "change_diff_floor"}


def test_sound_run_is_correct(capsys):
    line = result(capsys, CELL)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == CHECKS


def test_bfloat16_control_is_caught(capsys):
    line = result(capsys, CELL, "--control")
    assert not line["correct"], line["checks"]


def _fault_line(capsys, fault: str) -> dict:
    assert fault_sync.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "0.5",
                            "--rehearsal", "--fault", fault]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["run"] == f"rehearsal fault {fault}"
    assert not line["correct"], line["checks"]
    return line


def test_drop_routed_is_caught(capsys):
    line = _fault_line(capsys, "drop_routed")
    check = line["checks"][CAUGHT_BY["drop_routed"]]
    assert not check["value"] <= check["limit"]


@pytest.mark.parametrize("fault", ["frozen_a", "sync_half_minibatch"])
def test_fault_is_caught(capsys, fault):
    line = _fault_line(capsys, fault)
    check = line["checks"][CAUGHT_BY[fault]]
    assert not check["value"] <= check["limit"]
