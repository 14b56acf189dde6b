"""``cifar10.async_fedbuff`` at its rehearsal size on the CPU: a sound
run is correct and the bfloat16 control is caught (the faults are in
``test_bench_megastep_faults.py``, so that the two halves can run on
different workers)."""
from __future__ import annotations

from bench.tests.cells import result

CELL = "cifar10.async_fedbuff"


def test_sound_run_is_correct(capsys):
    line = result(capsys, CELL)
    assert line["correct"], line["checks"]
    assert line["checks"]["tau_sum_gap"]["value"] == 0.0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}


def test_bfloat16_control_is_caught(capsys):
    line = result(capsys, CELL, "--control")
    assert not line["correct"], line["checks"]
