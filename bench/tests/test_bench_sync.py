"""``cifar100.sync_brdrag_alie`` at its rehearsal size on the CPU: a
sound run is correct and names every check; the bfloat16 control and
the sync round's faults are caught; the round's host spans reach the
trace and ``round_host_ms.sync`` reads them; the per-leaf comparison
and the sync readers on hand-made values."""
from __future__ import annotations

import json
import math
import types

import numpy as np
import pytest

from bench import fault_sync, harness, tracefile, work_lora
from bench.tests.cells import result
from bench.traffic import sync

CELL = "cifar100.sync_brdrag_alie"
#: the check each fault fails
CAUGHT_BY = {"sync_unchanged": "change_diff_floor", "sync_half_rows": "change_gap_floor"}


def test_traced_sound_run_is_correct_and_reads_the_round_spans(capsys, monkeypatch):
    """A sound run, traced: correct, with every check named; the round's
    host spans reach the trace and ``round_host_ms.sync`` reads them."""
    loaded = []
    load = tracefile.load
    monkeypatch.setattr(tracefile, "load", lambda d: loaded.append(load(d)) or loaded[-1])
    line = result(capsys, CELL, "--trace", "1")
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {"change_diff_floor", "change_gap_floor"}
    assert line["attempted"] > 0 and line["failed"] == 0
    events, = loaded
    names = {n for n, _, _ in events["host"]}
    assert {"repro.round.sample", "repro.round.dispatch", "repro.round.wait"} <= names
    run = types.SimpleNamespace(trace=tracefile.Trace(events))
    v = harness.load_module("metrics", "round_host_ms.sync").read(run)
    assert v is not None and math.isfinite(v) and v > 0


def test_bfloat16_control_is_caught(capsys):
    line = result(capsys, CELL, "--control")
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["sync_unchanged", "sync_half_rows"])
def test_fault_is_caught(capsys, fault):
    assert fault_sync.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "0.5",
                            "--rehearsal", "--fault", fault]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["run"] == f"rehearsal fault {fault}"
    assert not line["correct"], line["checks"]
    check = line["checks"][CAUGHT_BY[fault]]
    assert not check["value"] <= check["limit"]


def test_a_small_leaf_left_unchanged_reads_in_full():
    """A leaf holding a thousandth of the change, left as it was, reads
    1 by its own norm, and a thousandth with the median floor."""
    gen = sync.Generator({"model": "cnn"}, {"selected": 2}, 0, None, None)
    gen.before = {"a": np.zeros(4), "b": np.zeros(4), "c": np.zeros(4)}
    gen.kept_inputs = {"malicious": np.array([False, True])}
    ref = {"after": {"a": np.full(4, 1e-3), "b": np.ones(4), "c": np.ones(4)},
           "dod": np.zeros(2), "delta_norm": 1.0}
    prog = dict(ref, after={"a": np.zeros(4), "b": np.ones(4), "c": np.ones(4)})
    out = gen.compare(prog, ref)
    assert out["change_diff"] == out["change_gap"] == 1.0
    assert out["change_diff_floor"] == pytest.approx(1e-3)
    assert out["worst_leaf"] == "a"
    assert gen.compare(ref, ref)["change_diff"] == 0.0


def test_round_host_ms_on_hand_made_events():
    ms = 1_000_000
    ev = {"devices": [], "host": [
        [tracefile.WINDOW_SPAN, 100 * ms, 200 * ms],
        ["repro.round.sample", 101 * ms, 103 * ms],
        ["repro.round.dispatch", 103 * ms, 104 * ms],
        ["repro.round.wait", 104 * ms, 150 * ms],
        ["repro.round.sample", 150 * ms, 154 * ms],
        ["repro.round.dispatch", 154 * ms, 155 * ms],
        ["repro.round.dispatch", 195 * ms, 205 * ms],  # ends after the window
    ]}
    run = types.SimpleNamespace(trace=tracefile.Trace(ev))
    assert harness.load_module("metrics", "round_host_ms.sync").read(run) == pytest.approx(4.0)
    empty = types.SimpleNamespace(trace=tracefile.Trace(
        {"devices": [], "host": [[tracefile.WINDOW_SPAN, 0, 100]]}))
    assert harness.load_module("metrics", "round_host_ms.sync").read(empty) is None


def _config():
    return harness.load_json("configs", "moonlight_16b_a3b_ep8.json")


def test_lora_work_counts_the_adapters_and_the_model():
    c = _config()
    m = work_lora.macs_per_token(c, 4096)
    assert m["adapters"] == c["d"] == 2_888_704
    # the layers' weights, router and head without the routed experts
    assert m["weights"] == 13_762_560 * 5 + 3 * 2048 * 11264 + 4 * (2048 * 64 + 3 * 2048 * 2816) \
        + 2048 * 20480
    assert work_lora.expert_macs(c) == 3 * 2048 * 1408
    # forward 2 MACs, backward 2 MACs through the weights, 4 through the
    # attention products, plus the adapters' own gradients
    f = work_lora.train_flops(c, 4096, 1, 0)
    assert f == pytest.approx(2 * (2 * m["weights"] + 3 * m["adapters"] + 3 * m["attention"]))


def test_expert_mm_reader_on_hand_made_kernels():
    c = _config()
    peaks = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    ms = 1_000_000
    ev = {"devices": [[
        ["%ragged-dot-none.3 = f32[24576,1408]{1,0} custom-call(...)", 110 * ms, 112 * ms],
        ["%ragged-dot-metadata.1 = (s32[9]) custom-call(...)", 112 * ms, 113 * ms],
        ["%ragged-dot-none.9 = f32[24576,2048]{1,0} custom-call(...)", 120 * ms, 122 * ms],
        ["%fusion.2 = f32[4096,2048]{1,0} fusion(...)", 130 * ms, 140 * ms],
    ]], "host": [[tracefile.WINDOW_SPAN, 100 * ms, 200 * ms]]}
    trace = tracefile.Trace(ev)
    assert work_lora.expert_mm_s(trace) == pytest.approx(0.004)
    run = types.SimpleNamespace(trace=trace, config=c, peaks=peaks, steps=1,
                                work={"assignments": [5, 3000], "train_steps_per_round": 1})
    ideal = work_lora.expert_mm_ideal_s(c, 3000, 4 * 6, peaks)
    v = harness.load_module("metrics", "expert_mm_roofline_pct").read(run)
    assert v == pytest.approx(100 * ideal / 0.004)
    none = types.SimpleNamespace(trace=trace, config=c, peaks=peaks, steps=1, work={"k": 10})
    assert harness.load_module("metrics", "expert_mm_roofline_pct").read(none) is None
