"""The benchmark's yardstick on the CPU: the trace reduction, the work
counters, the peaks, and that every cell resolves to its files."""
from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, tracefile, work

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "server_flush_trace.json.gz")


def _config(name):
    return harness.load_json("configs", f"{name}.json")


# ------------------------------------------------------------ the trace
def test_trace_reduction_on_hand_made_events():
    ev = {
        "host": [[tracefile.WINDOW_SPAN, 100, 200], ["bench.ingest", 100, 130],
                 ["DevicePut", 105, 125]],
        "devices": [[
            ["%while.1 = f32[4] while(f32[4] %a)", 130, 170],  # encloses the next two
            ["%fusion.2 = f32[4] fusion(f32[4] %b)", 135, 150],
            ["%fusion.3 = f32[4] fusion(f32[4] %b)", 150, 160],
            ["%copy.4 = f32[4] copy(f32[4] %c)", 165, 190],  # overlaps the loop
            ["%copy.5 = f32[4] copy(f32[4] %c)", 40, 60],  # before the window
        ]],
    }
    tr = tracefile.Trace(ev)
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s() == pytest.approx(60e-9)  # union of [130, 190]
    ops = dict(tr.device_ops())
    assert ops["%while"] == pytest.approx(15e-9)  # 40 less 25 inside it
    assert ops["%fusion"] == pytest.approx(25e-9)
    assert ops["%copy"] == pytest.approx(25e-9)
    gaps = tr.idle_gaps()
    assert gaps[0] == ["bench.ingest > DevicePut", pytest.approx(30e-9)]
    assert gaps[1][1] == pytest.approx(10e-9)


def test_trace_reduction_on_recorded_chip_trace():
    """One step of the server cell (ten ingests and a flush), traced on
    a TPU v5e and cut to the traced window."""
    with gzip.open(FIXTURE, "rt") as f:
        tr = tracefile.Trace(json.load(f))
    assert tr.window_s == pytest.approx(0.027506258)
    assert tr.busy_s() == pytest.approx(0.001886399)
    assert 1.0 - tr.busy_s() / tr.window_s == pytest.approx(0.931419, abs=1e-6)
    assert tr.kernel_calls(tracefile.FLUSH_CALLS) == 1
    assert tr.kernel_s(("dot_norms",)) == pytest.approx(46.333e-6)
    assert tr.kernel_s(("blend_reduce",)) == pytest.approx(117.340e-6)
    labels = [name for name, _ in tr.device_ops()]
    assert "blend_reduce" in labels and "dot_norms" in labels
    assert len(tr.idle_gaps()) == 10


@pytest.mark.parametrize("name, label", [
    ('%fn.2 = (f32[16,1]{1,0:T(8,128)S(1)}, f32[16,1]{1,0:T(8,128)S(1)}, f32[1,1]{1,0:T(1,128)}) '
     'custom-call(f32[16,647168]{1,0:T(8,128)S(1)} %pad.16, f32[647168]{0:T(1024)S(1)} %pad.4), '
     'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}', "dot_norms"),
    ('%closed_call.408 = f32[581632]{0:T(1024)S(1)} custom-call(f32[16,581632]{1,0:T(8,128)S(1)} '
     '%pad.131, f32[581632]{0:T(1024)S(1)} %pad.132, f32[16,1]{1,0:T(8,128)S(1)} %copy.3, '
     'f32[16,1]{1,0:T(8,128)S(1)} %copy.4), custom_call_target="tpu_custom_call"', "blend_reduce"),
    ('%fn.9 = (f32[131072]{0}, f32[8,1]{1,0}, f32[8,1]{1,0}, f32[1,1]{1,0}) custom-call('
     'f32[8,131072]{1,0} %g, f32[131072]{0} %r, f32[8,1]{1,0} %phi, f32[8,1]{1,0} %w, '
     'f32[8,1]{1,0} %u, f32[1,1]{1,0} %sel), custom_call_target="tpu_custom_call"', "fused_flush"),
    ('%fusion.3 = s32[10]{0:T(128)} fusion(s32[10]{0:T(128)} %ids), kind=kLoop', None),
])
def test_flush_kernels_matched_by_signature(name, label):
    assert tracefile.kernel_of(name) == label


# ------------------------------------------------------------ the work
@pytest.mark.parametrize("name, d, macs", [
    ("cifar10_cnn", 579_402, 16_090_368),
    ("cifar100_cnn", 643_492, 10_871_808),
])
def test_counters_match_the_hand_counts(name, d, macs):
    cfg = _config(name)
    assert work.param_count(cfg) == d == cfg["d"]
    assert work.macs_per_sample(cfg) == macs == cfg["forward_macs_per_sample"]
    assert work.train_flops_per_sample(cfg) == 6 * macs


def test_flush_bytes():
    # two passes over a [10, 579402] f32 stack, r twice, delta once
    assert work.flush_bytes(10, 579_402) == 2 * 10 * 579_402 * 4 + 3 * 579_402 * 4
    assert work.flush_bytes(10, 579_402) == 53_304_984


def test_peaks_are_keyed_on_the_device_kind():
    assert work.peaks("TPU v5 lite") == {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        work.peaks("cpu")


# ------------------------------------------------------------ the cells
def test_every_cell_resolves_to_its_files():
    bench = harness.benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = harness.load_json("workloads", f"{w['name']}.json")
        assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
            (w["name"], w["config"], w["traffic"], w["chips"])
        config = harness.load_json("configs", f"{cell['config']}.json")
        assert configs[cell["config"]]["file"] == f"bench/configs/{cell['config']}.json"
        assert sorted(config["reduced"]) == sorted(configs[cell["config"]]["reduced"])
        mix = harness.load_json("traffic", f"{cell['traffic']}.json")
        gen = harness.load_module("traffic", mix["generator"]).Generator
        assert all(hasattr(gen, a) for a in ("setup", "step", "reference", "compare"))
        assert cell["limits"]
        for section in ("end_to_end", "per_layer"):
            assert harness.cell_metrics(bench, section, w["name"])
    for m in bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
        for cell in m["workloads"]:
            moved = harness.cell_metrics(bench, "end_to_end", cell)
            assert m["moves"] in [e["name"] for e in moved]


# ------------------------------------------------------------ the command
def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10.async_fedbuff", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    root = os.path.dirname(harness.BENCH)
    proc = _run(root)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    root = os.path.dirname(harness.BENCH)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
