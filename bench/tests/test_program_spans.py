"""The readers of the program's own spans (``repro.*`` on the profiler's
host timeline): on hand-made events, on a program without the spans,
and on a traced rehearsal of each cell on the CPU."""
from __future__ import annotations

import math
import types

import pytest

from bench import harness, tracefile
from bench.run import main

READERS = ("ingest_h2d_us_per_update", "flush_dispatch_us.server", "chunk_host_ms.train")


def _read(name, events):
    run = types.SimpleNamespace(trace=tracefile.Trace(events))
    return harness.load_module("metrics", name).read(run)


def test_readers_on_hand_made_events():
    us, ms = 1_000, 1_000_000  # ns
    ev = {"devices": [], "host": [
        [tracefile.WINDOW_SPAN, 100 * ms, 200 * ms],
        ["repro.ingest.h2d", 90 * ms, 101 * ms],  # starts before the window
        ["repro.ingest.h2d", 110 * ms, 110 * ms + 300 * us],
        ["repro.ingest.h2d", 120 * ms, 120 * ms + 500 * us],
        ["repro.ingest.write", 121 * ms, 122 * ms],
        ["repro.flush.dispatch", 130 * ms, 130 * ms + 2_000 * us],
        ["bench.flush", 129 * ms, 135 * ms],
        ["repro.megastep", 140 * ms, 170 * ms],
        ["repro.megastep.prepare", 140 * ms, 142 * ms],
        ["repro.megastep.dispatch", 142 * ms, 145 * ms],
        ["repro.megastep.wait", 145 * ms, 165 * ms],
        ["repro.megastep.drain", 165 * ms, 170 * ms],
        ["repro.megastep", 175 * ms, 195 * ms],
        ["repro.megastep.prepare", 175 * ms, 176 * ms],
        ["repro.megastep.dispatch", 176 * ms, 177 * ms],
        ["repro.megastep.wait", 177 * ms, 194 * ms],
        ["repro.megastep.drain", 194 * ms, 195 * ms],
        ["repro.megastep.drain", 196 * ms, 197 * ms],  # outside every megastep span
        ["repro.megastep", 198 * ms, 210 * ms],  # ends after the window
    ]}
    assert _read("ingest_h2d_us_per_update", ev) == pytest.approx(400.0)
    assert _read("flush_dispatch_us.server", ev) == pytest.approx(2_000.0)
    # (2 + 3 + 5) + (1 + 1 + 1) ms over two chunks; the waits do not count
    assert _read("chunk_host_ms.train", ev) == pytest.approx(6.5)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_program_spans(name):
    """A program that writes no ``repro.*`` spans (the commit before
    them) gives no value, and no error."""
    ev = {"devices": [], "host": [[tracefile.WINDOW_SPAN, 0, 100],
                                  ["bench.ingest", 10, 20], ["bench.chunk", 30, 90]]}
    assert _read(name, ev) is None


CHILDREN = {
    "cifar100.server_drag": {"repro.ingest": ("repro.ingest.h2d", "repro.ingest.write"),
                             "repro.flush": ("repro.flush.dispatch", "repro.flush.mirror")},
    "cifar10.async_fedbuff": {"repro.megastep": tuple(
        f"repro.megastep.{c}" for c in ("prepare", "dispatch", "wait", "drain"))},
}


@pytest.mark.parametrize("cell", sorted(CHILDREN))
def test_traced_rehearsal_holds_the_program_spans(cell, capsys, monkeypatch):
    """``bench/run.py --trace 1`` at the rehearsal size on the CPU: the
    program's spans reach the profiler's host timeline, each child
    inside its parent, and this cell's readers read them."""
    loaded = []
    load = tracefile.load
    monkeypatch.setattr(tracefile, "load", lambda d: loaded.append(load(d)) or loaded[-1])
    assert main(["--workload", cell, "--seed", "3000000019", "--seconds", "0.5",
                 "--rehearsal", "--trace", "1"]) == 0
    capsys.readouterr()
    events, = loaded
    host = {}
    for name, a, b in events["host"]:
        host.setdefault(name, []).append((a, b))
    for parent, children in CHILDREN[cell].items():
        assert parent in host, sorted(n for n in host if n.startswith("repro."))
        for child in children:
            assert child in host, child
            for a, b in host[child]:
                assert any(p0 <= a and b <= p1 for p0, p1 in host[parent]), child
    readers = [m["name"] for m in harness.cell_metrics(harness.benchmark(), "per_layer", cell)
               if m["name"] in READERS]
    assert readers
    for name in readers:
        v = _read(name, events)
        assert v is not None and math.isfinite(v) and v > 0, name
