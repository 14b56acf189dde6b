"""Telemetry sinks: where host-side trace events land.

Sinks are HOST-side only (the obs boundary rule): anything with an
``emit(event: dict)`` method.  Three implementations:

  * :class:`MemorySink` — in-memory recorder; what tests assert against
    and what the benchmarks aggregate into BENCH_*.json provenance.
  * :class:`JsonlSink` — structured JSONL event log, one event per
    line (the schema is ``repro.obs.trace.EVENT_SCHEMA``;
    ``benchmarks/validate.py --telemetry`` checks recorded files).
  * :func:`perfetto_trace` / :func:`write_perfetto` — Chrome
    ``trace_event`` JSON export of a recorded event list, loadable in
    ``ui.perfetto.dev`` / ``chrome://tracing``.
"""
from __future__ import annotations

import json
from typing import IO


class MemorySink:
    """In-memory event recorder (tests, benchmark provenance)."""

    def __init__(self) -> None:
        self.events: list[dict] = []

    def emit(self, event: dict) -> None:
        self.events.append(event)

    def clear(self) -> None:
        self.events.clear()

    def spans(self) -> list[dict]:
        return [e for e in self.events if e.get("type") == "span"]

    def counters(self) -> list[dict]:
        return [e for e in self.events if e.get("type") == "counter"]


class JsonlSink:
    """Append-only JSONL event log: one JSON object per line.

    Values must already be JSON-safe (the tracer emits plain
    floats/ints/strs; metrics bundles are scalarised host-side in
    ``repro.obs.session`` before they get here).
    """

    def __init__(self, path: str) -> None:
        self.path = path
        self._f: IO[str] | None = open(path, "w")

    def emit(self, event: dict) -> None:
        if self._f is None:
            raise ValueError(f"JsonlSink({self.path!r}) is closed")
        self._f.write(json.dumps(event) + "\n")

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# --------------------------------------------------------- Perfetto export
def perfetto_trace(events, process_name: str = "repro") -> dict:
    """Chrome/Perfetto ``trace_event`` JSON from a recorded event list.

    Spans become complete ("X") events, counters "C", alerts
    process-scoped instants "i" — the nesting Perfetto renders is the
    real span nesting because the tracer's ``ts``/``dur`` come from one
    clock, the profiler's (``repro.obs.trace``).
    """
    trace_events = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": 1,
            "args": {"name": process_name},
        }
    ]
    for ev in events:
        kind = ev.get("type")
        tid = ev.get("tid", 0)
        if kind == "span":
            trace_events.append({
                "name": ev["name"],
                "ph": "X",
                "ts": ev["ts_us"],
                "dur": ev["dur_us"],
                "pid": 1,
                "tid": tid,
                "args": ev.get("attrs", {}),
            })
        elif kind == "counter":
            trace_events.append({
                "name": ev["name"],
                "ph": "C",
                "ts": ev["ts_us"],
                "pid": 1,
                "args": {"value": ev["value"]},
            })
        elif kind == "alert":
            # alerts render as process-scoped instants so onset markers
            # line up against the flush spans they diagnosed
            args = {"signal": ev["signal"], "round": ev["round"]}
            args.update(ev.get("attrs", {}))
            trace_events.append({
                "name": ev["name"],
                "ph": "i",
                "s": "p",
                "ts": ev["ts_us"],
                "pid": 1,
                "tid": tid,
                "args": args,
            })
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def write_perfetto(events, path: str, process_name: str = "repro") -> None:
    with open(path, "w") as f:
        json.dump(perfetto_trace(events, process_name), f)
