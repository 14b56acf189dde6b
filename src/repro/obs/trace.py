"""Host-side span tracing: nestable spans on the profiler's clock, sink
fan-out, and profiler annotations.

The span API instruments the engines' HOST boundaries — the event loop
around the jitted flush, the dispatch/ingest path, eval points — never
code inside jit (a traced region runs once at trace time; timing it
would time compilation, not serving).  That boundary rule lives in
ROADMAP §System map.

Everything funnels through one :class:`Tracer`:

  * ``span(name, **attrs)`` — a context manager timing a host region.
    Spans nest: each records its parent via a per-thread stack, so sinks
    can rebuild the tree and the Perfetto export shows real nesting.
    Child spans of one boundary share their parent's name as a prefix
    (``ingest`` > ``ingest.h2d``, ``ingest.write``).
  * ``counter(name, value)`` — a point value (kernel call counts, drop
    totals).
  * ``alert(signal, round)`` — a monitor detector fired.

A span is live when a sink is attached OR a ``jax.profiler`` session is
running.  Under an active profiler a live span is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (``repro.flush``,
``repro.megastep.dispatch``): it lands on the profiler's host timeline
beside the device ops, and the ``repro.`` prefix keeps the program's
spans apart from the runtime's own events and from a benchmark's spans.
The annotation carries the name only, not the attrs.  With only the
profiler on, no event dict is built.

Clock: events are stamped with ``time.time_ns`` (the wall clock,
``CLOCK_REALTIME``), the clock the profiler stamps its host events
with, so an exported ``flush`` span and its ``repro.flush`` profiler
event start at the same instant (``jax.profiler.ProfileData`` gives
event times relative to the profile's ``profile_start_time``, itself
on this clock).

With neither a sink nor a profiler (the default — telemetry is opt-in
via ``api.TelemetrySpec``) ``span`` costs one attribute check and one
profiler-state check, and returns a shared no-op context manager.
Events are plain dicts (the JSONL schema, ``benchmarks.validate``
checks it) fanned out to the attached sinks.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Callable

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

#: event schema version stamped on every emitted event (JSONL consumers
#: and ``benchmarks/validate.py`` key on it)
SCHEMA_VERSION = 1

#: required keys per event type — THE schema ``benchmarks.validate``
#: checks recorded JSONL files against
EVENT_SCHEMA: dict[str, tuple[str, ...]] = {
    "span": ("type", "name", "ts_us", "dur_us", "tid"),
    "counter": ("type", "name", "ts_us", "value"),
    # ``alert``: a monitor detector fired (repro.obs.monitor).  ``signal``
    # names the MONITOR_SIGNALS entry, ``round`` the server round of the
    # flush that tripped it; value/score evidence rides in ``attrs``.
    "alert": ("type", "name", "ts_us", "signal", "round"),
}

#: prefix of the spans' names on the profiler's timeline
PROFILER_PREFIX = "repro."

#: True while a ``jax.profiler`` session records host events
_profiling: Callable[[], bool] = _profiler.TraceMe.is_enabled


def _now_us() -> float:
    return time.time_ns() / 1e3


class _NullSpan:
    """Shared no-op context manager — the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:  # parity with _LiveSpan
        pass


_NULL_SPAN = _NullSpan()


class _AnnotationSpan(TraceAnnotation):
    """A span under an active profiler with no sink attached: the
    profiler annotation alone, no event."""

    def set(self, **attrs) -> None:  # parity with _LiveSpan
        pass


class _LiveSpan:
    """One open span: collects attrs, emits on exit, and is a profiler
    annotation while a profiler session runs."""

    __slots__ = ("tracer", "name", "attrs", "t0", "parent", "span_id", "ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.parent = 0
        self.span_id = 0
        self.ann = None

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. a flush's round)."""
        self.attrs.update(attrs)

    def __enter__(self):
        stack = self.tracer._stack()
        self.parent = stack[-1] if stack else 0
        self.span_id = next(self.tracer._ids)
        stack.append(self.span_id)
        if _profiling():
            self.ann = TraceAnnotation(PROFILER_PREFIX + self.name)
            self.ann.__enter__()
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc):
        t1 = _now_us()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        stack = self.tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        ev = {
            "type": "span",
            "name": self.name,
            "ts_us": self.t0,
            "dur_us": t1 - self.t0,
            "tid": threading.get_ident() & 0xFFFF,
            "span_id": self.span_id,
            "parent": self.parent,
            "v": SCHEMA_VERSION,
        }
        if self.attrs:
            ev["attrs"] = self.attrs
        self.tracer._emit(ev)
        return False


class Tracer:
    """Span/counter event source fanning out to attached sinks.

    Disabled (no sinks) by default; :meth:`attach`/:meth:`detach` flip
    the ``enabled`` flag, which says whether events are recorded; a
    running profiler makes spans live as annotations either way.  Sinks
    are host-side only: anything with an ``emit(event: dict)`` method
    (``repro.obs.sinks``).
    """

    def __init__(self) -> None:
        self.sinks: list[Any] = []
        self.enabled = False
        self._ids = itertools.count(1)
        self._local = threading.local()

    # ------------------------------------------------------------ plumbing
    def _stack(self) -> list[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _emit(self, event: dict) -> None:
        for sink in self.sinks:
            sink.emit(event)

    def attach(self, sink) -> None:
        if sink not in self.sinks:
            self.sinks.append(sink)
        self.enabled = bool(self.sinks)

    def detach(self, sink) -> None:
        if sink in self.sinks:
            self.sinks.remove(sink)
        self.enabled = bool(self.sinks)

    # ------------------------------------------------------------- the API
    def span(self, name: str, **attrs):
        """Time a host-side region; nestable.  An event for the sinks,
        a ``repro.<name>`` annotation for a running profiler, else a
        no-op."""
        if self.enabled:
            return _LiveSpan(self, name, attrs)
        if _profiling():
            return _AnnotationSpan(PROFILER_PREFIX + name)
        return _NULL_SPAN

    def counter(self, name: str, value, **attrs) -> None:
        """Record a point value (a count, a rate) at the current time."""
        if not self.enabled:
            return
        ev = {
            "type": "counter",
            "name": name,
            "ts_us": _now_us(),
            "value": float(value),
            "tid": threading.get_ident() & 0xFFFF,
            "v": SCHEMA_VERSION,
        }
        if attrs:
            ev["attrs"] = attrs
        self._emit(ev)

    def alert(self, signal: str, round: int, **attrs) -> None:
        """A monitor detector fired: typed alert event (diagnosis plane)."""
        if not self.enabled:
            return
        ev = {
            "type": "alert",
            "name": f"alert/{signal}",
            "ts_us": _now_us(),
            "signal": signal,
            "round": int(round),
            "tid": threading.get_ident() & 0xFFFF,
            "v": SCHEMA_VERSION,
        }
        if attrs:
            ev["attrs"] = attrs
        self._emit(ev)

    @contextlib.contextmanager
    def attached(self, *sinks):
        """Attach sinks for the duration of a block (tests, benchmarks)."""
        for s in sinks:
            self.attach(s)
        try:
            yield self
        finally:
            for s in sinks:
                self.detach(s)


#: the process-default tracer the engines emit through; a
#: TelemetrySession attaches its sinks here for the run's duration
tracer = Tracer()


def get_tracer() -> Tracer:
    return tracer


def span(name: str, **attrs):
    """``obs.trace.span("ingest")`` — a span on the default tracer."""
    return tracer.span(name, **attrs)


def counter(name: str, value, **attrs) -> None:
    tracer.counter(name, value, **attrs)


def aggregate_spans(events) -> dict[str, dict[str, float]]:
    """Span-attributed wall-clock breakdown from a recorded event list.

    Returns ``{span_name: {count, total_ms, mean_us, max_us}}`` — the
    provenance shape the benchmarks embed in their BENCH_*.json records
    (where the 300x ingest-vs-flush gap becomes a budget, not an
    anecdote).  SELF time is not subtracted: spans nest, so parents
    include children — read the tree through the Perfetto export when
    attribution matters.
    """
    out: dict[str, dict[str, float]] = {}
    for ev in events:
        if ev.get("type") != "span":
            continue
        rec = out.setdefault(
            ev["name"], {"count": 0, "total_ms": 0.0, "mean_us": 0.0, "max_us": 0.0}
        )
        rec["count"] += 1
        rec["total_ms"] += ev["dur_us"] / 1e3
        rec["max_us"] = max(rec["max_us"], ev["dur_us"])
    for rec in out.values():
        rec["mean_us"] = rec["total_ms"] * 1e3 / max(rec["count"], 1)
    return out
