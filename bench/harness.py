"""What every cell shares: finding the cell's files by name, the compile
clock, host spans, the checks against their limits, and the result line.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: dict, section: str, cell: str) -> list[dict]:
    """The metrics of one section that this cell reports."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


class CompileClock:
    """Counts and sums JAX's backend compilations (XLA and Mosaic)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


class Spans:
    """Host-clock spans of the benchmark's own calls into the program:
    total seconds and count per name.  Inside a profiled stretch each
    span is also a ``TraceAnnotation``, so the trace shows what the host
    was doing in the device's idle gaps."""

    def __init__(self):
        self.total: dict[str, float] = {}
        self.count: dict[str, int] = {}
        self.annotate = False

    def span(self, name: str):
        return _Span(self, name)

    def mean_us(self, name: str) -> float | None:
        n = self.count.get(name, 0)
        return self.total[name] / n * 1e6 if n else None


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: Spans, name: str):
        self.spans, self.name, self.ann = spans, name, None

    def __enter__(self):
        if self.spans.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        s = self.spans
        s.total[self.name] = s.total.get(self.name, 0.0) + dt
        s.count[self.name] = s.count.get(self.name, 0) + 1
        return False


def drive(gen, seconds: float) -> tuple[int, float]:
    """The measured window: steps until ``seconds`` have passed, each
    ending at a point the host sees after the device.  Returns (updates,
    elapsed seconds)."""
    start = time.perf_counter()
    updates = 0
    while True:
        updates += gen.step()
        elapsed = time.perf_counter() - start
        if elapsed >= seconds:
            return updates, elapsed


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when every number
    is finite and at most its limit."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok = ok and good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)


#: when the process started; ``bench/run.py`` sets it at its first line
START = time.perf_counter()


def info(**kw) -> None:
    """One line of set-up parts, counts and the like, before the result,
    with the seconds since the process started."""
    kw["at_s"] = time.perf_counter() - START
    print("info " + json.dumps(kw, default=float), flush=True)
