"""The program's own host spans in a reduced trace: ``repro.obs.trace``
writes each span under an active profiler as a ``repro.<name>``
annotation on the profiler's host timeline.  A program without them
gives empty lists, so a reader built on these returns None there."""
from __future__ import annotations

PREFIX = "repro."


def inside(trace, name: str) -> list[tuple[float, float]]:
    """(start_ns, end_ns) of each ``repro.<name>`` event that lies
    wholly inside the traced window, in start order."""
    full = PREFIX + name
    return sorted((a, b) for n, a, b in trace.host
                  if n == full and a >= trace.t0 and b <= trace.t1)


def mean_us(trace, name: str) -> float | None:
    """Mean duration in microseconds of ``repro.<name>`` in the window."""
    spans = inside(trace, name)
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e3

