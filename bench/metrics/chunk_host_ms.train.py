"""Host milliseconds per megastep chunk spent outside the device's
work: the program's ``repro.megastep.prepare``, ``.dispatch`` and
``.drain`` spans, summed inside each ``repro.megastep`` span of the
window."""
from bench import program_spans


def read(run):
    chunks = program_spans.inside(run.trace, "megastep")
    if not chunks:
        return None
    host = [iv for part in ("prepare", "dispatch", "drain")
            for iv in program_spans.inside(run.trace, f"megastep.{part}")]
    total = sum(b - a for a, b in host if any(c0 <= a and b <= c1 for c0, c1 in chunks))
    return total / len(chunks) / 1e6
