"""Host milliseconds per sync round in the program's ``repro.round.sample``
(client selection, batch build and transfer) and ``repro.round.dispatch``
(the jitted round call, entry to return) spans, mean over the window's
rounds."""
from bench import program_spans


def read(run):
    rounds = program_spans.inside(run.trace, "round.dispatch")
    if not rounds:
        return None
    host = rounds + program_spans.inside(run.trace, "round.sample")
    return sum(b - a for a, b in host) / len(rounds) / 1e6
