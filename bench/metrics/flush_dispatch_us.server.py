"""Host microseconds per flush in the program's ``repro.flush.dispatch``
span: the jitted flush call, from entry to return."""
from bench import program_spans


def read(run):
    return program_spans.mean_us(run.trace, "flush.dispatch")
