"""Host microseconds per upload in the program's ``repro.ingest.h2d``
span: the upload's host-to-device copy, before the buffer write."""
from bench import program_spans


def read(run):
    return program_spans.mean_us(run.trace, "ingest.h2d")
