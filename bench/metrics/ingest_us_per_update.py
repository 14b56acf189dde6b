"""Host microseconds per ``AsyncStreamServer.ingest`` call over the
window: the upload's host-to-device copy and the ingest's dispatch."""


def read(run):
    return run.spans.get("bench.ingest")
