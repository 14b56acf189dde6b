"""Flush kernels' share of their HBM roofline in this cell."""
from bench import work


def read(run):
    return work.flush_roofline_pct(run.trace, run.work["k"], run.config, run.peaks)
