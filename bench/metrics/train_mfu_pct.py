"""Model FLOPs of the client training in the traced window (forward and
backward of every sample trained, counted from the CNN's layer shapes)
over the window and the chip's peak."""
from bench import work


def read(run):
    samples = run.steps * run.work["samples"]
    if not samples:
        return None
    flops = samples * work.train_flops_per_sample(run.config)
    return 100.0 * flops / (run.trace.window_s * run.peaks["flops"])
