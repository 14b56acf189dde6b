"""Model FLOPs of the traced rounds' training (every client's and the
root's tokens, forward and backward through the frozen and adapted
weights, counted from the configuration's shapes and the in-jit token
and expert-assignment counters) over the window and the chip's bf16
peak."""
from bench import work_lora


def read(run):
    w = run.work
    if "tokens" not in w:
        return None
    tokens, assignments = sum(w["tokens"][-run.steps:]), sum(w["assignments"][-run.steps:])
    flops = work_lora.train_flops(run.config, run.mix["seq_len"], tokens, assignments)
    return 100.0 * flops / (run.trace.window_s * run.peaks["flops"])
