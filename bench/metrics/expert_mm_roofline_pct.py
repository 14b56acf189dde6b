"""The held experts' grouped products against their roofline: the least
time their needed work takes (the traced rounds' held assignments
through the three SwiGLU products forward and their input gradients
backward, each product reading the held weights once) over the summed
device time of the ``ragged-dot`` kernels in the trace (recomputation
included)."""
from bench import work_lora


def read(run):
    w = run.work
    spent = work_lora.expert_mm_s(run.trace)
    if "assignments" not in w or spent <= 0.0:
        return None
    assignments = sum(w["assignments"][-run.steps:])
    moe_layers = run.config["num_hidden_layers"] - run.config["first_k_dense_replace"]
    calls = run.steps * w["train_steps_per_round"] * moe_layers * 6
    ideal = work_lora.expert_mm_ideal_s(run.config, assignments, calls, run.peaks)
    return 100.0 * ideal / spent
