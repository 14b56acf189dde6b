"""Work of the LoRA-adapted MLA + MoE clients, counted from the shapes
of the configuration file (its catalog keys) and the traffic mix, and
the trace's grouped expert products."""
from __future__ import annotations

import re

#: the grouped products of the held experts (``lax.ragged_dot``), which
#: the TPU runs as Mosaic kernels named ``ragged-dot-*`` beside a
#: ``ragged-dot-metadata`` kernel that only lays out the groups
EXPERT_MM = re.compile(r"^%ragged-dot-(?!metadata)[\w.\-]* = ")


def _adapted(c: dict) -> list[tuple[int, int, int]]:
    """(in, out, layers) of every adapted matrix."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, kr = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    n_layers, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    ff, fs = c["intermediate_size"], c["moe_intermediate_size"] * c["n_shared_experts"]
    attn = [(d, h * (dn + dr)), (d, kr + dr), (kr, h * (dn + dv)), (h * dv, d)]
    out = [(i, o, n_layers) for i, o in attn]
    out += [(d, ff, n_dense), (d, ff, n_dense), (ff, d, n_dense)]
    out += [(d, fs, n_layers - n_dense), (d, fs, n_layers - n_dense), (fs, d, n_layers - n_dense)]
    return out


def macs_per_token(c: dict, seq_len: int) -> dict:
    """Forward multiply-adds per token, routed experts aside: through
    the frozen weights (``weights``), the adapters (``adapters``), and
    the causal attention products (``attention``, at the mean context
    (L + 1) / 2)."""
    d, h, v = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_layers, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    n_moe = n_layers - n_dense
    weights = sum(i * o * n for i, o, n in _adapted(c))
    weights += n_moe * d * c["router_experts"] + d * v
    adapters = sum(c["adapters"]["rank"] * (i + o) * n for i, o, n in _adapted(c))
    ctx = (seq_len + 1) / 2
    attention = n_layers * h * ((dn + dr) + dv) * ctx
    return {"weights": weights, "adapters": adapters, "attention": attention}


def expert_macs(c: dict) -> int:
    """Forward multiply-adds of one (token, routed expert) assignment."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def train_flops(c: dict, seq_len: int, tokens: int, assignments: int) -> float:
    """Model FLOPs of training on ``tokens`` tokens with ``assignments``
    held-expert assignments: the forward, then the backward through the
    frozen and adapted weights (their input gradients, once the forward's
    work), the attention products (both operands, twice the forward's)
    and the adapters' own gradients.  Recomputation does not count."""
    m = macs_per_token(c, seq_len)
    fwd = tokens * (m["weights"] + m["adapters"] + m["attention"]) + assignments * expert_macs(c)
    bwd = (tokens * (m["weights"] + 2 * m["adapters"] + 2 * m["attention"])
           + assignments * expert_macs(c))
    return 2.0 * (fwd + bwd)


def expert_mm_ideal_s(c: dict, assignments: int, calls: int, peaks: dict) -> float:
    """Least time of the grouped expert products the training needs:
    ``assignments`` rows through the three products forward and their
    input gradients backward, ``calls`` products each reading the held
    experts' weights; the larger of FLOPs over peak and bytes over HBM
    bandwidth."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    flops = 2.0 * 2 * expert_macs(c) * assignments
    rows = 2 * 3 * assignments * (d + f) * 4  # each product reads its rows and writes its result
    weights = calls * c["n_routed_experts"] * d * f * 4
    return max(flops / peaks["flops"], (rows + weights) / peaks["hbm_bytes_per_s"])


def expert_mm_s(trace) -> float:
    """Summed device seconds of the grouped expert products."""
    return sum(b - a for ops in trace.devices for n, a, b in ops if EXPERT_MM.match(n)) / 1e9
