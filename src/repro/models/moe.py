"""Mixture-of-Experts MLP with expert parallelism (Llama-4-Scout, Kimi-K2,
Moonlight).

A layer told which experts it holds (``MoEConfig.experts_held`` > 0)
routes over all of them (:func:`route`, DeepSeek-V3's sigmoid scores and
selection-only bias) and computes its own experts' share of the output,
dropless, in a row buffer sized on the device from :func:`row_ladder`:
``_dispatch_held``.  Otherwise, two capacity dispatch strategies,
selectable via ``MoEConfig.dispatch``:

  * ``einsum`` — classic capacity-based one-hot dispatch/combine einsums
    (Switch/GShard style).  Tokens are partitioned into *groups* so the
    [G, T_g, E, C] dispatch tensor stays bounded; under GSPMD the expert
    axis shards over the ``model`` mesh axis producing the canonical
    all-to-all.  This is the paper-era baseline.
  * ``sort``  — gather/scatter dispatch: tokens are routed via a sort by
    expert id, removing the O(T·E·C·d) one-hot matmul FLOPs.  This is
    the beyond-baseline variant used in §Perf hillclimbing.

Shared experts (always-on dense SwiGLU) follow the DeepSeek/Kimi design.
Aux load-balance loss: E * sum_e f_e * p_e  (Switch eq. 4).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, linear

GROUP_SIZE = 1024  # tokens per dispatch group (einsum mode)
ROW_TILE = 128  # the held dispatch's row capacities are multiples of this
MAX_RUNGS = 4  # row capacities a held layer compiles


def init_moe(key, cfg, dtype):
    d, e = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 5)
    n_local = e.experts_held or e.n_experts  # the experts this device holds
    p = {
        "router": dense_init(ks[0], d, e.n_experts, jnp.float32),
        "w_gate": (
            jax.random.normal(ks[1], (n_local, d, e.d_ff_expert)) / d**0.5
        ).astype(dtype),
        "w_up": (
            jax.random.normal(ks[2], (n_local, d, e.d_ff_expert)) / d**0.5
        ).astype(dtype),
        "w_down": (
            jax.random.normal(ks[3], (n_local, e.d_ff_expert, d))
            / e.d_ff_expert**0.5
        ).astype(dtype),
    }
    if e.selection_bias:
        p["router_bias"] = jnp.zeros((e.n_experts,), jnp.float32)
    if e.n_shared_experts:
        dsh = e.d_ff_expert * e.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, d, dsh, dtype),
            "w_up": dense_init(k2, d, dsh, dtype),
            "w_down": dense_init(k3, dsh, d, dtype),
        }
    return p


def _router(params, cfg, x):
    """x: [T, d] -> (probs [T, E], topk_idx [T, k], topk_w [T, k], aux)."""
    e = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, e.top_k)
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    # load-balance aux: fraction routed (top-1 counts all k choices) x mean prob
    f = jnp.zeros((e.n_experts,), jnp.float32)
    f = f.at[topk_idx.reshape(-1)].add(1.0) / (x.shape[0] * e.top_k)
    p_mean = jnp.mean(probs, axis=0)
    aux = e.n_experts * jnp.sum(f * p_mean)
    return probs, topk_idx, topk_w, aux


def route(params, cfg, x):
    """DeepSeek-V3 routing.  x: [T, d] -> (topk_idx [T, k], topk_w [T, k]).

    Scores s = sigmoid(x W_r) (or softmax) over all experts; the top k
    are chosen on s + b (the selection bias, when the layer has one) and
    weighted by their own scores, renormalised to sum to one and scaled
    by ``routed_scaling``."""
    e = cfg.moe
    # full float32, as the published gate computes it: at one bf16 pass
    # the scores of near-tied experts swap and tokens change expert
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if e.scoring == "sigmoid" else jax.nn.softmax(logits, -1)
    choice = scores + params["router_bias"] if e.selection_bias else scores
    _, topk_idx = jax.lax.top_k(choice, e.top_k)
    topk_w = jnp.take_along_axis(scores, topk_idx, axis=-1)
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    return topk_idx, topk_w * e.routed_scaling


def row_ladder(t: int, top_k: int, held: int, n_experts: int) -> tuple[int, ...]:
    """The row capacities the held dispatch chooses from, smallest first.

    The rungs start at 4/3 of the expected count of held assignments,
    T·k·held/E, and double, each rounded up to ``ROW_TILE``; the last is
    always T·min(k, held), the most a sequence can route to the held
    experts (a token picks k distinct experts), so no rung can drop a
    row.  At most ``MAX_RUNGS``.  With every expert held the expected
    count is that most, and the ladder is the one rung T·k."""
    top = t * min(top_k, held)
    rungs = []
    c = 4 * t * top_k * held / (3 * n_experts)
    while len(rungs) < MAX_RUNGS - 1:
        rung = -(-math.ceil(c) // ROW_TILE) * ROW_TILE
        if rung >= top:
            break
        rungs.append(rung)
        c *= 2
    return (*rungs, top)


def _held_rows(weights, x, order, sizes, w, rows: int, top_k: int):
    """The held experts' weighted share of the output from the first
    ``rows`` rows of the sorted (token, choice) assignments ``order``,
    those of the held experts first; ``w`` is each assignment's routing
    weight, zero off the held experts."""
    idx = order[:rows]
    tok = idx // top_k
    # rows past the held groups are left unwritten by the TPU's grouped
    # product, in its output and in its input gradient: mask both ends
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    w_gate, w_up, w_down = weights
    xs = jnp.where(live, x[tok], 0.0)
    g = jax.lax.ragged_dot(xs, w_gate, sizes)
    u = jax.lax.ragged_dot(xs, w_up, sizes)
    out = jax.lax.ragged_dot(jax.nn.silu(g) * u, w_down, sizes)
    contrib = jnp.where(live, out * w[idx].astype(x.dtype)[:, None], 0.0)
    return jnp.zeros_like(x).at[tok].add(contrib)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_ladder(ladder, top_k, rung, weights, x, order, sizes, w):
    """:func:`_held_rows` on the rung ``rung`` of ``ladder``.  Its
    gradient saves only the inputs, whose shapes no rung changes, and
    recomputes the rung's rows in the backward pass: a plain switch
    under differentiation would save every rung's buffers, the larger
    ones as zeros."""
    return jax.lax.switch(rung, [partial(_held_rows, rows=c, top_k=top_k) for c in ladder],
                          weights, x, order, sizes, w)


def _held_ladder_fwd(ladder, top_k, rung, weights, x, order, sizes, w):
    y = _held_ladder(ladder, top_k, rung, weights, x, order, sizes, w)
    return y, (rung, weights, x, order, sizes, w)


def _held_ladder_bwd(ladder, top_k, res, ct):
    rung, weights, x, order, sizes, w = res

    def rung_vjp(rows):
        def vjp(weights, x, w, order, sizes):
            f = lambda weights, x, w: _held_rows(weights, x, order, sizes, w, rows, top_k)
            return jax.vjp(f, weights, x, w)[1](ct)
        return vjp

    d_weights, dx, dw = jax.lax.switch(rung, [rung_vjp(c) for c in ladder],
                                       weights, x, w, order, sizes)
    return None, d_weights, dx, None, None, dw


_held_ladder.defvjp(_held_ladder_fwd, _held_ladder_bwd)


def _dispatch_held(params, cfg, x):
    """Dropless dispatch onto the experts this device holds.  x: [T, d].

    Every (token, choice) assignment is sorted by its expert, those of
    the held experts first and in expert order, and each held expert's
    rows go through its SwiGLU as one group of a grouped product
    (``lax.ragged_dot``), however many there are.  The row buffer has
    the smallest capacity of :func:`row_ladder` that holds the count of
    held assignments, picked on the device (``lax.switch``); its top
    rung holds every row a sequence can route to the held experts, so
    nothing can be dropped.  Each rung's rows are recomputed under
    differentiation (:func:`_held_ladder`), so that the switch saves only
    its inputs, which have one shape on every rung.  With every expert
    held the ladder is one rung of T·k rows and there is no switch.
    Returns the held experts' weighted share of the
    output and the counters ``assignments`` (per held expert) and
    ``expert_rows`` (the capacity the layer ran)."""
    e = cfg.moe
    held = e.experts_held
    topk_idx, topk_w = route(params, cfg, x)
    local = topk_idx.reshape(-1) - e.expert_offset
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    w = jnp.where(mine, topk_w.reshape(-1), 0.0)
    args = ((params["w_gate"], params["w_up"], params["w_down"]), x, order, sizes, w)
    ladder = row_ladder(x.shape[0], e.top_k, held, e.n_experts)
    if len(ladder) == 1:
        y = _held_rows(*args, rows=ladder[0], top_k=e.top_k)
        rung = 0
    else:
        rung = jnp.searchsorted(jnp.asarray(ladder), jnp.sum(sizes))
        y = _held_ladder(ladder, e.top_k, rung, *args)
    return y, {"assignments": sizes, "expert_rows": jnp.asarray(ladder, jnp.int32)[rung]}


def _experts_ffn(params, h_in):
    """h_in: [E, C', d] -> [E, C', d] through per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", h_in, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h_in, params["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w_down"])


def _dispatch_einsum(params, cfg, x, shard):
    """Capacity one-hot dispatch.  x: [T, d]."""
    e = cfg.moe
    t, d = x.shape
    g = max(t // (e.group_size or GROUP_SIZE), 1)
    tg = t // g
    cap = max(int(tg * e.top_k / e.n_experts * e.capacity_factor), e.top_k)

    probs, topk_idx, topk_w, aux = _router(params, cfg, x)
    xg = x.reshape(g, tg, d)
    idx_g = topk_idx.reshape(g, tg, e.top_k)
    w_g = topk_w.reshape(g, tg, e.top_k)

    # expert mask per k-choice: [G, Tg, k, E].  Position bookkeeping runs
    # in int32 (exact counts); the one-hot dispatch/combine tensors and
    # their einsums run in the activation dtype — the [*, E, C]-scale
    # intermediates are the memory hot spot at Kimi-K2 scale (§Perf H2c).
    mask_i = jax.nn.one_hot(idx_g, e.n_experts, dtype=jnp.int32)
    flat_mask = mask_i.reshape(g, tg * e.top_k, e.n_experts)
    pos = jnp.cumsum(flat_mask, axis=1) - flat_mask  # exclusive
    pos = pos.reshape(g, tg, e.top_k, e.n_experts)
    keep = ((pos < cap) & (mask_i > 0)).astype(x.dtype)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=x.dtype)  # [G,Tg,k,E,C]
    dispatch = jnp.einsum("gtke,gtkec->gtec", keep, pos_oh)
    combine = jnp.einsum("gtk,gtke,gtkec->gtec", w_g.astype(x.dtype), keep, pos_oh)

    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, xg)
    expert_in = shard(expert_in.reshape(g, e.n_experts, cap * 1, d), "moe_expert_in")
    expert_in = expert_in.reshape(e.n_experts, g * cap, d)
    expert_out = _experts_ffn(params, expert_in).reshape(e.n_experts, g, cap, d)
    # Keep expert_out EXPERT-SHARDED (bf16) into the combine so GSPMD
    # contracts the sharded E dim (partial sums + one all-reduce of the
    # [G,Tg,d] result) instead of all-gathering the [G,E,C,d] tensor —
    # ~20x less collective volume at Kimi-K2 scale (§Perf H2b).
    expert_out = jnp.moveaxis(expert_out, 1, 0).astype(x.dtype)  # [G, E, C, d]
    expert_out = shard(expert_out, "moe_expert_out")
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), expert_out)
    y = shard(y, "moe_combine")
    return y.reshape(t, d), aux


def _dispatch_sort(params, cfg, x, shard):
    """Sort/gather dispatch — no one-hot matmul FLOPs.  x: [T, d]."""
    e = cfg.moe
    t, d = x.shape
    cap = max(int(t * e.top_k / e.n_experts * e.capacity_factor), e.top_k)

    probs, topk_idx, topk_w, aux = _router(params, cfg, x)
    n = t * e.top_k
    flat_expert = topk_idx.reshape(n)
    flat_w = topk_w.reshape(n)
    flat_tok = jnp.repeat(jnp.arange(t), e.top_k)

    order = jnp.argsort(flat_expert)
    se, st, sw = flat_expert[order], flat_tok[order], flat_w[order]
    counts = jnp.bincount(flat_expert, length=e.n_experts)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(n) - starts[se]
    ok = pos_in_e < cap

    buf = jnp.zeros((e.n_experts, cap, d), x.dtype)
    buf = buf.at[se, jnp.where(ok, pos_in_e, cap - 1)].add(
        jnp.where(ok[:, None], x[st], 0.0).astype(x.dtype)
    )
    buf = shard(buf, "moe_expert_in2")
    out_buf = _experts_ffn(params, buf)  # [E, C, d]
    contrib = out_buf[se, jnp.where(ok, pos_in_e, cap - 1)]
    contrib = jnp.where(ok[:, None], contrib * sw[:, None].astype(x.dtype), 0.0)
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib)
    return y, aux


def moe_mlp(params, cfg, x, shard=lambda t, n: t):
    """x: [B, S, d] -> ([B, S, d], aux_loss, counters).  ``counters``
    (assignments per held expert and the row capacity run) come from the
    held dispatch; the capacity dispatches return none."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    counts = {}
    if cfg.moe.experts_held:
        y, counts = _dispatch_held(params, cfg, xt)
        aux = jnp.float32(0.0)
    elif cfg.moe.dispatch == "sort":
        y, aux = _dispatch_sort(params, cfg, xt, shard)
    else:
        y, aux = _dispatch_einsum(params, cfg, xt, shard)
    y = y.reshape(b, s, d)
    if cfg.moe.n_shared_experts:
        sh = params["shared"]
        g = linear(x, sh["w_gate"])
        u = linear(x, sh["w_up"])
        y = y + linear(jax.nn.silu(g) * u, sh["w_down"])
    return shard(y, "act_model"), aux, counts
