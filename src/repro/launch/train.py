"""Production-regime steps.

``make_fl_round_step``  — the paper's federated round as ONE SPMD program:
    shard_map manual over the *client* mesh axis ("data" on a single pod,
    "pod" across pods = cross-silo), auto over the rest (GSPMD handles
    TP/FSDP inside each client group).  U local-SGD steps run with ZERO
    cross-client collectives; the round ends with the DRAG / BR-DRAG
    calibration (per-client scalars, local) + one pmean of the calibrated
    updates over the client axis — exactly FedAvg's communication volume,
    realising the paper's "no extra communication cost" claim in HLO.

``make_train_step``     — standard FSDP+TP training step (baseline infra,
    and the fallback for architectures whose per-client parameter copies
    exceed a client group's HBM — see DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, param_count
from repro.core import pytree as pt
from repro.launch.mesh import batch_axes_of
from repro.models import transformer as T
from repro.optim import get_optimizer
from repro.sharding import rules

EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class FLStepConfig:
    aggregator: str = "drag"  # drag | br_drag | fedavg
    local_steps: int = 1  # U
    lr: float = 1e-2
    alpha: float = 0.25
    c: float = 0.1
    c_br: float = 0.5


def fits_fl_single_pod(cfg: ArchConfig, hbm_per_chip=16e9, tp=16, bytes_per_param=6):
    """Can one 16-chip client group hold a private model copy (+grad/upd)?"""
    return param_count(cfg) * bytes_per_param / tp < 0.85 * hbm_per_chip


# ------------------------------------------------------------- FL round

def _full_rank(spec_prefix, leaf, axis_pos=None):
    """Expand a per-leaf PartitionSpec to the leaf's full rank."""
    pads = leaf.ndim - len(spec_prefix)
    return P(*spec_prefix, *([None] * pads))


def make_fl_round_step(
    arch: ArchConfig,
    mesh,
    client_axis: str,
    fl: FLStepConfig,
    dtype=jnp.bfloat16,
):
    """Returns (step_fn, in_shardings, out_shardings).

    step(params, reference, batch[, root_batch]) ->
        (new_params, new_reference, metrics)
    """
    fsdp = "data" if client_axis == "pod" else None
    pspec = rules.param_spec(arch, fsdp_axis=fsdp, tp_axis="model")
    c_benign, c_byz = fl.c, fl.c_br
    lr, alpha = fl.lr, fl.alpha
    agg = fl.aggregator

    # H3 (§Perf): inside the client group the model axis is an *auto*
    # mesh axis — without explicit constraints GSPMD replicates the model
    # over it and every chip computes the full fwd/bwd (16x redundant
    # compute + a full-size client-axis all-reduce).  Constraining the
    # ACTIVATIONS to the act_specs layout inside the shard_map body is
    # sufficient: GSPMD back-propagates the TP layout onto the weights.
    # (Directly constraining the param tree in-body trips an XLA SPMD
    # partitioner CHECK at 256 devices — see EXPERIMENTS.md §Perf H3.)
    shard = rules.make_shard_fn(mesh, rules.act_specs(arch, None), use_pspec=True)

    def local_loss(p, mb):
        return T.loss_fn(p, arch, mb, shard=shard, remat=True)

    def local_updates(params, batch):
        """U local SGD steps (scan over leading U axis); returns g_m."""

        def step(theta, mb):
            g = jax.grad(local_loss)(theta, mb)
            theta = jax.tree.map(lambda t, gg: t - lr * gg.astype(t.dtype), theta, g)
            return theta, None

        theta_u, _ = jax.lax.scan(step, params, batch)
        return pt.tree_sub(theta_u, params)

    def round_body(params, reference, batch, root_batch=None):
        g = local_updates(params, batch)

        gn = pt.tree_norm(g, EPS)
        if agg == "fedavg":
            v = g
            lam = jnp.float32(0.0)
            new_ref = reference
        else:
            if agg == "br_drag":
                # trusted reference from the root data (computed per client
                # group; identical inputs -> identical result == PS broadcast)
                assert root_batch is not None
                reference = local_updates(params, root_batch)
            rn = pt.tree_norm(reference, EPS)
            cos = pt.tree_dot(g, reference) / (gn * rn)
            if agg == "drag":
                lam = c_benign * (1.0 - cos)
                v = pt.tree_lincomb(1.0 - lam, g, lam * gn / rn, reference)
            else:  # br_drag, eq. (15): norm-clamped to ||r||
                lam = c_byz * (1.0 - cos)
                v = pt.tree_lincomb((1.0 - lam) * rn / gn, g, lam, reference)

        delta = jax.tree.map(lambda x: jax.lax.pmean(x, client_axis), v)

        if agg == "drag":
            new_ref = pt.tree_lincomb(1.0 - alpha, reference, alpha, delta)
        elif agg == "br_drag":
            new_ref = reference  # recomputed fresh each round from D_root
        new_params = pt.tree_add(params, delta)

        metrics = {
            "dod_mean": jax.lax.pmean(lam, client_axis),
            "update_norm_mean": jax.lax.pmean(gn, client_axis),
            "delta_norm": pt.tree_norm(delta),
        }
        return new_params, new_ref, metrics

    # ---- specs
    params_eval = jax.eval_shape(lambda k: T.init_params(k, arch, dtype), jax.random.PRNGKey(0))
    p_sm_spec = jax.tree.map(lambda _: P(), params_eval)  # replicated over client

    def batch_sm_spec(batch_tree):
        # leaves [U, B, ...] -> B sharded over the client axis
        return jax.tree.map(lambda leaf: _full_rank((None, client_axis), leaf), batch_tree)

    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec(params_eval))

    def build(with_root: bool):
        def fn(params, reference, batch, *maybe_root):
            in_specs = (p_sm_spec, p_sm_spec, batch_sm_spec(batch)) + (
                (batch_sm_spec(maybe_root[0]),) if with_root else ()
            )
            # root batch is replicated across clients (same D_root)
            if with_root:
                in_specs = (
                    p_sm_spec,
                    p_sm_spec,
                    batch_sm_spec(batch),
                    jax.tree.map(lambda _: P(), maybe_root[0]),
                )
            out_specs = (p_sm_spec, p_sm_spec, {k: P() for k in ("dod_mean", "update_norm_mean", "delta_norm")})
            # replication checking off: the body mixes manual collectives
            # with GSPMD-auto axes, which the checker rejects
            body = jax.shard_map(
                round_body,
                mesh=mesh,
                axis_names={client_axis},
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
            return body(params, reference, batch, *maybe_root)

        return fn

    with_root = agg == "br_drag"
    fn = build(with_root)
    jitted = jax.jit(fn, donate_argnums=(0,))
    shardings = {
        "params": pshard,
        "reference": pshard,
    }
    return jitted, shardings


# ------------------------------------------------------- standard train

def make_train_step(
    arch: ArchConfig,
    mesh,
    optimizer: str = "adamw",
    lr: float = 3e-4,
    dtype=jnp.bfloat16,
):
    """Standard data-parallel (FSDP) + TP training step; returns
    (step_fn, param_sharding_tree, opt_init)."""
    baxes = batch_axes_of(mesh)
    pspec = rules.param_spec(arch, fsdp_axis="data", tp_axis="model")
    act = rules.act_specs(arch, baxes)
    shard = rules.make_shard_fn(mesh, act)
    opt = get_optimizer(optimizer)

    def loss_fn(p, mb):
        return T.loss_fn(p, arch, mb, shard=shard, remat=True)

    def step(params, opt_state, batch):
        mb = jax.tree.map(lambda x: x[0], batch)  # [U=1, B, ...] -> [B, ...]
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        updates, new_state = opt.update(grads, opt_state, params, lr)
        new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
        return new_params, new_state, {"loss": loss}

    params_eval = jax.eval_shape(lambda k: T.init_params(k, arch, dtype), jax.random.PRNGKey(0))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec(params_eval))
    ostate_eval = jax.eval_shape(opt.init, params_eval)
    # optimizer state shards like params (prefix-matched)
    ospec = rules.param_spec(arch, fsdp_axis="data", tp_axis="model")

    def opt_shardings():
        def per_leaf(path_tree):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), path_tree)

        out = {}
        for k, sub in ostate_eval.items():
            if k == "t":
                out[k] = NamedSharding(mesh, P())
            else:
                out[k] = per_leaf(ospec(sub))
        return out

    oshard = opt_shardings() if isinstance(ostate_eval, dict) else {}
    jitted = jax.jit(step, donate_argnums=(0, 1))
    return jitted, {"params": pshard, "opt": oshard}, opt
