"""BR-DRAG — Byzantine-Resilient DRAG (paper §IV).

Differences from DRAG:

  * the reference direction r^t comes from ``U`` SGD steps on a vetted
    root dataset held by the PS (eq. 13), not from worker uploads;
  * the calibration normalizes the *worker* update onto ||r|| (eq. 15):

        v_m = (1 - lam_m) * (||r|| / ||g_m||) * g_m + lam_m * r,
        lam_m = c^t * (1 - cos(g_m, r))                       (eq. 16)

    which bounds ||v_m|| <= ||r|| (triangle inequality, used to bound T_3
    in Appendix B) — attackers cannot dominate the aggregate by inflating
    update norms, and misaligned directions are rotated toward r.

The PS performs the calibration itself (Alg. 2 step 8), so workers upload
raw g_m; this matters for the threat model (a malicious worker cannot lie
about its own lambda).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.core.drag import EPS, degree_of_divergence
from repro.kernels import ops as kops


class BRDragConfig(NamedTuple):
    c: float = 0.5  # c^t; may be scheduled per round (paper §V-B)
    local_steps: int = 5  # U — root-dataset SGD steps for r^t
    lr: float = 0.01  # eta for the root pass


def calibrate(g: pt.Pytree, r: pt.Pytree, lam, eps: float = EPS) -> pt.Pytree:
    """BR-DRAG modified gradient (eq. 15): norm-clamped to ||r||."""
    scale = pt.tree_norm(r, eps) / pt.tree_norm(g, eps)
    return pt.tree_lincomb((1.0 - lam) * scale, g, lam, r)


def calibrate_worker(g: pt.Pytree, r: pt.Pytree, c) -> tuple[pt.Pytree, jax.Array]:
    lam = degree_of_divergence(g, r, c)
    return calibrate(g, r, lam), lam


def aggregate(
    updates_stacked: pt.Pytree, r: pt.Pytree, c, discounts=None, weights=None
) -> tuple[pt.Pytree, jax.Array]:
    """PS-side calibration of all S uploads + mean (eq. 14).

    ``discounts`` (optional [S] float32) are staleness factors phi(tau_m)
    from the async engine; None is folded into phi = 1 (bit-exact the
    synchronous paper form — one code path, no fresh/stale branch).
    ``weights`` (optional [S] float32) are trust reputations
    (``repro.trust``) making the aggregate a reputation-weighted mean of
    the calibrated updates; None = the paper's uniform mean, bit-for-bit.
    """
    s = jax.tree.leaves(updates_stacked)[0].shape[0]
    phi = jnp.ones((s,), jnp.float32) if discounts is None else discounts

    def one(g, phi_m):
        lam = degree_of_divergence(g, r, c, phi_m)
        return calibrate(g, r, lam), lam

    vs, lams = jax.vmap(one)(updates_stacked, phi)
    if weights is None:
        delta = jax.tree.map(lambda x: jnp.mean(x, axis=0), vs)
    else:
        delta = pt.tree_weighted_mean(vs, weights)
    return delta, lams


def root_reference(
    params: pt.Pytree,
    grad_fn: Callable[[pt.Pytree, object], pt.Pytree],
    root_batches,
    lr: float,
) -> pt.Pytree:
    """Trusted reference direction r^t = theta^{t,U} - theta^t (eqs. 12/13).

    ``root_batches`` is a pytree of arrays with a leading U axis, each
    slice an independent mini-batch from D_root.  ``grad_fn(params, batch)``
    returns dF/dparams.
    """

    def body(theta, batch):
        g = grad_fn(theta, batch)
        return jax.tree.map(lambda p, d: p - lr * d, theta, g), None

    theta_u, _ = jax.lax.scan(body, params, root_batches)
    return pt.tree_sub(theta_u, params)


def round_step(
    params: pt.Pytree,
    updates_stacked: pt.Pytree,
    reference: pt.Pytree,
    *,
    c: float,
    discounts=None,
    weights=None,
) -> tuple[pt.Pytree, dict]:
    """One BR-DRAG server round given uploads and the trusted r^t."""
    delta, lams = aggregate(updates_stacked, reference, c, discounts, weights)
    new_params = pt.tree_add(params, delta)
    metrics = {
        "dod_mean": jnp.mean(lams),
        "dod_max": jnp.max(lams),
        "delta_norm": pt.tree_norm(delta),
        "ref_norm": pt.tree_norm(reference),
    }
    return new_params, metrics


# ------------------------------------------------------- flat update plane

def aggregate_flat(
    g: jax.Array, r: jax.Array, c, discounts=None, weights=None, interpret=None
) -> tuple[jax.Array, jax.Array, tuple]:
    """:func:`aggregate` on the flat plane: G [S, d], r [d].

    Two HBM passes over G via the fused kernels; returns (delta [d] f32,
    lam [S], (dots, g_sq, r_sq)) — the stats feed
    ``trust.signals_from_stats`` so the trust layer costs no extra pass.
    """
    return kops.drag_calibrate_reduce(
        g, r, c, "br_drag", discounts=discounts, weights=weights, interpret=interpret
    )


def round_step_flat(
    params: pt.Pytree,
    stack: flat_mod.UpdateStack,
    reference_flat: jax.Array,
    *,
    c: float,
    discounts=None,
    weights=None,
    interpret=None,
) -> tuple[pt.Pytree, dict, tuple]:
    """:func:`round_step` on the flat plane given the flat trusted r^t.

    Returns (params', metrics, (dots, g_sq, r_sq), lam [S])."""
    delta_flat, lams, stats = aggregate_flat(
        stack.data, reference_flat, c, discounts, weights, interpret=interpret
    )
    new_params = pt.tree_add(params, flat_mod.unflatten_tree(delta_flat, stack.spec))
    metrics = {
        "dod_mean": jnp.mean(lams),
        "dod_max": jnp.max(lams),
        "delta_norm": jnp.linalg.norm(delta_flat),
        "ref_norm": jnp.linalg.norm(reference_flat),
    }
    return new_params, metrics, stats, lams


def c_schedule(w: float, x: float) -> float:
    """Theorem 2 choice c^t = w^t / (w^t - x^t), clipped into [1/2, 1].

    ``w`` is the attack intensity (fraction of selected workers that are
    malicious) and ``x`` the mean attacker cosine alignment; the PS rarely
    knows either, so this is exposed for experiments/ablations while the
    default c^t = 0.5 matches the paper's experiment section.
    """
    denom = max(w - x, 1e-6)
    return float(min(1.0, max(0.5, w / denom)))
