"""DRAG — DiveRgence-based Adaptive aGgregation (paper §III).

Implements, over parameter pytrees:

  * the momentum-style global reference direction r^t          (eqs. 5a/5b/8)
  * the degree-of-divergence (DoD) lambda_m^t                  (eq. 10)
  * the calibrated ("dragged") local update v_m^t              (eq. 11)
  * the server aggregation Delta^t and model update            (eqs. 6/7)

Everything is jit-compatible.  Worker updates are carried stacked along a
leading worker axis S (``tree_stack``), which maps 1:1 onto either a vmap
axis (simulation regime) or a mesh axis (production regime, see
``repro.fl.round`` / ``repro.launch``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.kernels import ops as kops

EPS = 1e-12


class DragState(NamedTuple):
    """Server-side state retained across rounds (Alg. 1 step 18)."""

    reference: pt.Pytree  # r^t
    initialized: jax.Array  # bool scalar: False until t=0 bootstraps r


def init_state(params: pt.Pytree) -> DragState:
    return DragState(
        reference=pt.tree_zeros_like(params),
        initialized=jnp.asarray(False),
    )


def degree_of_divergence(g: pt.Pytree, r: pt.Pytree, c, discount=1.0) -> jax.Array:
    """DoD lambda_m^t = c * (1 - cos(g_m, r)) * phi  in [0, 2c]   (eq. 10).

    ``discount`` is the staleness factor phi(tau_m) used by the async
    engine (``repro.stream.staleness``); the default 1.0 — a fresh update,
    tau = 0 — recovers the paper's synchronous eq. (10) exactly (x * 1.0
    is bit-exact in IEEE float).
    """
    return c * (1.0 - pt.cosine_similarity(g, r, EPS)) * discount


def calibrate(g: pt.Pytree, r: pt.Pytree, lam, eps: float = EPS) -> pt.Pytree:
    """DRAG modified gradient (eq. 11).

    v = (1 - lam) * g + lam * (||g|| / ||r||) * r

    The aligned component of v along r is never smaller than that of g
    (Fig. 2); for lam > 1 (severe divergence) the g term flips sign,
    enforcing adherence to the reference direction.
    """
    scale = pt.tree_norm(g, eps) / pt.tree_norm(r, eps)
    return pt.tree_lincomb(1.0 - lam, g, lam * scale, r)


def calibrate_worker(g: pt.Pytree, r: pt.Pytree, c) -> tuple[pt.Pytree, jax.Array]:
    """Per-worker step 15-16 of Alg. 1: DoD then calibrated update."""
    lam = degree_of_divergence(g, r, c)
    return calibrate(g, r, lam), lam


def aggregate(
    updates_stacked: pt.Pytree, r: pt.Pytree, c, discounts=None, weights=None
) -> tuple[pt.Pytree, jax.Array]:
    """Calibrate a stacked [S, ...] update pytree and average (eq. 6).

    ``discounts`` (optional [S] float32) are per-update staleness factors
    phi(tau_m) from the async engine (``repro.stream.staleness``); None
    means fresh updates — folded into phi = 1, which recovers the
    synchronous paper setting bit-for-bit (x * 1.0 is exact in IEEE
    float), so fresh and discounted updates share ONE code path.

    ``weights`` (optional [S] float32) are cross-round reputation weights
    from the trust layer (``repro.trust``): the aggregate becomes the
    reputation-weighted mean of the calibrated updates.  None = the
    paper's uniform mean, bit-for-bit.

    Returns (Delta^t, lambdas[S]).
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


def update_reference(state: DragState, delta: pt.Pytree, raw_mean: pt.Pytree, alpha) -> DragState:
    """Advance r^t per eqs. (5a)/(5b).

    t = 0:  r^0 = mean of raw local updates (5a) — ``raw_mean``.
    t >= 1: r^t = (1-alpha) r^{t-1} + alpha * Delta^{t-1} (5b).
    """
    ema = pt.tree_lincomb(1.0 - alpha, state.reference, alpha, delta)
    new_r = pt.tree_where(state.initialized, ema, raw_mean)
    return DragState(reference=new_r, initialized=jnp.asarray(True))


def round_step(
    params: pt.Pytree,
    state: DragState,
    updates_stacked: pt.Pytree,
    *,
    alpha: float,
    c: float,
    discounts=None,
    weights=None,
) -> tuple[pt.Pytree, DragState, dict]:
    """One full DRAG server round given the S raw worker updates.

    Matches Alg. 1: on the bootstrap round the raw FedAvg mean both forms
    r^0 and is applied directly (the paper computes r^0 from the round-0
    uploads, eq. 5a); afterwards workers calibrate against r^t and the PS
    applies Delta^t and rolls the EMA.  ``discounts``/``weights`` as in
    :func:`aggregate` (async staleness factors / trust reputations; None
    = the synchronous, trust-free paper setting).  The bootstrap round
    is always the uniform raw mean — no reference yet means no
    divergence history to weight by.
    """
    raw_mean = jax.tree.map(lambda x: jnp.mean(x, axis=0), updates_stacked)

    def bootstrap(_):
        lam0 = jnp.zeros(jax.tree.leaves(updates_stacked)[0].shape[0], jnp.float32)
        return raw_mean, lam0

    def calibrated(_):
        return aggregate(updates_stacked, state.reference, c, discounts, weights)

    delta, lams = jax.lax.cond(state.initialized, calibrated, bootstrap, None)
    new_params = pt.tree_add(params, delta)
    new_state = update_reference(state, delta, raw_mean, alpha)
    metrics = {
        "dod_mean": jnp.mean(lams),
        "dod_max": jnp.max(lams),
        "delta_norm": pt.tree_norm(delta),
        "ref_norm": pt.tree_norm(new_state.reference),
    }
    return new_params, new_state, metrics


# ------------------------------------------------------- flat update plane

def aggregate_flat(
    g: jax.Array, r: jax.Array, c, discounts=None, weights=None, interpret=None
) -> tuple[jax.Array, jax.Array, tuple]:
    """:func:`aggregate` on the flat plane: G [S, d], r [d].

    Dispatches to the fused Pallas kernels (``repro.kernels.ops``) —
    one ``fused_flush`` pass for VMEM-resident stacks, else the two
    streaming passes.  Returns (delta [d] f32, lam [S],
    (dots, g_sq, r_sq)); the phase-1 stats feed the trust layer's
    divergence signals for free (``trust.signals_from_stats``).
    """
    return kops.drag_calibrate_reduce(
        g, r, c, "drag", discounts=discounts, weights=weights, interpret=interpret
    )


def round_step_flat(
    params: pt.Pytree,
    state: DragState,
    stack: flat_mod.UpdateStack,
    *,
    alpha: float,
    c: float,
    discounts=None,
    weights=None,
    interpret=None,
    reduce=kops.calibrated_reduce,
) -> tuple[pt.Pytree, "DragState", dict, tuple]:
    """:func:`round_step` on the flat plane — the serving path.

    Same semantics (bootstrap = uniform raw mean seeding r^0, eq. 5a;
    afterwards calibrated weighted mean + reference EMA, eqs. 5b/6/10/11)
    but expressed through ``kops.calibrated_reduce`` — ONE fused HBM pass
    for VMEM-resident stacks, two streaming passes otherwise
    (``kops.flush_path``): the bootstrap switch is a select on the
    [S]-sized blend coefficients, never a separate raw-mean pass, and the
    reference round-trips through its flat form so only [d]-sized
    vectors are unflattened.  ``reduce`` is the plane's calibrated
    reduction (``kops.calibrated_reduce``'s signature; the sharded plane
    passes its per-pod one, ``repro.stream.sharded.pod_reduce``).

    Returns (params', state', metrics, (dots, g_sq, r_sq)) — the stats
    are against the PRE-update reference, exactly what the trust layer
    observes.
    """
    g = stack.data
    s = g.shape[0]
    r_flat = flat_mod.flatten_tree(state.reference)
    w = kops.normalize_weights(weights, s)
    init = state.initialized
    # bootstrap (eq. 5a): uniform raw mean — a = 1, b = 0, w = 1/S
    delta_flat, lam, (dots, gsq, rsq) = reduce(
        g, r_flat, c, "drag", w=w, discounts=discounts, init=init,
        boot_aw=jnp.full((s,), 1.0 / s, jnp.float32), interpret=interpret,
    )
    ema = (1.0 - alpha) * r_flat + alpha * delta_flat
    new_ref_flat = jnp.where(init, ema, delta_flat)
    new_params = pt.tree_add(params, flat_mod.unflatten_tree(delta_flat, stack.spec))
    new_state = DragState(
        reference=flat_mod.unflatten_tree(new_ref_flat, stack.spec),
        initialized=jnp.asarray(True),
    )
    metrics = {
        "dod_mean": jnp.mean(lam),
        "dod_max": jnp.max(lam),
        "delta_norm": jnp.linalg.norm(delta_flat),
        "ref_norm": jnp.linalg.norm(new_ref_flat),
    }
    return new_params, new_state, metrics, (dots, gsq, rsq)
