"""Staleness-aware DRAG / BR-DRAG calibration.

A buffered-async server aggregates updates computed against *older*
model versions.  The update of a client dispatched at version t - tau_m
drifts away from the current reference direction r^t for two compounding
reasons: its data heterogeneity (what DoD already measures) and its
staleness.  We fold the second into the first with a discount

    lambda_m = c * (1 - cos(g_m, r^t)) * phi(tau_m)          (eq. 10 x phi)

where phi is a staleness discount (:data:`DISCOUNTS`): ``poly``
phi(tau) = (1 + tau)^-a (FedBuff-style polynomial), ``exp``
phi(tau) = e^(-a tau), or ``none`` (phi = 1).  Every phi satisfies
phi(0) = 1, so a fresh update is calibrated exactly per the paper's
eq. (10)/(11) (DRAG) or eq. (15)/(16) (BR-DRAG) — the sync bridge in
``repro.fl.bridge`` checks this bit-for-bit.

Shrinking lambda for very stale updates is deliberate: lambda > 1 flips
the g_m term's sign (Fig. 2), an aggressive correction that is only
trustworthy when g_m and r^t describe the *same* model version.  For a
stale update the calibrated vector is kept closer to the raw upload while
the BR-DRAG norm clamp (||v_m|| <= ||r||) still bounds its influence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import br_drag, drag
from repro.core import pytree as pt


# ---------------------------------------------------------------- phi(tau)
def _phi_none(tau, a):
    del a
    return jnp.ones(jnp.shape(tau), jnp.float32)


def _phi_poly(tau, a):
    return (1.0 + tau.astype(jnp.float32)) ** (-a)


def _phi_exp(tau, a):
    return jnp.exp(-a * tau.astype(jnp.float32))


DISCOUNTS = {"none": _phi_none, "poly": _phi_poly, "exp": _phi_exp}


def make_discount(name: str, a: float = 0.5):
    """Returns phi: tau[int array] -> discount[float32 array], phi(0) = 1."""
    if name not in DISCOUNTS:
        raise KeyError(f"unknown discount {name!r}; have {sorted(DISCOUNTS)}")
    fn = DISCOUNTS[name]
    return lambda tau: fn(jnp.asarray(tau), a)


# ----------------------------------------------------- calibrated flushes
# The discounted calibration itself lives in core (``drag.aggregate`` /
# ``br_drag.aggregate`` grew a ``discounts`` parameter) so the sync and
# async paths share ONE implementation — these wrappers just fix the
# async argument order.  With discounts = 1 they match the synchronous
# calls bit-for-bit.
#
# These are the PYTREE-ORACLE forms.  Every serving flush runs the flat
# update plane instead: ``repro.fl.aggregate.server_step`` hands phi(tau)
# and the trust weights to ``drag.round_step_flat`` /
# ``br_drag.round_step_flat``, which fold them into the flush kernels.


def drag_aggregate(
    updates_stacked: pt.Pytree, r: pt.Pytree, c, discounts, weights=None
) -> tuple[pt.Pytree, jax.Array]:
    """Staleness-aware DRAG flush: eq. (11) with lambda_m discounted."""
    return drag.aggregate(updates_stacked, r, c, discounts, weights)


def br_drag_aggregate(
    updates_stacked: pt.Pytree, r: pt.Pytree, c, discounts, weights=None
) -> tuple[pt.Pytree, jax.Array]:
    """Staleness-aware BR-DRAG flush: eq. (15) with lambda_m discounted."""
    return br_drag.aggregate(updates_stacked, r, c, discounts, weights)


def drag_round_step(
    params: pt.Pytree,
    state: drag.DragState,
    updates_stacked: pt.Pytree,
    discounts,
    *,
    alpha: float,
    c: float,
    weights=None,
) -> tuple[pt.Pytree, drag.DragState, dict]:
    """Async analogue of ``drag.round_step`` (same bootstrap semantics:
    the t = 0 flush applies the raw mean and seeds r^0, eq. 5a).
    ``weights`` are trust reputations (``repro.trust``); None = uniform."""
    return drag.round_step(
        params, state, updates_stacked, alpha=alpha, c=c,
        discounts=discounts, weights=weights,
    )


def br_drag_round_step(
    params: pt.Pytree,
    updates_stacked: pt.Pytree,
    reference: pt.Pytree,
    discounts,
    *,
    c: float,
    weights=None,
) -> tuple[pt.Pytree, dict]:
    """Async analogue of ``br_drag.round_step`` given the trusted r^t.
    ``weights`` are trust reputations (``repro.trust``); None = uniform."""
    return br_drag.round_step(
        params, updates_stacked, reference, c=c, discounts=discounts,
        weights=weights,
    )
