"""Client-side local update rules (paper eq. (2) + baseline variants).

``local_update`` runs U SGD steps over a [U, B, ...] batch stack via
``lax.scan`` and returns the *update vector* g_m = theta^{t,U} - theta^t
(what the paper's workers upload).  Variants:

  * ``sgd``      — plain local SGD (FedAvg / DRAG / BR-DRAG workers)
  * ``fedprox``  — + mu * (theta - theta_global) proximal gradient [16]
  * ``scaffold`` — + (h - h_m) control variates [13]
  * ``fedacg``   — + beta * (theta - lookahead) anchor gradient [21]

All variants are vmap-able across the worker axis.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import pytree as pt

LossFn = Callable[[object, dict], tuple]  # (params, batch) -> (scalar, counters)


def with_counters(loss_fn: Callable) -> LossFn:
    """A scalar loss in the form :func:`local_update` takes: no counters."""
    return lambda params, batch: (loss_fn(params, batch), {})


def local_update(
    loss_fn: LossFn,
    params_global: pt.Pytree,
    batches_u: dict,
    lr: float,
    *,
    variant: str = "sgd",
    mu: float = 0.2,  # fedprox
    control_local: pt.Pytree | None = None,  # scaffold h_m
    control_global: pt.Pytree | None = None,  # scaffold h
    anchor: pt.Pytree | None = None,  # fedacg theta^{t-1} + lambda m^{t-1}
    beta: float = 0.2,  # fedacg
    unroll: bool = True,
):
    """Returns (g_m, aux) where aux carries variant-specific outputs and
    ``counters``: the loss's in-jit counters (an architecture's,
    ``models/factory.py``; none for a scalar loss wrapped by
    :func:`with_counters`), summed over the U steps.  ``unroll=False``
    keeps the U steps one scanned body (large models, where unrolling
    multiplies compile time)."""
    grad_fn = jax.grad(loss_fn, has_aux=True)

    def step(theta, batch):
        g, counters = grad_fn(theta, batch)
        if variant == "fedprox":
            g = jax.tree.map(lambda gg, th, gl: gg + mu * (th - gl), g, theta, params_global)
        elif variant == "scaffold":
            g = jax.tree.map(
                lambda gg, hm, h: gg - hm + h, g, control_local, control_global
            )
        elif variant == "fedacg":
            g = jax.tree.map(lambda gg, th, an: gg + beta * (th - an), g, theta, anchor)
        theta = jax.tree.map(lambda th, gg: th - lr * gg, theta, g)
        return theta, counters

    # unroll=True: XLA:CPU executes while-loop bodies ~11x slower than
    # straight-line code (measured; see EXPERIMENTS.md §Perf notes), and U
    # is small and static in the paper's protocol (U=5).
    theta_u, counters = jax.lax.scan(step, params_global, batches_u, unroll=unroll)
    g_m = pt.tree_sub(theta_u, params_global)

    aux = {"counters": jax.tree.map(lambda c: jnp.sum(c, axis=0), counters)}
    if variant == "scaffold":
        # h_m^{t+1} = grad at the *start* point on the first batch (option II
        # of [13] simplified per the paper's §VI baseline description)
        first_batch = jax.tree.map(lambda x: x[0], batches_u)
        aux["new_control"] = grad_fn(params_global, first_batch)[0]
    return g_m, aux
