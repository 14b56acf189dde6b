"""The server's aggregation step: a stack of client rows becomes one global step.

Every engine ends a round or a flush here — the sync round
(``repro.fl.round.federated_round``), the single-buffer flush and the
sharded flush (``repro.stream.server.flush``, which the compiled
megastep runs inside its scan).  :func:`server_step` runs, in order:

  1. the adversary's update-space attack over the honest flat stack;
  2. the trust gate: reputations from PAST rounds' divergence history;
  3. the rule: DRAG / BR-DRAG through ``core/drag.round_step_flat`` /
     ``core/br_drag.round_step_flat`` (the flush kernels), FLTrust, or
     any other rule of the flat registry;
  4. this round's trust observation, from the kernels' phase-1 scalars;
  5. the update norms and the metrics;
  6. the telemetry bundle and the change-point monitor.

What differs between the engines arrives as an input, never as a
branch on the caller: the staleness discounts (None in the sync round),
the buffer facts telemetry reports (None: a round of S fresh rows), and
the ``reduce`` the calibrated rules run (``kops.calibrated_reduce`` over
one ``[S, d]`` stack; the sharded plane's per-pod reduction with one
psum, ``repro.stream.sharded.pod_reduce``).  Client training, the root
reference, the FedACG/SCAFFOLD post-steps, buffer views and resets, and
where the monitor's output is kept stay with each engine.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.adversary import engine as adversary_engine
from repro.core import aggregators, br_drag, drag
from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.kernels import ops as kops
from repro.obs import metrics as obs_metrics
from repro.obs import monitor as obs_monitor
from repro.trust import reputation as trust_mod


class Step(NamedTuple):
    """What :func:`server_step` hands back to its engine."""

    params: pt.Pytree
    drag: drag.DragState
    adversary: pt.Pytree
    trust: pt.Pytree
    metrics: dict
    monitor: tuple | None  # (MonitorState, verdict) with a monitor, else None
    delta: jax.Array | None  # the [d] step of a rule without a DRAG body
    lam: jax.Array | None  # [S] per-row DoD of BR-DRAG, else None


def init_step_states(cfg, n_clients: int | None) -> tuple:
    """(adversary memory, trust table, monitor state) that
    :func:`server_step` carries for ``cfg``; all empty without a config —
    fine for stateless attacks with trust off, enforced in the step."""
    if cfg is None:
        return (), (), ()
    adv_state = adversary_engine.resolve(cfg.attack, dict(cfg.attack_kw)).init()
    trust_state = ()
    if cfg.trust:
        if not n_clients:
            raise ValueError("cfg.trust=True needs n_clients for the trust table")
        trust_state = trust_mod.init_trust(n_clients)
    monitor_state = (
        obs_monitor.monitor_init() if cfg.telemetry and cfg.monitor is not None else ()
    )
    return adv_state, trust_state, monitor_state


def server_step(
    cfg,  # RoundConfig | StreamConfig — the fields both have
    params: pt.Pytree,
    drag_state: drag.DragState,
    adv_state: pt.Pytree,
    trust_state: pt.Pytree,
    monitor_state: pt.Pytree,
    stack: flat_mod.UpdateStack,  # the honest [S, d] rows, before any attack
    malicious,  # [S] bool
    rnd,  # [] int32 — the server version t
    key,
    *,
    reference=None,  # [d] flat r^t for br_drag / fltrust
    discounts=None,  # [S] phi(tau) | None (fresh rows)
    facts: dict | None = None,  # fill / capacity / drops / pod_fill
    reduce=kops.calibrated_reduce,
) -> Step:
    """One global step from the stack; sizes come from the stack itself.

    With ``discounts`` the rows carry staleness (``stack.staleness``):
    the adversary sees both, the calibration discounts by them and the
    metrics report them.  A ``reduce`` other than
    ``kops.calibrated_reduce`` serves fedavg as its mean (mode "mean")
    beside DRAG and BR-DRAG; the flat registry serves only the single
    stack.
    """
    s = stack.data.shape[0]
    taus = None if discounts is None else stack.staleness

    # ---- Byzantine update-space attack: the adversary sees the honest
    # stack (omniscient threat model), and the staleness tags and
    # discounts it may hide behind
    adv = adversary_engine.resolve(cfg.attack, dict(cfg.attack_kw))
    if jax.tree.structure(adv_state) != jax.tree.structure(adv.init()):
        raise ValueError(
            f"attack {cfg.attack!r} carries state; build the engine's state "
            "from this config (init_step_states)"
        )
    ctx = adversary_engine.AttackContext(
        key=key, updates=stack.data, malicious_mask=malicious, round=rnd,
        taus=taus, discounts=discounts, spec=stack.spec,
    )
    g, new_adv = adv.craft(adv_state, ctx)
    stack = dataclasses.replace(stack, data=g)

    # ---- trust layer: reputation weights from PAST rounds' divergence
    # history weight this round's aggregation; this round's divergences
    # are folded into the history afterwards
    use_trust = cfg.trust and cfg.algorithm in ("drag", "br_drag")
    if cfg.trust and not use_trust:
        raise ValueError(
            f"trust reputation needs a reference direction; algorithm "
            f"{cfg.algorithm!r} has none (use drag or br_drag)"
        )
    if use_trust and not isinstance(trust_state, trust_mod.TrustState):
        raise ValueError(
            "cfg.trust=True needs a trust table; build the engine's state "
            "from this config and the client count (init_step_states)"
        )
    tcfg = trust_mod.TrustConfig(**dict(cfg.trust_kw)) if use_trust else None
    weights = (
        # the stack's client ids are the single source the table indexes by
        trust_mod.reputation(trust_state, stack.client_ids, tcfg) if use_trust else None
    )

    metrics: dict = {} if discounts is None else {
        "staleness_mean": jnp.mean(taus.astype(jnp.float32)),
        "staleness_max": jnp.max(taus),
        "discount_mean": jnp.mean(discounts),
    }
    new_drag = drag_state
    new_trust = trust_state
    stats = lam = delta_flat = None

    if cfg.algorithm == "drag":
        params, new_drag, dm, stats = drag.round_step_flat(
            params, drag_state, stack, alpha=cfg.alpha, c=cfg.c,
            discounts=discounts, weights=weights, reduce=reduce,
        )
        metrics.update(dm)
    elif cfg.algorithm == "br_drag":
        params, dm, stats, lam = br_drag.round_step_flat(
            params, stack, reference, c=cfg.c_br, discounts=discounts,
            weights=weights, reduce=reduce,
        )
        metrics.update(dm)
    elif cfg.algorithm == "fltrust":
        delta_flat = aggregators.fltrust_flat(g, reference)
    else:
        # registry-driven dispatch: every non-reference rule is reachable
        # by name through the FLAT tier; the client-side variants
        # (fedprox/scaffold/fedacg) reduce with the plain mean
        rule = "fedavg" if cfg.algorithm in aggregators.MEAN_REDUCED else cfg.algorithm
        if rule not in aggregators.FLAT_CAPABLE or rule in aggregators.NEEDS_REFERENCE:
            raise ValueError(f"unknown algorithm {cfg.algorithm}")
        if reduce is kops.calibrated_reduce:
            delta_flat = aggregators.FLAT_AGGREGATORS[rule](
                g,
                **aggregators.rule_kwargs(
                    rule, n_byzantine=cfg.n_byzantine_hint, geomed_iters=cfg.geomed_iters
                ),
            )
        else:
            # a plane with its own reduce takes the mean through it; its
            # phase-1 g_sq gives the row norms for free
            delta_flat, _, stats = reduce(
                g, jnp.zeros((stack.d,), jnp.float32), 0.0, "mean",
                w=kops.normalize_weights(None, s),
            )
    if delta_flat is not None:
        params = pt.tree_add(params, flat_mod.unflatten_tree(delta_flat, stack.spec))
        metrics["delta_norm"] = jnp.linalg.norm(delta_flat)

    calibrated = cfg.algorithm in ("drag", "br_drag")
    # [S] row norms; free from the kernel stats when there are any
    update_norms = jnp.linalg.norm(g, axis=1) if stats is None else jnp.sqrt(stats[1])
    if use_trust:
        div, nr = trust_mod.signals_from_stats(*stats)
        # no reference on DRAG's bootstrap round -> no observation
        new_trust = trust_mod.observe(
            trust_state, stack.client_ids, div, nr, tcfg,
            gate=drag_state.initialized if cfg.algorithm == "drag" else True,
        )
        metrics["trust_weight_mean"] = jnp.mean(weights)
        metrics["quarantined"] = jnp.sum(new_trust.quarantined.astype(jnp.int32))
    metrics["update_norm_mean"] = jnp.mean(update_norms)

    monitor = None
    if cfg.telemetry:
        # a round without a buffer: fill = capacity = S, nothing dropped
        facts = {"fill": s, "capacity": s} if facts is None else facts
        metrics["obs"] = obs_metrics.flush_bundle(
            rnd=rnd, **facts, taus=taus, discounts=discounts,
            stats=stats if calibrated else None, update_norms=update_norms,
            reputations=weights, trust_state=new_trust if use_trust else None,
            c=cfg.c if cfg.algorithm == "drag" else cfg.c_br,
            mode=cfg.algorithm if calibrated else "none",
        )
        if cfg.monitor is not None:
            # detectors read ONLY the already-reduced bundle
            mstate = monitor_state if monitor_state != () else obs_monitor.monitor_init()
            monitor = obs_monitor.monitor_step(mstate, metrics["obs"], cfg.monitor)
    return Step(params, new_drag, new_adv, new_trust, metrics, monitor, delta_flat, lam)
