"""The jitted federated round — simulation regime.

One call = one full paper round: S parallel local-SGD clients ->
flatten onto the [S, d] update plane (``repro.core.flat``) -> for
BR-DRAG / FLTrust the root's reference pass -> the server's
aggregation step (``repro.fl.aggregate.server_step``: attack, trust,
the rule through the flat kernels, telemetry, monitor; the flushes of
``repro.stream`` run the same step) -> the FedACG / SCAFFOLD server
state.

``repro.launch.train.make_fl_round_step`` is a further round over mesh
axis groups (collectives instead of vmap); only the dry-run grid
(``repro.launch.dryrun``) runs it, no served path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import drag
from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.fl.aggregate import init_step_states, server_step
from repro.fl.client import local_update, with_counters


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    algorithm: str = "fedavg"  # fedavg|fedprox|scaffold|fedexp|fedacg|drag|
    #                            fltrust|rfa|raga|geomed|krum|multi_krum|
    #                            bulyan|trimmed_mean|median|br_drag
    local_steps: int = 5  # U
    lr: float = 0.01  # eta
    alpha: float = 0.25  # DRAG EMA
    c: float = 0.1  # DRAG DoD coefficient
    c_br: float = 0.5  # BR-DRAG DoD coefficient
    mu: float = 0.2  # FedProx
    acg_beta: float = 0.2  # FedACG local regulariser
    acg_lambda: float = 0.85  # FedACG momentum
    attack: str = "none"  # any repro.adversary registry name
    attack_kw: tuple = ()  # e.g. (("std", 3.0),)
    n_byzantine_hint: int = 0  # for krum / trimmed_mean
    geomed_iters: int = 8
    trust: bool = False  # divergence-history reputation (drag/br_drag)
    trust_kw: tuple = ()  # TrustConfig overrides, e.g. (("decay", 0.9),)
    telemetry: bool = False  # metrics["obs"] = MetricsBundle per round
    #   (repro.obs) — STATIC: off leaves the round jaxpr untouched; on
    #   adds one extra pytree output from already-computed signals
    monitor: object = None  # obs.monitor.MonitorConfig | None — online
    #   change-point detectors over the bundle (requires telemetry=True)
    model_kind: str = "cnn"  # models/factory.Model.kind.  "arch": the
    #   round takes the frozen base as an argument, its loss returns
    #   in-jit counters (reported in the metrics), and the S clients run
    #   under lax.map with their U steps one scanned body (an unrolled
    #   copy per client would multiply a large model's compile time by S)


class ServerState(NamedTuple):
    params: pt.Pytree
    round: jax.Array  # int32
    drag: drag.DragState  # reference EMA (drag) / unused otherwise
    momentum: pt.Pytree  # fedacg server momentum m^t
    control_global: pt.Pytree  # scaffold h
    control_workers: pt.Pytree  # scaffold h_m stacked [M, ...]
    adversary: pt.Pytree = ()  # attack memory (repro.adversary)
    trust: pt.Pytree = ()  # TrustState | () (repro.trust)
    monitor: pt.Pytree = ()  # obs.monitor.MonitorState | () (diagnosis)


def init_server_state(
    params: pt.Pytree, n_workers: int, cfg: RoundConfig | None = None
) -> ServerState:
    # Copy params: the jitted round fn donates the state, and donating a
    # buffer the caller still aliases (e.g. two states built from the same
    # init) would invalidate it out from under them.
    adv_state, trust_state, monitor_state = init_step_states(cfg, n_workers)
    return ServerState(
        params=jax.tree.map(lambda x: jnp.array(x, copy=True), params),
        round=jnp.zeros((), jnp.int32),
        drag=drag.init_state(params),
        momentum=pt.tree_zeros_like(params),
        control_global=pt.tree_zeros_like(params),
        control_workers=jax.tree.map(
            lambda x: jnp.zeros((n_workers,) + x.shape, x.dtype), params
        ),
        adversary=adv_state,
        trust=trust_state,
        monitor=monitor_state,
    )


def _client_updates(loss_fn, state: ServerState, cfg: RoundConfig, batches, selected_idx):
    """vmapped local updates for the S selected workers.

    batches: pytree [S, U, B, ...]; selected_idx: int32 [S] (for scaffold
    per-worker control variates).
    """
    anchor = None
    if cfg.algorithm == "fedacg":
        anchor = pt.tree_axpy(cfg.acg_lambda, state.momentum, state.params)

    def one(args):
        batch_u, widx = args
        kw: dict = {}
        if cfg.algorithm == "scaffold":
            kw["control_local"] = pt.tree_index(state.control_workers, widx)
            kw["control_global"] = state.control_global
        if cfg.algorithm == "fedacg":
            kw["anchor"] = anchor
        variant = {
            "fedprox": "fedprox",
            "scaffold": "scaffold",
            "fedacg": "fedacg",
        }.get(cfg.algorithm, "sgd")
        return local_update(
            loss_fn, state.params, batch_u, cfg.lr,
            variant=variant, mu=cfg.mu, beta=cfg.acg_beta,
            unroll=cfg.model_kind == "cnn", **kw,
        )

    if cfg.model_kind == "arch":
        return jax.lax.map(one, (batches, selected_idx))

    # NOTE: an unrolled python loop over the S selected workers, not vmap
    # and not lax.map — vmap batches the conv *filters* (each client's
    # params diverge during local SGD) which XLA:CPU executes ~17x
    # slower, and while-loops (lax.map/scan) are ~11x slower than
    # straight-line code on the CPU backend.  S is small and static in
    # the paper's protocol.  The dry-run's round parallelises clients
    # over mesh axes instead (repro.launch.train).
    s = jax.tree.leaves(batches)[0].shape[0]
    outs = [one((pt.tree_index(batches, i), selected_idx[i])) for i in range(s)]
    gs = pt.tree_stack([o[0] for o in outs])
    aux = {k: pt.tree_stack([o[1][k] for o in outs]) for k in outs[0][1]}
    return gs, aux


def federated_round(
    loss_fn: Callable,
    state: ServerState,
    cfg: RoundConfig,
    batches,  # [S, U, B, ...]
    selected_idx,  # [S] int32
    malicious_mask,  # [S] bool
    key,
    root_batches=None,  # [U, B, ...] — BR-DRAG / FLTrust root data
    frozen=None,  # the frozen base of an architecture's adapters
) -> tuple[ServerState, dict]:
    if cfg.model_kind == "arch":
        loss = partial(loss_fn, base=frozen)  # (loss, counters)
    else:
        loss = with_counters(loss_fn)

    g_stacked, aux = _client_updates(loss, state, cfg, batches, selected_idx)
    counters = jax.tree.map(lambda c: jnp.sum(c, axis=0), aux.pop("counters"))

    # ---- THE flatten boundary (repro.core.flat): the S uploads enter
    # the flat [S, d] update plane here and stay flat through attack
    # crafting, calibration, trust signals, and reduction; only the
    # aggregated [d] delta is unflattened, once, onto the params
    stack = flat_mod.stack_updates(g_stacked, client_ids=selected_idx)

    r_flat = None
    if cfg.algorithm in ("br_drag", "fltrust"):
        assert root_batches is not None, f"{cfg.algorithm} needs a root dataset"
        # the root trains as a client does: r^t = theta^{t,U} - theta^t
        reference, root_aux = local_update(loss, state.params, root_batches, cfg.lr,
                                           unroll=cfg.model_kind == "cnn")
        counters = jax.tree.map(jnp.add, counters, root_aux["counters"])
        r_flat = flat_mod.flatten_tree(reference)

    step = server_step(
        cfg, state.params, state.drag, state.adversary, state.trust, state.monitor,
        stack, malicious_mask, state.round, key, reference=r_flat,
    )
    metrics = step.metrics
    if cfg.algorithm == "br_drag":
        metrics["dod"] = step.lam  # the per-client DoD the round reports
    new_momentum = state.momentum
    new_h = state.control_global
    new_hm = state.control_workers
    if cfg.algorithm == "fedacg":
        delta = flat_mod.unflatten_tree(step.delta, stack.spec)
        new_momentum = pt.tree_axpy(cfg.acg_lambda, state.momentum, delta)
    if cfg.algorithm == "scaffold":
        n_workers = jax.tree.leaves(state.control_workers)[0].shape[0]
        new_controls = aux["new_control"]  # [S, ...]
        old_controls = jax.vmap(lambda i: pt.tree_index(state.control_workers, i))(
            selected_idx
        )
        # h <- h + (1/M) sum_S (new - old)
        diff = jax.tree.map(lambda a, b: jnp.sum(a - b, 0) / n_workers, new_controls, old_controls)
        new_h = pt.tree_add(state.control_global, diff)
        new_hm = jax.tree.map(
            lambda all_h, upd: all_h.at[selected_idx].set(upd),
            state.control_workers,
            new_controls,
        )
    # in-jit counters of every client's and the root's training steps
    metrics.update(counters)
    new_monitor = state.monitor
    if step.monitor is not None:
        # the verdict is telemetry: the host loop pops it for the session
        new_monitor, metrics["obs_alerts"] = step.monitor
    new_state = ServerState(
        params=step.params,
        round=state.round + 1,
        drag=step.drag,
        momentum=new_momentum,
        control_global=new_h,
        control_workers=new_hm,
        adversary=step.adversary,
        trust=step.trust,
        monitor=new_monitor,
    )
    return new_state, metrics


def make_round_fn(loss_fn, cfg: RoundConfig, with_root: bool):
    """jit-compiled round with static config: ``fn(state, batches,
    selected_idx, malicious_mask, key[, root_batches][, frozen])``, the
    root batches with ``with_root``, the frozen base for an
    architecture (``cfg.model_kind == "arch"``)."""
    n_extra = int(with_root) + int(cfg.model_kind == "arch")

    @partial(jax.jit, donate_argnums=(0,))
    def fn(state, batches, selected_idx, malicious_mask, key, *extra):
        if len(extra) != n_extra:
            raise TypeError(f"the round takes {n_extra} argument(s) after the key, "
                            f"got {len(extra)}")
        return federated_round(
            loss_fn, state, cfg, batches, selected_idx, malicious_mask, key,
            root_batches=extra[0] if with_root else None,
            frozen=extra[-1] if cfg.model_kind == "arch" else None,
        )

    return fn
