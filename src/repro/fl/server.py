"""FL training driver (simulation regime): the paper's full §VI protocol.

Orchestrates: UAR worker selection (partial participation), per-round
data sampling (with label poisoning for malicious workers), the jitted
federated round, and periodic test evaluation.

The driver reads everything from a declarative
:class:`repro.api.ExperimentSpec` (sync regime) and lowers its static
round config through ``repro.api.lowering`` — the one field-copying
path shared with the async engine and the sync<->async bridge.  The
legacy :class:`ExperimentConfig` dataclass is retained as a thin
deprecation shim: it is adopted losslessly into a spec on entry, so
pre-API callers (and their tests) exercise the same code path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import FederatedData
from repro.fl.round import init_server_state, make_round_fn
from repro.models import cnn
from repro.obs import session as obs_session
from repro.obs import trace as obs_trace


@dataclasses.dataclass
class ExperimentConfig:
    """DEPRECATED shim — prefer ``repro.api.ExperimentSpec``.

    Kept so existing entry points and tests double as the API
    redesign's oracle; ``run_experiment`` adopts it via
    ``repro.api.lowering.spec_from_sync_config`` (lossless, including
    the legacy ``attack_kw``/``trust_kw`` tuple-of-pairs).
    """

    dataset: str = "cifar10"
    model: str = "cifar10_cnn"
    n_workers: int = 40  # M
    n_selected: int = 10  # S
    rounds: int = 100  # T
    local_steps: int = 5  # U
    batch_size: int = 10  # B
    lr: float = 0.01
    beta: float = 0.1  # Dirichlet heterogeneity
    algorithm: str = "fedavg"
    attack: str = "none"  # any repro.adversary registry name
    attack_kw: tuple = ()
    malicious_fraction: float = 0.0
    alpha: float = 0.25
    c: float = 0.1
    c_br: float = 0.5
    trust: bool = False  # divergence-history reputation (drag/br_drag)
    trust_kw: tuple = ()
    root_samples: int = 3000
    eval_every: int = 10
    seed: int = 0

    def to_spec(self):
        """The declarative form (``repro.api.ExperimentSpec``)."""
        from repro.api import lowering

        return lowering.spec_from_sync_config(self)


def run_experiment(
    exp,  # repro.api.ExperimentSpec (sync regime) | legacy ExperimentConfig
    data: FederatedData | None = None,
    progress: Callable[[dict], None] | None = None,
    check: bool = True,  # False: spec already validated (api.compile)
) -> dict:
    """Runs the experiment; returns the history: accuracy and update norm
    per eval point, ``final_accuracy``, and the final ``params`` pytree."""
    from repro.api import lowering
    from repro.api.validation import ensure_executable, validate
    from repro.data.pipeline import build_federated_data

    spec = lowering.as_spec(exp)
    if spec.regime.kind != "sync":
        raise ValueError(
            f"run_experiment drives the synchronous regime; got a "
            f"{spec.regime.kind!r} regime — use repro.api.run / "
            "repro.stream.run_stream_experiment"
        )
    if check:
        validate(spec)
        ensure_executable(spec)
    d, regime = spec.data, spec.regime

    rng = np.random.RandomState(spec.seed)
    key = jax.random.PRNGKey(spec.seed)

    if data is None:
        data = build_federated_data(
            d.dataset, d.n_workers, d.beta,
            malicious_fraction=d.malicious_fraction, attack=spec.attack.name,
            seed=spec.seed,
        )

    init_fn, apply_fn = cnn.MODELS[spec.model.name]
    key, k_init = jax.random.split(key)
    if spec.model.name == "mlp":
        in_dim = int(np.prod(data.x.shape[1:]))
        params = init_fn(k_init, in_dim, 64, data.n_classes)
    else:
        params = init_fn(k_init)

    def loss_fn(p, batch):
        return cnn.classification_loss(apply_fn, p, batch)

    # THE sync lowering (repro.api.lowering): spec -> static round config
    cfg = lowering.round_config(spec)
    with_root = cfg.algorithm in ("br_drag", "fltrust")
    round_fn = make_round_fn(loss_fn, cfg, with_root)

    state = init_server_state(params, d.n_workers, cfg)
    eval_jit = jax.jit(lambda p, b: cnn.accuracy(apply_fn, p, b))
    tb = data.test_batch()
    test_x = jnp.asarray(tb["x"])
    test_batch = {"x": test_x, "y": jnp.asarray(tb["y"])}

    # non-stationary drift (DataSpec.drift): labels rotate with the round
    # index; train, root, and eval batches all see the time-t labels
    from repro.data.pipeline import drift_labels

    drift_on = d.drift != "none" and d.drift_rate > 0.0

    session = obs_session.session_from_spec(getattr(spec, "telemetry", None))

    history = {"round": [], "accuracy": [], "update_norm": [], "wall_s": []}
    t0 = time.time()
    with session:
        for t in range(regime.rounds):
            with obs_trace.span("sample_round"):
                selected = rng.choice(d.n_workers, size=regime.n_selected, replace=False)
                batch_np = data.sample_round(rng, selected, regime.local_steps, regime.batch_size)
                y_np = batch_np["y"]
                if drift_on:
                    y_np = drift_labels(y_np, data.n_classes, t, d.drift, d.drift_rate)
                batches = {"x": jnp.asarray(batch_np["x"]), "y": jnp.asarray(y_np)}
                malicious_mask = jnp.asarray(data.malicious[selected])
            key, k_round = jax.random.split(key)
            args = [state, batches, jnp.asarray(selected, jnp.int32), malicious_mask, k_round]
            if with_root:
                root_np = data.root_batches(rng, regime.local_steps, regime.batch_size, d.root_samples)
                root_y = root_np["y"]
                if drift_on:
                    root_y = drift_labels(root_y, data.n_classes, t, d.drift, d.drift_rate)
                args.append({"x": jnp.asarray(root_np["x"]), "y": jnp.asarray(root_y)})
            with obs_trace.span("round", t=t):
                state, metrics = round_fn(*args)
            session.record_alerts(metrics.pop("obs_alerts", None), state.monitor)
            session.record_flush(metrics.pop("obs", None))

            if (t + 1) % regime.eval_every == 0 or t == regime.rounds - 1:
                with obs_trace.span("eval"):
                    tbatch = test_batch
                    if drift_on:
                        tbatch = {
                            "x": test_x,
                            "y": jnp.asarray(drift_labels(
                                tb["y"].astype(np.int32), data.n_classes, t,
                                d.drift, d.drift_rate,
                            )),
                        }
                    acc = float(eval_jit(state.params, tbatch))
                history["round"].append(t + 1)
                history["accuracy"].append(acc)
                history["update_norm"].append(float(metrics["update_norm_mean"]))
                history["wall_s"].append(time.time() - t0)
                if progress:
                    progress({"round": t + 1, "accuracy": acc, **{k: float(v) for k, v in metrics.items()}})

    history["final_accuracy"] = history["accuracy"][-1] if history["accuracy"] else 0.0
    history["params"] = state.params
    if session.enabled:
        history["telemetry"] = session.summary()
    return history
