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


class SyncExperiment:
    """The synchronous engine of one spec: its data, model, server state
    and jitted round, driven one round at a time.  ``run_experiment``
    loops over :meth:`round`; a benchmark drives the same calls.

    Each round is three host spans: ``round.sample`` (client selection,
    batch build and transfer), ``round.dispatch`` (the jitted round call,
    entry to return) and, when the caller waits for the new parameters,
    ``round.wait``.  ``inputs`` holds the last round's host inputs."""

    def __init__(self, exp, data: FederatedData | None = None, check: bool = True):
        from repro.api import lowering
        from repro.api.validation import ensure_executable, validate
        from repro.data.pipeline import build_federated_data
        from repro.models import factory

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
        self.spec = spec
        d = spec.data
        self.rng = np.random.RandomState(spec.seed)
        self.key = jax.random.PRNGKey(spec.seed)
        self.model = factory.build(spec.model)
        if data is None:
            data = build_federated_data(
                d.dataset, d.n_workers, d.beta,
                malicious_fraction=d.malicious_fraction, attack=spec.attack.name,
                seed=spec.seed, seq_len=d.seq_len,
                vocab=self.model.arch.vocab if self.model.arch else 0,
                root_samples=d.root_samples,
            )
        self.data = data
        self.key, k_init = jax.random.split(self.key)
        self.frozen, params = self.model.init(k_init, data)

        # THE sync lowering (repro.api.lowering): spec -> static round config
        self.cfg = lowering.round_config(spec)
        self.with_root = self.cfg.algorithm in ("br_drag", "fltrust")
        self.round_fn = make_round_fn(self.model.loss, self.cfg, self.with_root)
        self.state = init_server_state(params, d.n_workers, self.cfg)
        self.eval_jit = jax.jit(self.model.accuracy)
        tb = data.test_batch()
        self.test_x, self.test_y = jnp.asarray(tb["x"]), tb["y"]
        # non-stationary drift (DataSpec.drift): labels rotate with the round
        # index; train, root, and eval batches all see the time-t labels
        self.drift_on = d.drift != "none" and d.drift_rate > 0.0
        self.session = obs_session.session_from_spec(getattr(spec, "telemetry", None))
        self.inputs: dict = {}

    def _labels(self, y: np.ndarray, t: int) -> np.ndarray:
        from repro.data.pipeline import drift_labels

        d = self.spec.data
        if not self.drift_on:
            return y
        return drift_labels(y, self.data.n_classes, t, d.drift, d.drift_rate)

    def round(self, t: int) -> dict:
        """Round ``t``: sample, dispatch; returns its metrics (not waited on)."""
        d, regime, data = self.spec.data, self.spec.regime, self.data
        with obs_trace.span("round.sample"):
            selected = self.rng.choice(d.n_workers, size=regime.n_selected, replace=False)
            batch_np = data.sample_round(self.rng, selected, regime.local_steps, regime.batch_size)
            batch_np["y"] = self._labels(batch_np["y"], t)
            self.inputs = {"selected": selected, "batches": batch_np,
                           "malicious": data.malicious[selected]}
            batches = {"x": jnp.asarray(batch_np["x"]), "y": jnp.asarray(batch_np["y"])}
            malicious_mask = jnp.asarray(data.malicious[selected])
            self.key, k_round = jax.random.split(self.key)
            args = [self.state, batches, jnp.asarray(selected, jnp.int32), malicious_mask, k_round]
            if self.with_root:
                root_np = data.root_batches(self.rng, regime.local_steps, regime.batch_size,
                                            d.root_samples)
                root_np["y"] = self._labels(root_np["y"], t)
                self.inputs["root"] = root_np
                args.append({"x": jnp.asarray(root_np["x"]), "y": jnp.asarray(root_np["y"])})
            if self.frozen is not None:
                args.append(self.frozen)
        with obs_trace.span("round.dispatch", t=t):
            self.state, metrics = self.round_fn(*args)
        self.session.record_alerts(metrics.pop("obs_alerts", None), self.state.monitor)
        self.session.record_flush(metrics.pop("obs", None))
        return metrics

    def wait(self) -> None:
        """Blocks until the last round's parameters are ready."""
        with obs_trace.span("round.wait"):
            jax.block_until_ready(self.state.params)

    def evaluate(self, t: int) -> float:
        with obs_trace.span("eval"):
            batch = {"x": self.test_x, "y": jnp.asarray(self._labels(self.test_y, t))}
            extra = () if self.frozen is None else (self.frozen,)
            return float(self.eval_jit(self.state.params, batch, *extra))


def run_experiment(
    exp,  # repro.api.ExperimentSpec (sync regime) | legacy ExperimentConfig
    data: FederatedData | None = None,
    progress: Callable[[dict], None] | None = None,
    check: bool = True,  # False: spec already validated (api.compile)
) -> dict:
    """Runs the experiment; returns the history: accuracy and update norm
    per eval point, ``final_accuracy``, and the final ``params`` pytree."""
    run = SyncExperiment(exp, data, check)
    regime = run.spec.regime
    history = {"round": [], "accuracy": [], "update_norm": [], "wall_s": []}
    t0 = time.time()
    with run.session:
        for t in range(regime.rounds):
            metrics = run.round(t)
            if (t + 1) % regime.eval_every == 0 or t == regime.rounds - 1:
                acc = run.evaluate(t)
                history["round"].append(t + 1)
                history["accuracy"].append(acc)
                history["update_norm"].append(float(metrics["update_norm_mean"]))
                history["wall_s"].append(time.time() - t0)
                if progress:
                    progress({"round": t + 1, "accuracy": acc,
                              **{k: float(v) for k, v in metrics.items() if jnp.ndim(v) == 0}})

    history["final_accuracy"] = history["accuracy"][-1] if history["accuracy"] else 0.0
    history["params"] = run.state.params
    if run.session.enabled:
        history["telemetry"] = run.session.summary()
    return history
