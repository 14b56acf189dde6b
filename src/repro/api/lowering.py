"""Lowering: ExperimentSpec -> the engines' static configs.

THE one code path for the field copying that previously lived, hand
rolled and drifting, in ``fl/server.py``, ``fl/bridge.py``, and
``adversary/scenarios.py``:

  * :func:`round_config`  — sync regime  -> ``repro.fl.round.RoundConfig``
  * :func:`stream_config` — async/sharded -> ``repro.stream.server.StreamConfig``
  * :func:`stream_config_from_round` — the sync<->async bridge's
    RoundConfig -> StreamConfig conversion, routed through a spec so the
    bridge's bit-for-bit equivalence proof exercises this lowering.

Plus the legacy shims: :func:`as_spec` adopts the pre-API experiment
dataclasses (``repro.fl.server.ExperimentConfig``,
``repro.stream.server.StreamExperimentConfig``) losslessly, so every
existing entry point constructs its run from an ExperimentSpec and the
old tests double as this redesign's oracle.

Boundary rule: lowering is a PURE field mapping — no validation, no
defaulting beyond the documented ``n_byzantine_hint`` policy.
Validation happens once, in :mod:`repro.api.validation`, before any
engine config exists.
"""
from __future__ import annotations

from typing import Mapping

from repro.api.spec import (
    AggregationSpec,
    AsyncRegime,
    AttackSpec,
    DataSpec,
    ExperimentSpec,
    ModelSpec,
    ShardedRegime,
    SyncRegime,
    TrustSpec,
)
from repro.fl.round import RoundConfig
from repro.models.factory import is_arch
from repro.stream.server import StreamConfig


def kw_tuple(kw: Mapping) -> tuple:
    """Spec kwargs dict -> the engines' hashable static tuple-of-pairs
    (insertion order preserved, so dict -> tuple -> dict round-trips)."""
    return tuple((k, v) for k, v in kw.items())


def byzantine_hint(spec: ExperimentSpec) -> int:
    """The shared trim-level policy: an explicit
    ``AggregationSpec.n_byzantine_hint`` wins; otherwise derive from the
    malicious fraction over the aggregation group (S selected workers
    sync, K buffer slots async) — 0 under a benign config (krum /
    trimmed_mean must not trim an honest worker when nothing is
    malicious), >= 1 once any fraction is."""
    if spec.aggregation.n_byzantine_hint is not None:
        return int(spec.aggregation.n_byzantine_hint)
    mf = spec.data.malicious_fraction
    group = (
        spec.regime.n_selected
        if spec.regime.kind == "sync"
        else spec.regime.buffer_capacity
    )
    return max(int(mf * group), 1) if mf > 0 else 0


def monitor_config(spec: ExperimentSpec):
    """Diagnosis-layer lowering: MonitorSpec -> ``obs.monitor.MonitorConfig``
    (or None — the default — which keeps the flush jaxpr monitor-free)."""
    tel = spec.telemetry
    mon = tel.monitor
    if not (tel.enabled and tel.metrics and mon.enabled):
        return None
    from repro.obs.monitor import MonitorConfig

    return MonitorConfig(
        ewma_alpha=mon.ewma_alpha,
        cusum_k=mon.cusum_k,
        cusum_h=mon.cusum_h,
        ph_delta=mon.ph_delta,
        ph_lambda=mon.ph_lambda,
        warmup=mon.warmup,
        min_sigma=mon.min_sigma,
    )


# -------------------------------------------------------------- engine configs
def round_config(spec: ExperimentSpec) -> RoundConfig:
    """Sync lowering: the jitted federated round's static config."""
    agg, regime = spec.aggregation, spec.regime
    return RoundConfig(
        algorithm=agg.algorithm,
        local_steps=regime.local_steps,
        lr=regime.lr,
        alpha=agg.alpha,
        c=agg.c,
        c_br=agg.c_br,
        mu=agg.mu,
        acg_beta=agg.acg_beta,
        acg_lambda=agg.acg_lambda,
        attack=spec.attack.name,
        attack_kw=kw_tuple(spec.attack.kwargs),
        n_byzantine_hint=byzantine_hint(spec),
        geomed_iters=agg.geomed_iters,
        trust=spec.trust.enabled,
        trust_kw=kw_tuple(spec.trust.kwargs),
        telemetry=spec.telemetry.enabled and spec.telemetry.metrics,
        monitor=monitor_config(spec),
        model_kind="arch" if is_arch(spec.model.name) else "cnn",
    )


def stream_config(spec: ExperimentSpec) -> StreamConfig:
    """Async/sharded lowering: the jitted ingest/flush steps' config."""
    agg, regime = spec.aggregation, spec.regime
    return StreamConfig(
        algorithm=agg.algorithm,
        buffer_capacity=regime.buffer_capacity,
        local_steps=regime.local_steps,
        lr=regime.lr,
        alpha=agg.alpha,
        c=agg.c,
        c_br=agg.c_br,
        discount=regime.discount,
        discount_a=regime.discount_a,
        attack=spec.attack.name,
        attack_kw=kw_tuple(spec.attack.kwargs),
        n_byzantine_hint=byzantine_hint(spec),
        geomed_iters=agg.geomed_iters,
        trust=spec.trust.enabled,
        trust_kw=kw_tuple(spec.trust.kwargs),
        root_refresh_every=regime.root_refresh_every,
        shards=getattr(regime, "shards", 0),
        telemetry=spec.telemetry.enabled and spec.telemetry.metrics,
        monitor=monitor_config(spec),
    )


def population_model(spec: ExperimentSpec):
    """Population-regime lowering: the AsyncRegime churn/diurnal knobs ->
    ``repro.stream.events.PopulationModel`` (or None — the default — which
    keeps the event stream on the exact legacy draw path)."""
    regime = spec.regime
    if regime.kind == "sync":
        return None
    if regime.churn_period <= 0.0 and regime.diurnal_amp <= 0.0:
        return None
    from repro.stream.events import PopulationModel

    return PopulationModel(
        churn_period=regime.churn_period,
        churn_duty=regime.churn_duty,
        diurnal_amp=regime.diurnal_amp,
        diurnal_period=regime.diurnal_period,
        seed=spec.seed,
    )


def megastep_params(spec: ExperimentSpec) -> dict:
    """Compiled-serving lowering: the AsyncRegime megastep knobs ->
    ``repro.stream.megastep.CompiledStream`` constructor kwargs.  The
    documented ``0 = derive`` defaults resolve here: block 0 -> K (whole
    flush per vmapped batch), chunk 0 -> eval_every (evals land exactly
    on megastep boundaries)."""
    regime = spec.regime
    return dict(
        block=regime.compiled_block or regime.buffer_capacity,
        chunk=regime.compiled_chunk or regime.eval_every,
    )


def stream_config_from_round(
    cfg: RoundConfig, capacity: int, shards: int = 0
) -> StreamConfig:
    """The sync<->async bridge conversion (``repro.fl.bridge``), as a
    spec round trip: RoundConfig -> spec fragments -> ``stream_config``.

    Zero-staleness semantics (discount "none"), explicit
    ``n_byzantine_hint`` carry-over — the resulting StreamConfig is
    field-for-field what the bridge's bit-for-bit equivalence proof
    pins against ``federated_round``.
    """
    if shards > 0:
        regime = ShardedRegime(
            buffer_capacity=capacity,
            local_steps=cfg.local_steps,
            lr=cfg.lr,
            discount="none",
            shards=shards,
        )
    else:
        regime = AsyncRegime(
            buffer_capacity=capacity,
            local_steps=cfg.local_steps,
            lr=cfg.lr,
            discount="none",
        )
    spec = ExperimentSpec(
        aggregation=AggregationSpec(
            algorithm=cfg.algorithm,
            alpha=cfg.alpha,
            c=cfg.c,
            c_br=cfg.c_br,
            mu=cfg.mu,
            acg_beta=cfg.acg_beta,
            acg_lambda=cfg.acg_lambda,
            geomed_iters=cfg.geomed_iters,
            n_byzantine_hint=cfg.n_byzantine_hint,
        ),
        attack=AttackSpec(cfg.attack, dict(cfg.attack_kw)),
        trust=TrustSpec(cfg.trust, dict(cfg.trust_kw)),
        regime=regime,
    )
    return stream_config(spec)


# ---------------------------------------------------------------- legacy shims
def spec_from_sync_config(exp) -> ExperimentSpec:
    """Lossless adoption of a legacy ``repro.fl.server.ExperimentConfig``."""
    return ExperimentSpec(
        data=DataSpec(
            dataset=exp.dataset,
            n_workers=exp.n_workers,
            beta=exp.beta,
            malicious_fraction=exp.malicious_fraction,
            root_samples=exp.root_samples,
        ),
        model=ModelSpec(exp.model),
        aggregation=AggregationSpec(
            algorithm=exp.algorithm, alpha=exp.alpha, c=exp.c, c_br=exp.c_br
        ),
        attack=AttackSpec(exp.attack, dict(exp.attack_kw)),
        trust=TrustSpec(exp.trust, dict(exp.trust_kw)),
        regime=SyncRegime(
            rounds=exp.rounds,
            n_selected=exp.n_selected,
            local_steps=exp.local_steps,
            batch_size=exp.batch_size,
            lr=exp.lr,
            eval_every=exp.eval_every,
        ),
        seed=exp.seed,
    )


def spec_from_stream_config(exp) -> ExperimentSpec:
    """Lossless adoption of a legacy ``StreamExperimentConfig``."""
    regime_kw = dict(
        flushes=exp.flushes,
        concurrency=exp.concurrency,
        buffer_capacity=exp.buffer_capacity,
        latency=exp.latency,
        latency_kw=dict(exp.latency_kw),
        local_steps=exp.local_steps,
        batch_size=exp.batch_size,
        lr=exp.lr,
        discount=exp.discount,
        discount_a=exp.discount_a,
        root_refresh_every=exp.root_refresh_every,
        root_cache=exp.root_cache,
        eval_every=exp.eval_every,
    )
    regime = (
        ShardedRegime(shards=exp.shards, **regime_kw)
        if exp.shards > 0
        else AsyncRegime(**regime_kw)
    )
    return ExperimentSpec(
        data=DataSpec(
            dataset=exp.dataset,
            n_workers=exp.n_workers,
            beta=exp.beta,
            malicious_fraction=exp.malicious_fraction,
            root_samples=exp.root_samples,
        ),
        model=ModelSpec(exp.model),
        aggregation=AggregationSpec(
            algorithm=exp.algorithm, alpha=exp.alpha, c=exp.c, c_br=exp.c_br
        ),
        attack=AttackSpec(exp.attack, dict(exp.attack_kw)),
        trust=TrustSpec(exp.trust, dict(exp.trust_kw)),
        regime=regime,
        seed=exp.seed,
    )


def as_spec(exp) -> ExperimentSpec:
    """ExperimentSpec passthrough, or legacy-dataclass adoption."""
    if isinstance(exp, ExperimentSpec):
        return exp
    from repro.fl.server import ExperimentConfig
    from repro.stream.server import StreamExperimentConfig

    if isinstance(exp, StreamExperimentConfig):
        return spec_from_stream_config(exp)
    if isinstance(exp, ExperimentConfig):
        return spec_from_sync_config(exp)
    raise TypeError(
        f"expected an ExperimentSpec (repro.api) or a legacy "
        f"ExperimentConfig/StreamExperimentConfig, got {type(exp).__name__}"
    )
