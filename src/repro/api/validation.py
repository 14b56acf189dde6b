"""Capability validation of an :class:`~repro.api.spec.ExperimentSpec`
against the LIVE registries — aggregation rules, adversary names,
latency models, staleness discounts, datasets, models, trust knobs —
with actionable error messages.

This is the layer the fast ``spec-matrix`` CI job exercises: every
benchmark/example spec is instantiated and validated in seconds, with
no training, so config drift (a renamed attack, a rule dropped from the
flat tier, a sharded run over a non-shardable rule) fails loudly before
anything expensive runs.
"""
from __future__ import annotations

import inspect

from repro.api import spec as spec_mod


class SpecError(ValueError):
    """An ExperimentSpec that cannot be lowered onto any engine."""


#: the synthetic least-squares scenario lab (repro.adversary.scenarios)
#: is a first-class data source of the declarative plane — its cells are
#: specs too, so the spec-matrix job validates their attack/rule names.
SCENARIO_DATASET = "scenario"
SCENARIO_MODEL = "quadratic"


def _err(msg: str) -> None:
    raise SpecError(msg)


def ensure_executable(spec) -> None:
    """Rejects specs that validate but have no ENGINE behind them: the
    scenario-lab dataset/model name the synthetic least-squares
    federation, which is driven by ``repro.adversary.scenarios``
    (run_scenario / run_stream_scenario), not the data pipeline."""
    if spec.data.dataset == SCENARIO_DATASET or spec.model.name == SCENARIO_MODEL:
        _err(
            f"dataset {spec.data.dataset!r} / model {spec.model.name!r} is the "
            "synthetic scenario lab — drive it with repro.adversary.scenarios."
            "run_scenario / run_stream_scenario; the engine data pipeline "
            "cannot execute it"
        )


def sync_algorithms() -> frozenset:
    """Rules the synchronous round dispatches: every flat-capable rule
    plus the client-variant algorithms whose reduction is the mean."""
    from repro.core import aggregators

    return frozenset(aggregators.FLAT_CAPABLE) | frozenset(aggregators.MEAN_REDUCED)


def async_algorithms() -> frozenset:
    """Rules the stream flush serves on the flat [K, d] plane."""
    from repro.core import aggregators

    return frozenset(aggregators.FLAT_CAPABLE)


def _validate_arch(spec: spec_mod.ExperimentSpec) -> None:
    """An architecture trains adapters on its frozen base, on token
    data, through the sync engine."""
    import dataclasses

    from repro.configs import get_arch
    from repro.data.synthetic import TOKEN_SPECS
    from repro.models.lora import TARGETS

    model, data = spec.model, spec.data
    if spec.regime.kind != "sync":
        _err(f"model {model.name!r} runs through the sync engine only; got a "
             f"{spec.regime.kind!r} regime")
    if model.adapters is None:
        _err(f"model {model.name!r} trains low-rank adapters on a frozen base: "
             "set ModelSpec(adapters=AdapterSpec(...))")
    ad = model.adapters
    if ad.rank < 1 or ad.alpha <= 0:
        _err(f"adapters need rank >= 1 and alpha > 0, got {ad.rank}, {ad.alpha}")
    if not ad.targets or set(ad.targets) - set(TARGETS):
        _err(f"adapter targets {list(ad.targets)} must be a non-empty subset of {list(TARGETS)}")
    cfg = get_arch(model.name, smoke=model.smoke)
    for key in model.overrides:
        head, _, sub = key.partition(".")
        owner = getattr(cfg, head, None) if sub else cfg
        names = {f.name for f in dataclasses.fields(owner)} if dataclasses.is_dataclass(owner) else set()
        if (sub or head) not in names:
            _err(f"unknown ArchConfig override {key!r}")
    if data.dataset not in TOKEN_SPECS:
        _err(f"model {model.name!r} trains on a token dataset; have {sorted(TOKEN_SPECS)}")
    if data.seq_len < 2:
        _err(f"a token dataset needs seq_len >= 2, got {data.seq_len}")


def validate(spec: spec_mod.ExperimentSpec, mesh=None) -> spec_mod.ExperimentSpec:
    """Checks ``spec`` against the live registries; returns it unchanged.

    ``mesh`` (optional) is the pod mesh a sharded run will execute on —
    its ``("pod",)`` axis must match ``regime.shards``.  A sharded spec
    with ``shards > 1``, no mesh, and ``emulate=False`` is rejected
    (single-device emulation must be opted into).
    """
    from repro.adversary import engine as adversary_engine
    from repro.core import aggregators
    from repro.configs import FL_ARCH_IDS
    from repro.data.synthetic import SPECS as DATASETS
    from repro.data.synthetic import TOKEN_SPECS as TOKEN_DATASETS
    from repro.models import cnn
    from repro.stream import server as stream_server
    from repro.stream.events import LATENCIES
    from repro.stream.staleness import DISCOUNTS
    from repro.trust.reputation import TrustConfig

    if not isinstance(spec, spec_mod.ExperimentSpec):
        _err(f"expected an ExperimentSpec, got {type(spec).__name__}")
    data, model, agg = spec.data, spec.model, spec.aggregation
    attack, trust, regime = spec.attack, spec.trust, spec.regime

    # ---- data / model names
    datasets = set(DATASETS) | set(TOKEN_DATASETS) | {SCENARIO_DATASET}
    if data.dataset not in datasets:
        _err(f"unknown dataset {data.dataset!r}; have {sorted(datasets)}")
    models = set(cnn.MODELS) | set(FL_ARCH_IDS) | {SCENARIO_MODEL}
    if model.name not in models:
        _err(f"unknown model {model.name!r}; have {sorted(models)}")
    if model.name in FL_ARCH_IDS:
        _validate_arch(spec)
    elif model.smoke or model.overrides or model.adapters is not None:
        _err(f"model {model.name!r} is a CNN: smoke, overrides and adapters "
             "apply to an architecture id")
    elif data.dataset in TOKEN_DATASETS:
        _err(f"token dataset {data.dataset!r} needs an architecture id; "
             f"have {list(FL_ARCH_IDS)}")
    if data.n_workers < 1:
        _err(f"n_workers must be >= 1, got {data.n_workers}")
    if not 0.0 <= data.malicious_fraction <= 1.0:
        _err(f"malicious_fraction must be in [0, 1], got {data.malicious_fraction}")
    if data.drift not in ("none", "label_shift"):
        _err(f"unknown drift mode {data.drift!r}; have ['label_shift', 'none']")
    if data.drift_rate < 0:
        _err(f"drift_rate must be >= 0, got {data.drift_rate}")
    if data.drift != "none" and data.drift_rate <= 0:
        _err(f"drift={data.drift!r} needs drift_rate > 0, got {data.drift_rate}")

    # ---- aggregation rule vs regime capability tiers
    alg = agg.algorithm
    if regime.kind == "sync":
        if alg not in sync_algorithms():
            _err(
                f"unknown sync algorithm {alg!r}; "
                f"have {sorted(sync_algorithms())}"
            )
    else:  # async / sharded serve on the flat update plane
        if alg in aggregators.MEAN_REDUCED and alg != "fedavg":
            _err(
                f"algorithm {alg!r} needs client-variant local objectives; "
                "stream clients run plain SGD — use a sync regime"
            )
        elif alg not in aggregators.FLAT_CAPABLE:
            _err(
                f"algorithm {alg!r} is not FLAT_CAPABLE — the stream engine "
                f"serves on the flat [K, d] update plane; flat-capable rules: "
                f"{sorted(aggregators.FLAT_CAPABLE)}"
            )
    if regime.kind == "sharded" and alg not in stream_server.SHARDABLE:
        _err(
            f"algorithm {alg!r} has no hierarchical one-psum sharded flush "
            f"(shardable: {stream_server.SHARDABLE}); use an async regime"
        )

    # ---- regime structure
    for field, lo in (("local_steps", 1), ("batch_size", 1), ("eval_every", 1)):
        if getattr(regime, field) < lo:
            _err(f"{field} must be >= {lo}, got {getattr(regime, field)}")
    if regime.kind == "sync":
        if regime.rounds < 1:
            _err(f"rounds must be >= 1, got {regime.rounds}")
        if not 1 <= regime.n_selected <= data.n_workers:
            _err(
                f"n_selected={regime.n_selected} must be in "
                f"[1, n_workers={data.n_workers}]"
            )
    else:
        if regime.flushes < 1:
            _err(f"flushes must be >= 1, got {regime.flushes}")
        if regime.concurrency < 1:
            # zero in-flight dispatches would stall the event loop forever
            _err(f"concurrency must be >= 1, got {regime.concurrency}")
        if regime.buffer_capacity < 1:
            _err(f"buffer_capacity must be >= 1, got {regime.buffer_capacity}")
        if regime.root_refresh_every < 1:
            _err(f"root_refresh_every must be >= 1, got {regime.root_refresh_every}")
        if regime.latency not in LATENCIES:
            _err(
                f"unknown latency model {regime.latency!r}; "
                f"have {sorted(LATENCIES)}"
            )
        # every LATENCIES factory swallows **kw, so a trial call cannot
        # catch typos — check keys against the factory's NAMED params
        # (which name every real knob) instead
        allowed = {
            p.name
            for p in inspect.signature(LATENCIES[regime.latency]).parameters.values()
            if p.kind is not inspect.Parameter.VAR_KEYWORD
        }
        unknown = set(regime.latency_kw) - allowed
        if unknown:
            _err(
                f"latency {regime.latency!r} has no kwargs {sorted(unknown)}; "
                f"it takes {sorted(allowed) or 'no kwargs'}"
            )
        if regime.discount not in DISCOUNTS:
            _err(
                f"unknown staleness discount {regime.discount!r}; "
                f"have {sorted(DISCOUNTS)}"
            )
        if regime.compiled_block < 0:
            _err(f"compiled_block must be >= 0, got {regime.compiled_block}")
        if regime.compiled_chunk < 0:
            _err(f"compiled_chunk must be >= 0, got {regime.compiled_chunk}")
        # ---- population regimes (churn / diurnal / trust-gated dispatch)
        if regime.churn_period < 0:
            _err(f"churn_period must be >= 0, got {regime.churn_period}")
        if not 0.0 < regime.churn_duty <= 1.0:
            _err(f"churn_duty must be in (0, 1], got {regime.churn_duty}")
        if not 0.0 <= regime.diurnal_amp < 1.0:
            _err(f"diurnal_amp must be in [0, 1), got {regime.diurnal_amp}")
        if regime.diurnal_amp > 0 and regime.diurnal_period <= 0:
            _err(
                f"diurnal_amp={regime.diurnal_amp} needs diurnal_period > 0, "
                f"got {regime.diurnal_period}"
            )
        if regime.trust_gated_dispatch and not trust.enabled:
            _err(
                "trust_gated_dispatch requires TrustSpec(enabled=True): "
                "quarantine state comes from the trust reputation layer"
            )
        if regime.compiled and (
            regime.churn_period > 0
            or regime.diurnal_amp > 0
            or regime.trust_gated_dispatch
            or data.drift != "none"
        ):
            _err(
                "compiled=True (megastep) does not support population "
                "regimes yet — churn/diurnal/trust_gated_dispatch/drift "
                "need the host event loop; set compiled=False"
            )
        if regime.compiled:
            from repro.stream.events import LatencyModel, make_latency

            lat = make_latency(regime.latency, **dict(regime.latency_kw))
            if type(lat).icdf is LatencyModel.icdf:
                _err(
                    f"latency {regime.latency!r} has no inverse CDF — the "
                    "compiled megastep draws arrivals through "
                    "LatencyModel.icdf; use a built-in model or add one"
                )
            if (
                regime.compiled_block
                and regime.buffer_capacity % regime.compiled_block != 0
            ):
                _err(
                    f"compiled_block={regime.compiled_block} must divide "
                    f"buffer_capacity={regime.buffer_capacity}"
                )
    if regime.kind == "sharded":
        if regime.shards < 1:
            _err(f"shards must be >= 1, got {regime.shards}")
        if regime.buffer_capacity % regime.shards != 0:
            _err(
                f"buffer_capacity={regime.buffer_capacity} must divide into "
                f"shards={regime.shards} pod sub-buffers (K % p == 0)"
            )
        if mesh is not None:
            axes = dict(getattr(mesh, "shape", {}))
            if axes.get("pod") != regime.shards:
                _err(
                    f"shards={regime.shards} needs a ('pod',) mesh axis of "
                    f"that size (repro.launch.mesh.make_pod_mesh"
                    f"({regime.shards})); got axes {axes}"
                )
        elif regime.shards > 1 and not regime.emulate:
            _err(
                f"shards={regime.shards} without a pod mesh: pass mesh="
                f"repro.launch.mesh.make_pod_mesh({regime.shards}) or set "
                "emulate=True for single-device emulation"
            )
        if regime.compiled and mesh is not None:
            _err(
                "compiled=True runs the megastep on the single-device "
                "emulation path only; drop the pod mesh or set "
                "compiled=False"
            )

    # ---- adversary name + typed kwargs against the live registry
    if attack.name not in adversary_engine.names():
        _err(
            f"unknown attack {attack.name!r}; "
            f"registry has {adversary_engine.names()}"
        )
    try:
        # registry factories are lenient about unknown keys (**kw), but
        # bad VALUES — malformed schedule phases, an unknown inner
        # attack, a non-numeric scale — fail at construction
        adversary_engine.resolve(attack.name, dict(attack.kwargs))
    except (TypeError, ValueError, KeyError, IndexError) as e:
        _err(f"attack {attack.name!r} rejects kwargs {dict(attack.kwargs)!r}: {e}")

    # ---- trust layer
    if trust.enabled and alg not in ("drag", "br_drag"):
        _err(
            "trust reputation needs a reference direction; algorithm "
            f"{alg!r} has none (use drag or br_drag)"
        )
    bad = set(trust.kwargs) - set(TrustConfig._fields)
    if bad:
        _err(
            f"unknown TrustConfig fields {sorted(bad)}; "
            f"have {list(TrustConfig._fields)}"
        )

    # ---- telemetry plane (repro.obs)
    tel = spec.telemetry
    if tel.ring_capacity < 1:
        _err(f"telemetry ring_capacity must be >= 1, got {tel.ring_capacity}")
    if tel.jsonl and tel.jsonl == tel.perfetto:
        _err(
            f"telemetry jsonl and perfetto name the same file "
            f"{tel.jsonl!r}; the event log and the trace export would "
            "clobber each other"
        )
    if (tel.jsonl or tel.perfetto) and not tel.enabled:
        _err(
            "telemetry output paths are set but enabled=False; set "
            "TelemetrySpec(enabled=True) or drop the paths"
        )

    # ---- diagnosis layer (repro.obs.monitor)
    mon = tel.monitor
    if mon.enabled:
        if not (tel.enabled and tel.metrics):
            _err(
                "monitor.enabled requires TelemetrySpec(enabled=True, "
                "metrics=True): the detectors read the flush MetricsBundle"
            )
        if not (0.0 < mon.ewma_alpha <= 1.0):
            _err(f"monitor ewma_alpha must be in (0, 1], got {mon.ewma_alpha}")
        for name in ("cusum_k", "cusum_h", "ph_delta", "ph_lambda", "min_sigma"):
            v = getattr(mon, name)
            if v < 0:
                _err(f"monitor {name} must be >= 0, got {v}")
        if mon.warmup < 1:
            _err(f"monitor warmup must be >= 1 flush, got {mon.warmup}")
    return spec
