"""The declarative experiment plane: one validated, serializable spec.

An :class:`ExperimentSpec` is the single source of truth every entry
point — experiments, benchmarks, examples, CI matrices — constructs a
run from.  It is composed of typed sub-specs:

  * :class:`DataSpec`        — federation + dataset (who holds what)
  * :class:`ModelSpec`       — the trained architecture
  * :class:`AggregationSpec` — the server rule + its hyper-parameters
  * :class:`AttackSpec`      — Byzantine behaviour (typed kwargs, not
                               the legacy tuple-of-pairs)
  * :class:`TrustSpec`       — divergence-history reputation layer
  * a ``RegimeSpec`` tagged union — :class:`SyncRegime` /
    :class:`AsyncRegime` / :class:`ShardedRegime` — carrying the
    regime-specific knobs (rounds vs flushes, buffer capacity, phi
    discount, ``shards``, ``root_refresh_every``, ...)

The spec layer is PURE DATA: no jax, no registries, no engine imports.
Capability checking lives in :mod:`repro.api.validation` (against the
live registries) and the lowering onto the engines' static configs in
:mod:`repro.api.lowering`; :mod:`repro.api.compiling` ties them together.

Serialization is lossless and JSON-safe: ``from_dict(to_dict(spec)) ==
spec`` and the same through ``json.dumps``/``loads`` — sweep grids,
BENCH_* provenance records, and CI matrices are plain data.  Tuples
inside kwargs (e.g. an attack schedule's phases) are canonicalised at
construction so the round trip through JSON lists is exact.
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from dataclasses import field
from typing import Any, ClassVar, Mapping


# ------------------------------------------------------------ kwargs plumbing
def _freeze(v):
    """Canonical in-spec form: sequences -> tuples (hashable once lowered
    to the engines' static ``attack_kw``/``trust_kw``), mappings -> dicts
    of frozen values.  Applied at construction AND at ``from_dict`` so
    JSON's list round trip compares equal."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, Mapping):
        return {str(k): _freeze(x) for k, x in v.items()}
    return v


def _thaw(v):
    """JSON-safe form of a frozen value: tuples -> lists."""
    if isinstance(v, tuple):
        return [_thaw(x) for x in v]
    if isinstance(v, Mapping):
        return {k: _thaw(x) for k, x in v.items()}
    return v


def _hashable(v):
    """Deep-frozen view of a spec field value for hashing (dicts ->
    sorted item tuples)."""
    if isinstance(v, tuple):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, Mapping):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def _spec_hash(self):
    # dict-valued kwargs fields break the dataclass-generated __hash__;
    # hash the deep-frozen view instead so specs work as set members /
    # cache keys (sweep-grid dedup).  Assigned post-definition because
    # @dataclass(eq=True) overwrites an in-body __hash__.
    return hash(tuple(
        _hashable(getattr(self, f.name)) for f in dataclasses.fields(self)
    ))


def _coerce_kwargs(kw, owner: str) -> dict:
    """Typed-kwargs coercion with a legacy escape hatch: the pre-API
    tuple-of-pairs (``(("std", 3.0),)``) is still accepted, with a
    deprecation note."""
    if kw is None:
        return {}
    if isinstance(kw, tuple):
        try:
            as_dict = dict(kw)
        except (TypeError, ValueError):
            raise TypeError(
                f"{owner} kwargs must be a mapping (or the deprecated "
                f"tuple of (key, value) pairs), got {kw!r}"
            ) from None
        if kw:  # the empty tuple is the no-op default — nothing to warn about
            warnings.warn(
                f"{owner}: tuple-of-pairs kwargs are deprecated; pass a dict "
                f"(e.g. {as_dict!r})",
                DeprecationWarning,
                stacklevel=3,
            )
        kw = as_dict
    if not isinstance(kw, Mapping):
        raise TypeError(f"{owner} kwargs must be a mapping, got {type(kw).__name__}")
    return {str(k): _freeze(v) for k, v in kw.items()}


# ------------------------------------------------------------------ sub-specs
@dataclasses.dataclass(frozen=True)
class DataSpec:
    """The federation: dataset, population, heterogeneity, threat share."""

    dataset: str = "emnist"  # repro.data.synthetic.SPECS name | "scenario"
    n_workers: int = 40  # M
    beta: float = 0.1  # Dirichlet heterogeneity
    malicious_fraction: float = 0.0
    root_samples: int = 3000  # |D_root| for BR-DRAG / FLTrust
    drift: str = "none"  # non-stationary data: none | label_shift
    drift_rate: float = 0.0  # label rotation speed (classes per round/flush)
    seq_len: int = 0  # tokens per sequence of a token dataset


@dataclasses.dataclass(frozen=True)
class AdapterSpec:
    """Low-rank adapters trained on a frozen base (``repro.models.lora``):
    rank r, scale alpha / r, and the targets adapted (``attn``: every
    attention projection; ``mlp``: dense MLPs; ``shared``: shared
    experts)."""

    rank: int = 16
    alpha: float = 32.0
    targets: tuple = ("attn", "mlp", "shared")

    def __post_init__(self):
        object.__setattr__(self, "targets", _freeze(self.targets))


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The trained model: a ``repro.models.cnn.MODELS`` name, or an
    architecture id of ``repro.configs.FL_ARCH_IDS`` whose clients train
    ``adapters`` on its frozen base.  For an architecture, ``smoke``
    takes its reduced CPU-test variant and ``overrides`` replaces
    ArchConfig fields (dotted for a nested config, e.g.
    ``moe.experts_held``): the chip's share of a deployment."""

    name: str = "mlp"
    smoke: bool = False
    overrides: dict = field(default_factory=dict)
    adapters: AdapterSpec | None = None

    def __post_init__(self):
        object.__setattr__(self, "overrides", _coerce_kwargs(self.overrides, "ModelSpec"))
        if isinstance(self.adapters, Mapping):
            object.__setattr__(self, "adapters", AdapterSpec(**self.adapters))


@dataclasses.dataclass(frozen=True)
class AggregationSpec:
    """Server rule + hyper-parameters (registry name, see
    ``repro.core.aggregators``)."""

    algorithm: str = "fedavg"
    alpha: float = 0.25  # DRAG reference EMA
    c: float = 0.1  # DRAG DoD coefficient
    c_br: float = 0.5  # BR-DRAG DoD coefficient
    mu: float = 0.2  # FedProx proximal weight
    acg_beta: float = 0.2  # FedACG local regulariser
    acg_lambda: float = 0.85  # FedACG momentum
    geomed_iters: int = 8  # Weiszfeld iterations (geomed/rfa/raga)
    n_byzantine_hint: int | None = None  # krum/trimmed_mean trim level;
    #   None = derive from malicious_fraction x group size at lowering


@dataclasses.dataclass(frozen=True)
class AttackSpec:
    """Byzantine behaviour: adversary registry name + TYPED kwargs.

    ``kwargs`` is a plain dict (nested tuples allowed, e.g. a schedule's
    phases); the legacy tuple-of-pairs form is accepted with a
    deprecation note.
    """

    name: str = "none"
    kwargs: dict = field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "kwargs", _coerce_kwargs(self.kwargs, "AttackSpec"))


@dataclasses.dataclass(frozen=True)
class TrustSpec:
    """Divergence-history reputation layer (``repro.trust``)."""

    enabled: bool = False
    kwargs: dict = field(default_factory=dict)  # TrustConfig overrides

    def __post_init__(self):
        object.__setattr__(self, "kwargs", _coerce_kwargs(self.kwargs, "TrustSpec"))


@dataclasses.dataclass(frozen=True)
class MonitorSpec:
    """Online change-point detection over flush telemetry — OFF by default.

    Lowers to ``repro.obs.monitor.MonitorConfig``: EWMA-standardised
    CUSUM + Page-Hinkley detectors over the per-flush
    :class:`~repro.obs.metrics.MetricsBundle` signals (divergence mean,
    histogram shift, DoD, quarantine count, drop pressure, buffer fill,
    phi(tau) staleness).  Requires ``TelemetrySpec(enabled=True,
    metrics=True)`` — the detectors read the bundle the flush already
    assembles, nothing else.
    """

    enabled: bool = False
    ewma_alpha: float = 0.15  # baseline adaptation rate
    cusum_k: float = 0.6  # CUSUM slack (sigmas)
    cusum_h: float = 6.0  # CUSUM alarm threshold (sigmas)
    ph_delta: float = 0.25  # Page-Hinkley drift allowance (sigmas)
    ph_lambda: float = 12.0  # Page-Hinkley alarm threshold (sigmas)
    warmup: int = 10  # flushes before alarms may fire
    min_sigma: float = 0.05  # variance floor for standardisation


@dataclasses.dataclass(frozen=True)
class TelemetrySpec:
    """The telemetry plane (``repro.obs``) — OFF by default.

    ``metrics`` rides the jit-safe :class:`~repro.obs.metrics.MetricsBundle`
    out of every flush/round into an on-device ring of ``ring_capacity``
    bundles; ``spans`` records host-boundary wall-clock spans.  ``jsonl``
    / ``perfetto`` name output files for the structured event log and the
    Chrome/Perfetto ``trace_event`` export ("" = don't write).  Enabling
    telemetry never changes the training numerics — invariance is pinned
    by ``tests/test_obs.py``.
    """

    enabled: bool = False
    metrics: bool = True  # flush MetricsBundle ring (device-side)
    spans: bool = True  # host-side trace spans
    ring_capacity: int = 64  # bundles retained (oldest overwritten)
    jsonl: str = ""  # JSONL event-log path ("" = off)
    perfetto: str = ""  # Chrome/Perfetto trace path ("" = off)
    monitor: MonitorSpec = field(default_factory=MonitorSpec)

    def __post_init__(self):
        # from_dict round trip: the nested monitor arrives as a plain dict
        if isinstance(self.monitor, Mapping):
            object.__setattr__(self, "monitor", MonitorSpec(**self.monitor))


# ------------------------------------------------------- RegimeSpec tagged union
@dataclasses.dataclass(frozen=True)
class SyncRegime:
    """The paper's synchronous protocol (``repro.fl``): S-worker rounds."""

    kind: ClassVar[str] = "sync"

    rounds: int = 100  # T
    n_selected: int = 10  # S (UAR partial participation)
    local_steps: int = 5  # U
    batch_size: int = 10  # B
    lr: float = 0.01  # eta
    eval_every: int = 10  # in rounds


@dataclasses.dataclass(frozen=True)
class AsyncRegime:
    """Buffered-async serving (``repro.stream``): event-driven flushes."""

    kind: ClassVar[str] = "async"

    flushes: int = 60  # T — global steps
    concurrency: int = 16  # W — in-flight dispatches
    buffer_capacity: int = 10  # K — flush threshold
    latency: str = "exponential"  # repro.stream.events.LATENCIES name
    latency_kw: dict = field(default_factory=dict)
    local_steps: int = 5  # U
    batch_size: int = 10  # B
    lr: float = 0.01  # eta
    discount: str = "poly"  # staleness phi: none | poly | exp
    discount_a: float = 0.5  # phi sharpness a
    root_refresh_every: int = 1  # r^t cache coarsening (1 = exact)
    root_cache: bool = True  # version-keyed RootReferenceCache
    eval_every: int = 10  # in flushes
    compiled: bool = False  # device-resident megastep serving loop
    #   (repro.stream.megastep): the whole event->ingest->flush cycle as
    #   one lax.scan, host round-trips only at eval/telemetry boundaries.
    #   Requires a latency model with an inverse CDF (all built-ins) and
    #   swaps the MT19937 host sampling for the hash-mode event plane —
    #   a distinct-but-deterministic regime, pinned bit-for-bit against
    #   its own per-event unrolled execution (tests/test_megastep.py)
    compiled_block: int = 0  # events per vmapped client-update batch
    #   inside the megastep; 0 = K (whole flush), 1 = the unrolled
    #   oracle's per-event structure. Must divide buffer_capacity
    compiled_chunk: int = 0  # flushes per megastep host round-trip;
    #   0 = eval_every (evals land exactly on chunk boundaries)
    churn_period: float = 0.0  # client churn cycle in virtual time;
    #   0 = static population.  Each client is active on a hash-phased
    #   duty window of the cycle (repro.stream.events.PopulationModel)
    churn_duty: float = 1.0  # active fraction of the churn cycle, (0, 1]
    diurnal_amp: float = 0.0  # arrival-wave amplitude in [0, 1);
    #   completion latencies stretch by 1 + amp*sin(2*pi*t/period)
    diurnal_period: float = 0.0  # arrival-wave cycle in virtual time
    trust_gated_dispatch: bool = False  # skip quarantined clients
    #   (reputation 0) at dispatch; requires trust.enabled

    def __post_init__(self):
        object.__setattr__(
            self, "latency_kw", _coerce_kwargs(self.latency_kw, type(self).__name__)
        )


@dataclasses.dataclass(frozen=True)
class ShardedRegime(AsyncRegime):
    """Pod-sharded async serving (``repro.stream.sharded``): per-pod
    [K/p, d] sub-buffers + the hierarchical one-psum flush."""

    kind: ClassVar[str] = "sharded"

    shards: int = 2  # p — pod count; buffer_capacity must divide by it
    emulate: bool = True  # True: mesh-free single-device emulation is OK;
    #   False: validate() demands a ("pod",) mesh (launch.mesh.make_pod_mesh)


for _cls in (ModelSpec, AttackSpec, TrustSpec, AsyncRegime, ShardedRegime):
    _cls.__hash__ = _spec_hash  # dict kwargs fields; see _spec_hash


REGIMES: dict[str, type] = {
    SyncRegime.kind: SyncRegime,
    AsyncRegime.kind: AsyncRegime,
    ShardedRegime.kind: ShardedRegime,
}


def regime_from_dict(d: Mapping) -> SyncRegime | AsyncRegime | ShardedRegime:
    d = dict(d)
    kind = d.pop("kind", None)
    if kind not in REGIMES:
        raise ValueError(f"unknown regime kind {kind!r}; have {sorted(REGIMES)}")
    return REGIMES[kind](**d)


# ------------------------------------------------------------- the experiment
@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One declarative experiment: everything an engine needs, as data."""

    data: DataSpec = field(default_factory=DataSpec)
    model: ModelSpec = field(default_factory=ModelSpec)
    aggregation: AggregationSpec = field(default_factory=AggregationSpec)
    attack: AttackSpec = field(default_factory=AttackSpec)
    trust: TrustSpec = field(default_factory=TrustSpec)
    regime: SyncRegime | AsyncRegime | ShardedRegime = field(default_factory=SyncRegime)
    telemetry: TelemetrySpec = field(default_factory=TelemetrySpec)
    seed: int = 0

    # -------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        """Lossless, JSON-safe plain-data form (tuples become lists;
        ``from_dict`` restores them)."""
        return {
            "data": dataclasses.asdict(self.data),
            "model": {**dataclasses.asdict(self.model),
                      "overrides": _thaw(self.model.overrides),
                      "adapters": (None if self.model.adapters is None else
                                   _thaw(dataclasses.asdict(self.model.adapters)))},
            "aggregation": dataclasses.asdict(self.aggregation),
            "attack": {"name": self.attack.name, "kwargs": _thaw(self.attack.kwargs)},
            "trust": {"enabled": self.trust.enabled, "kwargs": _thaw(self.trust.kwargs)},
            "regime": {"kind": self.regime.kind, **_thaw(dataclasses.asdict(self.regime))},
            "telemetry": dataclasses.asdict(self.telemetry),
            "seed": self.seed,
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "ExperimentSpec":
        # a provenance record is only trustworthy if drift fails loudly:
        # sub-spec constructors reject unknown fields, so guard the one
        # remaining unchecked layer (a typo'd/renamed top-level section)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"unknown ExperimentSpec sections {sorted(unknown)}; "
                f"have {sorted(known)}"
            )
        return cls(
            data=DataSpec(**d.get("data", {})),
            model=ModelSpec(**d.get("model", {})),
            aggregation=AggregationSpec(**d.get("aggregation", {})),
            attack=AttackSpec(**d.get("attack", {})),
            trust=TrustSpec(**d.get("trust", {})),
            regime=regime_from_dict(d.get("regime", {"kind": "sync"})),
            # absent in pre-telemetry provenance records -> the off default
            telemetry=TelemetrySpec(**d.get("telemetry", {})),
            seed=int(d.get("seed", 0)),
        )

    def to_json(self, **dumps_kw) -> str:
        return json.dumps(self.to_dict(), **dumps_kw)

    @classmethod
    def from_json(cls, s: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(s))

    # ------------------------------------------------------------ behaviour
    def validate(self, mesh=None) -> "ExperimentSpec":
        from repro.api.validation import validate

        return validate(self, mesh=mesh)

    def compile(self, mesh=None):
        from repro.api.compiling import compile_spec

        return compile_spec(self, mesh=mesh)

    def run(self, data=None, progress=None, mesh=None) -> dict:
        return self.compile(mesh=mesh).run(data=data, progress=progress)
