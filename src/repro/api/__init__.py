"""repro.api — one declarative experiment plane.

A single validated, serializable :class:`ExperimentSpec` (typed
sub-specs + a sync/async/sharded ``RegimeSpec`` tagged union) that
every entry point constructs its run from, compiled onto the existing
engines::

    from repro.api import (AggregationSpec, AsyncRegime, DataSpec,
                           ExperimentSpec, ModelSpec, compile)

    spec = ExperimentSpec(
        data=DataSpec(dataset="emnist", n_workers=20),
        model=ModelSpec("mlp"),
        aggregation=AggregationSpec(algorithm="drag", c=0.25),
        regime=AsyncRegime(flushes=30, buffer_capacity=8, discount="poly"),
    )
    history = compile(spec).run()          # validate -> lower -> engine
    blob = spec.to_json()                  # sweep grids / CI are plain data
    assert ExperimentSpec.from_json(blob) == spec   # lossless

Layers (see each module's docstring):
  ``spec``      pure data — no jax, no registries
  ``validate``  capability checks against the live registries
  ``lowering``  THE field-copy onto RoundConfig / StreamConfig + legacy shims
  ``compiling``  validate + lower -> CompiledExperiment.run() (the ``compile`` verb)
"""
from repro.api.compiling import CompiledExperiment, compile_spec, run_spec  # noqa: F401
from repro.api.lowering import (  # noqa: F401
    as_spec,
    byzantine_hint,
    round_config,
    spec_from_stream_config,
    spec_from_sync_config,
    stream_config,
    stream_config_from_round,
)
from repro.api.spec import (  # noqa: F401
    REGIMES,
    AdapterSpec,
    AggregationSpec,
    AsyncRegime,
    AttackSpec,
    DataSpec,
    ExperimentSpec,
    ModelSpec,
    MonitorSpec,
    ShardedRegime,
    SyncRegime,
    TelemetrySpec,
    TrustSpec,
    regime_from_dict,
)
from repro.api.validation import SpecError, ensure_executable, validate  # noqa: F401

#: the API verbs: ``compile(spec).run()`` / ``run(spec)``
compile = compile_spec  # noqa: A001  (deliberate, namespaced API verb)
run = run_spec
