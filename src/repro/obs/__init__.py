"""repro.obs — ONE telemetry plane for the sync/async/sharded engines.

Two halves, with a hard boundary between them:

  * **On-device metrics** (``metrics``): a jittable
    :class:`~repro.obs.metrics.MetricsBundle` pytree assembled from
    signals the fused two-pass flush ALREADY computes — the phase-1
    dot/norm scalars, blend coefficients, phi(tau) discounts, trust
    reputations, buffer fill/drop counters.  Zero extra HBM passes over
    the ``[K, d]`` stack (asserted by the two-pass/one-psum probes);
    bundles ride out of the jitted flush as one extra output and
    accumulate in a fixed-capacity on-device ring
    (:class:`~repro.obs.metrics.MetricsRing`) so a compiled megastep
    can keep them device-resident.

  * **Host-side tracing + sinks** (``trace`` / ``sinks``): a
    lightweight nestable span API (``obs.trace.span("ingest")``, on the
    profiler's wall clock, and a ``repro.<name>`` profiler annotation
    while a ``jax.profiler`` session runs) over the engines' HOST
    boundaries — never inside jit — with pluggable sinks: an in-memory
    recorder for tests (:class:`~repro.obs.sinks.MemorySink`), a
    structured JSONL event log (:class:`~repro.obs.sinks.JsonlSink`),
    and Chrome/Perfetto ``trace_event`` export (:func:`~repro.obs.sinks.perfetto_trace`).

On top of the recording halves sits the **diagnosis layer** (PR 7):
``monitor`` runs jittable online change-point detectors (EWMA residual
+ CUSUM / Page-Hinkley) over each flush's bundle with O(1) pytree state
threaded through the jitted flush like ``TrustState``; ``forensics``
reconstructs per-client incident tables host-side and scores detection
precision/recall/latency against adversary-lab ground truth; ``report``
renders the joined span-breakdown + alert timeline as markdown.
Boundary rule: the monitor reads ONLY the already-reduced bundle
(zero extra HBM passes) and alert decoding stays host-side.

``probes`` is the shared call-site counter implementation behind
``repro.kernels.instrument`` (the two-pass and one-psum invariant
probes), so invariant tests and telemetry count the same quantities.
``session`` ties everything to the declarative plane: a
:class:`~repro.obs.session.TelemetrySession` is built from an
``api.TelemetrySpec`` (off by default) and threaded through the
engines without touching their math.
"""
from repro.obs.metrics import (  # noqa: F401
    DROP_BUCKETS,
    HIST_BINS,
    MetricsBundle,
    MetricsRing,
    bundle_to_dict,
    flush_bundle,
    make_ring_push,
    ring_init,
    ring_push,
    ring_read,
)
from repro.obs.monitor import (  # noqa: F401
    MONITOR_SIGNALS,
    MonitorConfig,
    MonitorState,
    MonitorVerdict,
    alerts_from_verdict,
    monitor_init,
    monitor_step,
    monitor_to_dict,
)
from repro.obs.forensics import (  # noqa: F401
    alert_latency,
    client_table,
    detection_quality,
    incident_timeline,
)
from repro.obs.report import run_report, write_report  # noqa: F401
from repro.obs.probes import counted_calls  # noqa: F401
from repro.obs.sinks import (  # noqa: F401
    JsonlSink,
    MemorySink,
    perfetto_trace,
    write_perfetto,
)
from repro.obs.trace import Tracer, get_tracer, span, tracer  # noqa: F401
from repro.obs.session import (  # noqa: F401
    TelemetrySession,
    host_drop_bucket,
    session_from_spec,
)
