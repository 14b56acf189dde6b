"""Event-driven asynchronous FL server (buffered-async, FedBuff-shaped).

The serving loop is::

    completion event -> client update (vs. the params SNAPSHOT the client
    was dispatched with) -> donated buffer ingest -> threshold flush
    (the server step every engine shares, ``repro.fl.aggregate``: any
    rule of the flat registry, staleness-aware for DRAG/BR-DRAG) ->
    global step -> reference EMA update -> re-dispatch

Clients never block each other: an upload lands in the fixed-capacity
ingest buffer (``repro.stream.buffer``) tagged with the model version it
trained from, and the global model only advances when the buffer reaches
its flush threshold K.  Staleness tau_m = t - t_dispatch is known
exactly at flush time and feeds the discounted DoD
(``repro.stream.staleness``).

Byzantine behaviour goes through the adversary engine
(``repro.adversary``): update-space attacks transform the buffered stack
at flush (the malicious mask rides along in the buffer, the adversary's
cross-round memory rides in the :class:`StreamState`), async-native
attacks additionally shape arrival times (``BiasedLatency``), and
data-space attacks poison the per-client sample stream.  The
divergence-history trust layer (``repro.trust``) indexes its reputation
table with the per-slot client ids and enters DRAG/BR-DRAG flushes as
the reputation-weighted mean.

For BR-DRAG/FLTrust flushes the trusted reference r^t (a U-step SGD pass
over D_root) is computed host-side through a version-keyed cache
(:class:`RootReferenceCache`) so it can be amortised across flushes;
``root_refresh_every > 1`` additionally reuses a slightly-stale r across
that many versions (ROADMAP open item).

With buffer capacity S, zero latency, and phi = none the engine
reproduces the synchronous ``repro.fl.round.federated_round`` trajectory
bit-for-bit — see ``repro.fl.bridge``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.adversary import engine as adversary_engine
from repro.core import aggregators, br_drag, drag
from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.fl import aggregate as fl_aggregate
from repro.fl.client import local_update, with_counters
from repro.kernels import ops as kops
from repro.obs import session as obs_session
from repro.obs import trace as obs_trace
from repro.stream import buffer as buf_mod
from repro.stream import sharded as sharded_mod
from repro.stream import staleness as stale
from repro.stream.events import EventStream


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static config of the jitted ingest/flush steps."""

    algorithm: str = "drag"  # any non-client-variant rule; see fl.bridge
    buffer_capacity: int = 10  # K — flush threshold
    local_steps: int = 5  # U (documents the protocol, as in RoundConfig;
    #                        the client scan infers U from the batch stack)
    lr: float = 0.01  # eta
    alpha: float = 0.25  # DRAG EMA
    c: float = 0.1  # DRAG DoD coefficient
    c_br: float = 0.5  # BR-DRAG DoD coefficient
    discount: str = "none"  # staleness phi: none | poly | exp
    discount_a: float = 0.5  # phi sharpness a
    attack: str = "none"  # any repro.adversary registry name
    attack_kw: tuple = ()
    n_byzantine_hint: int = 0  # krum / multi_krum / bulyan / trimmed_mean
    geomed_iters: int = 8
    trust: bool = False  # divergence-history reputation (drag/br_drag)
    trust_kw: tuple = ()  # TrustConfig overrides
    root_refresh_every: int = 1  # reuse cached r^t across this many versions
    shards: int = 0  # p — per-pod sub-buffers + hierarchical one-psum
    #                    flush (repro.stream.sharded); 0 = single buffer
    telemetry: bool = False  # metrics["obs"] = MetricsBundle per flush
    #   (repro.obs) — STATIC: off leaves the flush jaxpr untouched; on
    #   adds one extra pytree output assembled from the already-computed
    #   flush signals, never an extra pass over the stack
    monitor: object = None  # obs.monitor.MonitorConfig | None — online
    #   change-point detectors over the bundle (requires telemetry=True);
    #   None (default) keeps the flush jaxpr monitor-free


class StreamState(NamedTuple):
    """Full async-server state between events."""

    params: pt.Pytree
    round: jax.Array  # int32 — global model version t (flush count)
    drag: drag.DragState  # reference EMA (drag) / unused otherwise
    buffer: buf_mod.BufferState
    adversary: pt.Pytree = ()  # attack memory (repro.adversary)
    trust: pt.Pytree = ()  # TrustState | () (repro.trust)
    monitor: pt.Pytree = ()  # obs.monitor.MonitorState | () (diagnosis)


def init_stream_state(
    params: pt.Pytree,
    capacity: int,
    cfg: StreamConfig | None = None,
    n_clients: int | None = None,
    mesh=None,
) -> StreamState:
    # Copy params for the same aliasing reason as fl.round.init_server_state.
    #
    # ``cfg.shards > 0`` swaps the flat [K, d] buffer for p pod-sharded
    # [K/p, d] sub-buffers (``repro.stream.sharded``); ``mesh`` places
    # them over its "pod" axis.
    adv_state, trust_state, monitor_state = fl_aggregate.init_step_states(cfg, n_clients)
    if cfg is not None and cfg.shards > 0:
        buffer = sharded_mod.init_sharded_buffer(params, capacity, cfg.shards, mesh)
    else:
        buffer = buf_mod.init_buffer(params, capacity)
    return StreamState(
        params=jax.tree.map(lambda x: jnp.array(x, copy=True), params),
        round=jnp.zeros((), jnp.int32),
        drag=drag.init_state(params),
        buffer=buffer,
        adversary=adv_state,
        trust=trust_state,
        monitor=monitor_state,
    )


#: stream algorithms with a hierarchical (one-psum) sharded flush —
#: per-row blend coefficients are pod-local for these, so the cross-pod
#: traffic is exactly the partial [d] sums
SHARDABLE = ("fedavg", "drag", "br_drag")


def flush(
    loss_fn: Callable,
    cfg: StreamConfig,
    params: pt.Pytree,
    drag_state: drag.DragState,
    rnd: jax.Array,
    buf: buf_mod.BufferState,
    key,
    root_batches=None,  # [U, B, ...] — BR-DRAG / FLTrust root data
    adv_state: pt.Pytree = (),  # adversary memory (repro.adversary)
    trust_state: pt.Pytree = (),  # TrustState | ()
    reference=None,  # precomputed r^t (RootReferenceCache); overrides root_batches
    mesh=None,  # pod mesh for the sharded buffer (repro.stream.sharded)
    monitor_state: pt.Pytree = (),  # obs.monitor.MonitorState | ()
):
    """One global step from a full buffer; returns
    (params', drag', round+1, reset buffer, adv_state', trust_state',
    metrics).

    The buffer IS the flat plane: its view as an ``UpdateStack`` (whose
    staleness tags and client ids feed the discounts and the trust
    layer) goes through ``repro.fl.aggregate.server_step`` with the
    staleness discounts phi(tau) and the buffer's fill, capacity and
    drops.  A sharded buffer (``cfg.shards > 0``) is viewed pod-major
    and reduces through the per-pod passes whose partials meet in one
    psum (``repro.stream.sharded.pod_reduce``).  With a monitor its
    output, (state', verdict), rides back as ``metrics["obs_monitor"]``.
    """
    if cfg.algorithm in aggregators.MEAN_REDUCED and cfg.algorithm != "fedavg":
        # unlike fl.round, there is no client-variant objective here —
        # stream clients run plain SGD, so silently reducing these with
        # the mean would mislabel fedavg results
        raise ValueError(
            f"{cfg.algorithm} needs client-variant local objectives; "
            "stream clients run plain SGD — use the synchronous regime"
        )
    spec = flat_mod.spec_of(params)
    if isinstance(buf, sharded_mod.ShardedBufferState):
        if cfg.algorithm not in SHARDABLE:
            raise ValueError(
                f"stream algorithm {cfg.algorithm!r} has no hierarchical sharded "
                f"flush (shardable: {SHARDABLE}); use shards=0"
            )
        stack = sharded_mod.as_stack(buf, spec, rnd)
        malicious = buf.malicious.reshape(stack.s)
        facts = {"fill": sharded_mod.total_count(buf), "pod_fill": buf.counts,
                 "capacity": sharded_mod.capacity_of(buf), "drops": buf.drops}
        reduce = sharded_mod.pod_reduce(sharded_mod.n_pods(buf), mesh)
        reset = sharded_mod.reset
    else:
        stack = buf_mod.as_stack(buf, spec, rnd)
        malicious = buf.malicious
        facts = {"fill": buf.count, "capacity": buf_mod.capacity_of(buf), "drops": buf.drops}
        reduce = kops.calibrated_reduce
        reset = buf_mod.reset
    discounts = stale.make_discount(cfg.discount, cfg.discount_a)(stack.staleness)

    r_flat = None
    if cfg.algorithm in ("br_drag", "fltrust"):
        if reference is None:
            assert root_batches is not None, f"{cfg.algorithm} needs a root dataset"
            grad_fn = jax.grad(loss_fn)
            reference = br_drag.root_reference(
                params, lambda p, b: grad_fn(p, b), root_batches, cfg.lr
            )
        r_flat = flat_mod.flatten_tree(reference)

    step = fl_aggregate.server_step(
        cfg, params, drag_state, adv_state, trust_state, monitor_state,
        stack, malicious, rnd, key, reference=r_flat,
        discounts=discounts, facts=facts, reduce=reduce,
    )
    metrics = step.metrics
    if step.monitor is not None:
        # the monitor's O(1) state rides the metrics dict back to the host loop
        metrics["obs_monitor"] = step.monitor
    return (step.params, step.drag, rnd + 1, reset(buf), step.adversary, step.trust,
            metrics)


def make_flush_fn(loss_fn: Callable, cfg: StreamConfig, with_root: bool, mesh=None):
    """Jitted flush.  The BUFFER is donated (its slot storage is reused by
    the reset buffer); params are NOT — in-flight dispatch snapshots alias
    the pre-flush params and must stay valid.

    The with-root variant takes the PRECOMPUTED reference r^t (from
    :class:`RootReferenceCache` via :func:`make_root_fn`) instead of raw
    root batches, so the D_root SGD pass is not baked into — and re-run
    by — every flush.

    ``mesh`` (sharded buffers only) is the pod mesh the hierarchical
    flush shard_maps over; None runs the single-device emulation."""
    if with_root:

        @partial(jax.jit, donate_argnums=(3,))
        def fn(
            params, drag_state, rnd, buf, key, adv_state, trust_state, reference,
            monitor_state=(),
        ):
            return flush(
                loss_fn, cfg, params, drag_state, rnd, buf, key,
                adv_state=adv_state, trust_state=trust_state, reference=reference,
                mesh=mesh, monitor_state=monitor_state,
            )

    else:

        @partial(jax.jit, donate_argnums=(3,))
        def fn(
            params, drag_state, rnd, buf, key, adv_state, trust_state,
            monitor_state=(),
        ):
            return flush(
                loss_fn, cfg, params, drag_state, rnd, buf, key,
                adv_state=adv_state, trust_state=trust_state, mesh=mesh,
                monitor_state=monitor_state,
            )

    return fn


def make_root_fn(loss_fn: Callable, cfg: StreamConfig):
    """Jitted trusted-reference pass: r^t from U SGD steps on D_root."""
    grad_fn = jax.grad(loss_fn)

    def fn(params, root_batches):
        return br_drag.root_reference(
            params, lambda p, b: grad_fn(p, b), root_batches, cfg.lr
        )

    return jax.jit(fn)


class RootReferenceCache:
    """Version-keyed cache of the BR-DRAG root reference r^t.

    The D_root SGD pass costs a full local-training's worth of compute
    per flush.  Its inputs change only when the model version advances,
    so the cache keys on the version (coarsened to
    ``refresh_every``-sized buckets): within a bucket every flush reuses
    the stored r.  ``refresh_every = 1`` is exact — r is recomputed
    whenever the version advances, and a cache hit can only serve the
    bit-identical array that a recompute would produce.
    ``refresh_every > 1`` trades exactness for throughput by serving a
    slightly stale r while the version advances slowly (ROADMAP open
    item); BR-DRAG's norm clamp keeps the calibration bounded either way.
    """

    def __init__(self, compute_fn, refresh_every: int = 1, enabled: bool = True):
        self.compute_fn = compute_fn  # (params, root_batches) -> r
        self.refresh_every = max(int(refresh_every), 1)
        self.enabled = enabled
        self.hits = 0
        self.misses = 0
        self._key: int | None = None
        self._reference = None

    def get(self, version: int, params, root_batches):
        key = int(version) // self.refresh_every
        if self.enabled and key == self._key:
            self.hits += 1
            return self._reference
        self.misses += 1
        reference = self.compute_fn(params, root_batches)
        if self.enabled:
            self._key, self._reference = key, reference
        return reference

    def clear(self) -> None:
        self._key, self._reference = None, None


def make_client_fn(loss_fn: Callable, cfg: StreamConfig):
    """Jitted single-client local update (plain SGD — the stream engine
    carries no per-client server state, so client-variant algorithms like
    scaffold/fedacg stay in the synchronous regime)."""

    def fn(params, batches_u):
        g, _ = local_update(with_counters(loss_fn), params, batches_u, cfg.lr, variant="sgd")
        return g

    return jax.jit(fn)


class AsyncStreamServer:
    """Host-side driver: owns the StreamState plus the jitted step fns.

    The event loop calls ``client_update`` (against the dispatch-time
    snapshot), ``ingest``, and ``flush_if_ready`` — the server never
    blocks on slow clients.
    """

    def __init__(
        self,
        loss_fn: Callable,
        params: pt.Pytree,
        cfg: StreamConfig,
        n_clients: int | None = None,
        root_cache: bool = True,
        mesh=None,  # pod mesh for cfg.shards > 0 (None = emulation path)
        session: obs_session.TelemetrySession | None = None,
    ):
        self.cfg = cfg
        self.loss_fn = loss_fn  # the compiled megastep re-traces the flush
        # telemetry session (repro.obs): flush bundles ring-accumulate
        # here, host-side drop decisions mirror into its buckets, and the
        # ingest/flush host boundaries carry spans.  None = inert.
        self.session = session or obs_session.TelemetrySession(enabled=False)
        self.with_root = cfg.algorithm in ("br_drag", "fltrust")
        self.adversary = adversary_engine.resolve(cfg.attack, dict(cfg.attack_kw))
        self.state = init_stream_state(
            params, cfg.buffer_capacity, cfg, n_clients, mesh
        )
        self._ingest = (
            sharded_mod.make_ingest_fn() if cfg.shards > 0
            else buf_mod.make_ingest_fn()
        )
        self._ingest_packed = buf_mod.make_ingest_packed_fn()
        self._flush = make_flush_fn(loss_fn, cfg, self.with_root, mesh)
        self._client = make_client_fn(loss_fn, cfg)
        self.root_cache = RootReferenceCache(
            make_root_fn(loss_fn, cfg), cfg.root_refresh_every, enabled=root_cache
        ) if self.with_root else None
        self.t = 0  # host-side mirror of state.round (avoids device syncs)
        self.ingested = 0  # accepted since last flush (mirrors buffer.count)
        self.dropped = 0  # uploads refused because the buffer was full
        self.packed_ingests = 0  # accepted numpy uploads written as one packed row

    @property
    def params(self) -> pt.Pytree:
        return self.state.params

    def client_update(self, params_snapshot: pt.Pytree, batches_u) -> pt.Pytree:
        return self._client(params_snapshot, batches_u)

    def ingest(
        self, g: pt.Pytree, dispatch_round: int, is_malicious: bool, client_id: int = 0
    ) -> bool:
        """Accept one upload.  Returns False — and counts the drop — when
        the buffer is already at threshold; call ``flush_if_ready`` first
        if the update must not be lost.

        On the unsharded path a numpy upload off the wire is packed on
        the host with its tags (``core.flat.pack_upload``), so it costs
        one transfer and one execute of a write that takes no host
        scalars; it is counted in ``packed_ingests``.  A device upload
        keeps the scalar-tagged write.

        Spans: ``ingest`` (with ``packed`` on the unsharded path), and on
        the unsharded path its children ``ingest.h2d`` (the upload's
        host-to-device copy) and ``ingest.write`` (the jitted, donated
        buffer write).  The sharded path routes the upload to a pod inside
        its jitted write, so it keeps the single ``ingest`` span."""
        with obs_trace.span("ingest", client_id=int(client_id)) as sp:
            if self.ingested >= self.cfg.buffer_capacity:
                self.dropped += 1
                # the refusal happens HOST-side (the upload never touches
                # the device), so the bucket accounting mirrors here
                self.session.record_drop(client_id)
                sp.set(dropped=True)
                return False
            if self.cfg.shards > 0:
                buffer = self._ingest(
                    self.state.buffer, g, dispatch_round, is_malicious, client_id
                )
            else:
                with obs_trace.span("ingest.h2d"):
                    row = flat_mod.pack_upload(
                        g, dispatch_round, is_malicious, client_id
                    )
                    g = jax.device_put(g if row is None else row)
                sp.set(packed=row is not None)
                with obs_trace.span("ingest.write"):
                    if row is None:
                        buffer = self._ingest(
                            self.state.buffer, g, dispatch_round, is_malicious,
                            client_id,
                        )
                    else:
                        buffer = self._ingest_packed(self.state.buffer, g)
                        self.packed_ingests += 1
            self.state = self.state._replace(buffer=buffer)
            self.ingested += 1
            return True

    def buffer_ready(self) -> bool:
        # host-side mirror: count == ingested since last flush
        return self.ingested >= self.cfg.buffer_capacity

    def root_reference(self, root_batches) -> pt.Pytree:
        """Trusted r^t for the CURRENT model version, through the cache."""
        assert self.with_root
        return self.root_cache.get(self.t, self.state.params, root_batches)

    def flush_if_ready(self, key, root_batches=None) -> dict | None:
        """Flush the buffer once it holds K uploads; None before that.

        Spans: ``flush``, with children ``root_reference`` (BR-DRAG and
        FLTrust), ``flush.dispatch`` (the jitted flush call, from entry
        to return; ``sharded_flush`` on the sharded path) and
        ``flush.mirror`` (the host-side state, alert decode and the
        telemetry ring push)."""
        if not self.buffer_ready():
            return None
        with obs_trace.span("flush", round=self.t, shards=self.cfg.shards):
            args = [
                self.state.params, self.state.drag, self.state.round,
                self.state.buffer, key, self.state.adversary, self.state.trust,
            ]
            if self.with_root:
                assert root_batches is not None
                with obs_trace.span("root_reference"):
                    args.append(self.root_reference(root_batches))
            args.append(self.state.monitor)
            # the sharded one-psum flush keeps its own span name (host
            # boundary — never in jit)
            dispatch = obs_trace.span(
                sharded_mod.FLUSH_SPAN, **sharded_mod.span_attrs(self.cfg)
            ) if self.cfg.shards > 0 else obs_trace.span("flush.dispatch")
            with dispatch:
                params, new_drag, rnd, buf, adv, trust, metrics = (
                    self._flush(*args)
                )
            with obs_trace.span("flush.mirror"):
                new_monitor = self.state.monitor
                obs_mon = metrics.pop("obs_monitor", None)
                if obs_mon is not None:
                    new_monitor, verdict = obs_mon
                    self.session.record_alerts(verdict, new_monitor)
                self.state = StreamState(
                    params=params, round=rnd, drag=new_drag, buffer=buf,
                    adversary=adv, trust=trust, monitor=new_monitor,
                )
                self.t += 1
                self.ingested = 0
                # the bundle is telemetry, not a training metric: it leaves
                # the metrics dict here and accumulates in the session's ring
                self.session.record_flush(metrics.pop("obs", None))
            return metrics

    def serve_compiled(
        self, n_events: int, *, data, seed, key, concurrency: int,
        local_steps: int, batch_size: int, latency, bias_table=None,
        root_samples: int = 3000, rng=None, block: int = 0, chunk: int = 64,
    ) -> dict:
        """Complete ``n_events`` (a multiple of K) through the compiled
        megastep (``repro.stream.megastep``): the whole event -> client
        update -> ingest -> flush cycle runs as one lax.scan, with host
        round-trips only at chunk boundaries.  Uses hash-mode event
        sampling — a distinct-but-deterministic regime from the MT19937
        host loop, pinned bit-for-bit against its own per-event unrolled
        execution (``megastep.serve_unrolled``).  The first call builds
        the driver; later calls continue the same stream (the kwargs are
        then ignored).  Returns stacked per-flush metrics arrays."""
        from repro.stream import megastep as mega

        if getattr(self, "_compiled", None) is None:
            self._compiled = mega.CompiledStream(
                self, data, seed=seed, key=key, concurrency=concurrency,
                local_steps=local_steps, batch_size=batch_size,
                latency=latency, bias_table=bias_table,
                root_samples=root_samples, rng=rng, block=block, chunk=chunk,
            )
        return self._compiled.serve_events(n_events)


# ------------------------------------------------------------- experiment
@dataclasses.dataclass
class StreamExperimentConfig:
    """DEPRECATED shim — prefer ``repro.api.ExperimentSpec`` with an
    :class:`~repro.api.AsyncRegime` / :class:`~repro.api.ShardedRegime`.

    Kept so existing entry points and tests double as the API
    redesign's oracle; ``run_stream_experiment`` adopts it via
    ``repro.api.lowering.spec_from_stream_config`` (lossless, including
    the legacy ``attack_kw``/``trust_kw``/``latency_kw``
    tuple-of-pairs).
    """

    dataset: str = "emnist"
    model: str = "mlp"
    n_workers: int = 40  # M (the EVENT layer scales far beyond this;
    #                       the materialised data pipeline is the limit)
    concurrency: int = 16  # W — in-flight dispatches
    flushes: int = 60  # T — global steps to run
    buffer_capacity: int = 10  # K
    latency: str = "exponential"
    latency_kw: tuple = ()  # e.g. (("scale", 2.0),)
    local_steps: int = 5  # U
    batch_size: int = 10  # B
    lr: float = 0.01
    beta: float = 0.1  # Dirichlet heterogeneity
    algorithm: str = "drag"
    attack: str = "none"  # any repro.adversary registry name
    attack_kw: tuple = ()
    malicious_fraction: float = 0.0
    alpha: float = 0.25
    c: float = 0.1
    c_br: float = 0.5
    discount: str = "poly"
    discount_a: float = 0.5
    trust: bool = False  # divergence-history reputation (drag/br_drag)
    trust_kw: tuple = ()
    root_samples: int = 3000
    root_refresh_every: int = 1  # r^t cache coarsening (1 = exact)
    root_cache: bool = True  # disable to force a D_root pass per flush
    shards: int = 0  # pod-sharded ingest buffer (repro.stream.sharded)
    eval_every: int = 10  # in flushes
    seed: int = 0

    def to_spec(self):
        """The declarative form (``repro.api.ExperimentSpec``)."""
        from repro.api import lowering

        return lowering.spec_from_stream_config(self)


def run_stream_experiment(
    exp,  # repro.api.ExperimentSpec (async/sharded) | legacy StreamExperimentConfig
    data=None,
    progress: Callable[[dict], None] | None = None,
    mesh=None,  # pod mesh for sharded regimes (None = emulation path)
    check: bool = True,  # False: spec already validated (api.compile)
) -> dict:
    """Event-driven training run; returns a history dict with accuracy,
    staleness, and throughput (virtual + wall) per eval point, the final
    ``params`` pytree and, for sharded buffers, ``slot_devices``: how
    many devices hold the pod slots."""
    from repro.api import lowering
    from repro.api.validation import ensure_executable, validate
    from repro.data.pipeline import build_federated_data
    from repro.models import factory

    spec = lowering.as_spec(exp)
    if spec.regime.kind not in ("async", "sharded"):
        raise ValueError(
            f"run_stream_experiment drives the async/sharded regimes; got a "
            f"{spec.regime.kind!r} regime — use repro.api.run / "
            "repro.fl.run_experiment"
        )
    if check:
        validate(spec, mesh=mesh)
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

    model = factory.build(spec.model)
    key, k_init = jax.random.split(key)
    _, params = model.init(k_init, data)
    loss_fn = model.loss

    # THE async lowering (repro.api.lowering): spec -> static flush config.
    # label_flipping resolves to a data-space passthrough in the adversary
    # registry, so it no longer needs host-side special-casing.
    cfg = lowering.stream_config(spec)
    from repro.adversary.stream_attacks import BiasedLatency
    from repro.stream.events import make_latency

    session = obs_session.session_from_spec(getattr(spec, "telemetry", None))
    server = AsyncStreamServer(
        loss_fn, params, cfg, n_clients=d.n_workers,
        root_cache=regime.root_cache, mesh=mesh, session=session,
    )
    malicious_lookup = lambda m: bool(data.malicious[m])  # noqa: E731
    latency = make_latency(regime.latency, **dict(regime.latency_kw))

    # non-stationary drift (DataSpec.drift): labels rotate with the model
    # version; train, root, and eval batches all see time-t labels
    from repro.data.pipeline import drift_labels

    drift_on = d.drift != "none" and d.drift_rate > 0.0

    eval_jit = jax.jit(model.accuracy)
    tb = data.test_batch()
    test_x = jnp.asarray(tb["x"])
    test_batch = {"x": test_x, "y": jnp.asarray(tb["y"])}

    history = {
        "flush": [], "accuracy": [], "staleness_mean": [],
        "virtual_time": [], "wall_s": [], "update_norm": [],
    }
    t0 = time.time()

    def record_eval(staleness_mean, virtual_time, update_norm, extra):
        with obs_trace.span("eval"):
            tbatch = test_batch
            if drift_on:
                tbatch = {
                    "x": test_x,
                    "y": jnp.asarray(drift_labels(
                        tb["y"].astype(np.int32), data.n_classes, server.t,
                        d.drift, d.drift_rate,
                    )),
                }
            acc = float(eval_jit(server.params, tbatch))
        history["flush"].append(server.t)
        history["accuracy"].append(acc)
        history["staleness_mean"].append(float(staleness_mean))
        history["virtual_time"].append(float(virtual_time))
        history["wall_s"].append(time.time() - t0)
        history["update_norm"].append(float(update_norm))
        if progress:
            progress({"flush": server.t, "accuracy": acc, **extra})

    if getattr(regime, "compiled", False):
        # ---- compiled serving (repro.stream.megastep): the event loop
        # runs device-resident, chunk boundaries are the only host stops —
        # aligned on eval points so the eval cadence matches the host loop
        from repro.stream.megastep import CompiledStream

        bias = None
        if spec.attack.name != "none":
            # the arrival-shaping half of async-native adversaries, as
            # the precomputed per-client table HashArrivals multiplies in
            bias = np.array(
                [
                    server.adversary.latency_bias(m, malicious_lookup(m))
                    for m in range(d.n_workers)
                ],
                np.float32,
            )
        cs = CompiledStream(
            server, data, seed=spec.seed, key=key,
            concurrency=regime.concurrency, local_steps=regime.local_steps,
            batch_size=regime.batch_size, latency=latency, bias_table=bias,
            root_samples=d.root_samples, rng=rng,
            **lowering.megastep_params(spec),
        )
        with session:
            while server.t < regime.flushes:
                boundary = (server.t // regime.eval_every + 1) * regime.eval_every
                c = min(boundary, regime.flushes) - server.t
                mets = cs.serve_flushes(c)
                if server.t % regime.eval_every == 0 or server.t == regime.flushes:
                    record_eval(
                        mets["staleness_mean"][-1], mets["virtual_time"][-1],
                        mets["update_norm_mean"][-1],
                        {k: float(v[-1]) for k, v in mets.items()},
                    )
        updates_total = cs.events_done
    else:
        if spec.attack.name != "none":
            # async-native adversaries shape arrival times (buffer_flood /
            # staleness_camouflage); for everything else the bias is 1.0
            latency = BiasedLatency(latency, server.adversary, malicious_lookup)
        stream = EventStream(
            d.n_workers,
            latency,
            seed=spec.seed,
            malicious_lookup=malicious_lookup,
            # churn/diurnal population dynamics (None = the exact legacy
            # draw path — the flag-off parity tests pin this)
            population=lowering.population_model(spec),
        )
        if regime.trust_gated_dispatch:
            # trust-aware sampling: skip quarantined clients (reputation 0)
            # at dispatch.  The gate reads a HOST mirror of the quarantine
            # mask, refreshed after every flush — dispatch never syncs the
            # device
            quarantine_mask = {"m": np.zeros(d.n_workers, bool)}
            stream.blocked_lookup = lambda m: bool(quarantine_mask["m"][m])

        # prime the pipeline: W concurrent jobs against the initial model
        inflight: dict[int, pt.Pytree] = {}
        for _ in range(regime.concurrency):
            ev = stream.dispatch(server.t)
            inflight[ev.seq] = server.params

        with session:
            while server.t < regime.flushes:
                ev = stream.next_completion()
                snapshot = inflight.pop(ev.seq)
                batch_np = data.sample_round(rng, [ev.client_id], regime.local_steps, regime.batch_size)
                y_np = batch_np["y"][0]
                if drift_on:
                    y_np = drift_labels(
                        y_np, data.n_classes, server.t, d.drift, d.drift_rate
                    )
                batches = {
                    "x": jnp.asarray(batch_np["x"][0]),
                    "y": jnp.asarray(y_np),
                }
                with obs_trace.span("client_update"):
                    g = server.client_update(snapshot, batches)
                server.ingest(g, ev.dispatch_round, ev.malicious, ev.client_id)

                # keep the pipeline full: re-dispatch against the CURRENT model
                ev2 = stream.dispatch(server.t)
                inflight[ev2.seq] = server.params

                metrics = None
                if server.buffer_ready():
                    key, k_flush = jax.random.split(key)
                    root = None
                    if server.with_root:
                        root_np = data.root_batches(
                            rng, regime.local_steps, regime.batch_size, d.root_samples
                        )
                        root_y = root_np["y"]
                        if drift_on:
                            root_y = drift_labels(
                                root_y, data.n_classes, server.t, d.drift,
                                d.drift_rate,
                            )
                        root = {"x": jnp.asarray(root_np["x"]), "y": jnp.asarray(root_y)}
                    metrics = server.flush_if_ready(k_flush, root)
                    if metrics is not None and regime.trust_gated_dispatch:
                        quarantine_mask["m"] = np.asarray(
                            server.state.trust.quarantined
                        )

                if metrics is not None and (
                    server.t % regime.eval_every == 0 or server.t == regime.flushes
                ):
                    record_eval(
                        metrics["staleness_mean"], stream.now,
                        metrics["update_norm_mean"],
                        {k: float(v) for k, v in metrics.items()},
                    )
        updates_total = stream.completed

    history["final_accuracy"] = history["accuracy"][-1] if history["accuracy"] else 0.0
    history["updates_total"] = updates_total
    history["updates_per_wall_s"] = updates_total / max(time.time() - t0, 1e-9)
    history["params"] = server.params
    if cfg.shards:
        history["slot_devices"] = len(server.state.buffer.slots.sharding.device_set)
    if server.root_cache is not None:
        history["root_cache_hits"] = server.root_cache.hits
        history["root_cache_misses"] = server.root_cache.misses
    if session.enabled:
        history["telemetry"] = session.summary()
    return history
