"""Telemetry plane (repro.obs): jit-safe metrics, spans, sinks, and the
ISSUE 6 acceptance invariants — recording changes NOTHING but the
observation (bit-for-bit numerics, same kernel/collective counts, jaxpr
untouched when off)."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (
    DROP_BUCKETS,
    HIST_BINS,
    JsonlSink,
    MemorySink,
    MetricsBundle,
    TelemetrySession,
    bundle_to_dict,
    counted_calls,
    flush_bundle,
    host_drop_bucket,
    perfetto_trace,
    ring_init,
    ring_push,
    ring_read,
    session_from_spec,
)
from repro.obs import trace as obs_trace

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------- metrics
class TestMetricsBundle:
    def test_bundle_recomputes_drag_coeffs_from_phase1_scalars(self):
        """div/lambda/a/b derived from (dots, g_sq, r_sq) must match the
        direct formula — O(K) math, no stack access."""
        k = 6
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (k, 32))
        r = jax.random.normal(jax.random.fold_in(key, 1), (32,))
        dots, g_sq, r_sq = g @ r, jnp.sum(g * g, axis=1), jnp.sum(r * r)
        phi = jnp.linspace(1.0, 0.5, k)
        b = flush_bundle(
            rnd=3, fill=k, capacity=k, stats=(dots, g_sq, r_sq),
            discounts=phi, c=0.3, mode="drag",
        )
        cos = np.asarray(dots / (jnp.sqrt(g_sq + 1e-12) * jnp.sqrt(r_sq + 1e-12)))
        lam = 0.3 * (1.0 - cos) * np.asarray(phi)
        np.testing.assert_allclose(float(b.div_mean), np.mean(1.0 - cos), rtol=1e-6)
        np.testing.assert_allclose(float(b.dod_max), np.max(lam), rtol=1e-6)
        np.testing.assert_allclose(
            float(b.coeff_a_mean), np.mean(1.0 - lam), rtol=1e-6
        )
        assert int(b.div_hist.sum()) == k and b.div_hist.shape == (HIST_BINS,)
        assert float(b.row_norm_max) == pytest.approx(
            float(jnp.max(jnp.sqrt(g_sq))), rel=1e-6
        )

    def test_missing_signals_record_neutral_defaults(self):
        b = flush_bundle(rnd=0, fill=4, capacity=8)
        assert float(b.discount_mean) == 1.0  # no staleness => fresh
        assert float(b.weight_min) == 1.0  # no trust => full weight
        assert float(b.dod_mean) == 0.0
        assert int(b.drops.sum()) == 0 and b.drops.shape == (DROP_BUCKETS,)
        assert b.pod_fill.shape == (1,) and int(b.pod_fill[0]) == 4
        d = bundle_to_dict(b)
        json.dumps(d)  # JSON-safe
        assert d["capacity"] == 8

    def test_bundle_is_jittable(self):
        def f(dots, g_sq, r_sq):
            return flush_bundle(
                rnd=1, fill=4, capacity=4, stats=(dots, g_sq, r_sq),
                c=0.5, mode="br_drag",
            )

        b = jax.jit(f)(jnp.ones((4,)), jnp.ones((4,)) * 2.0, jnp.ones(()))
        assert math.isfinite(float(b.dod_mean))
        assert isinstance(b, MetricsBundle)


class TestMetricsRing:
    def test_ring_wraps_and_reads_oldest_first(self):
        proto = flush_bundle(rnd=0, fill=1, capacity=4)
        ring = ring_init(proto, capacity=4)
        for i in range(6):
            ring = ring_push(ring, flush_bundle(rnd=i, fill=1, capacity=4))
        got = [e["round"] for e in ring_read(ring)]
        assert got == [2, 3, 4, 5]  # oldest two overwritten
        assert int(ring.total) == 6

    def test_ring_partial_fill(self):
        proto = flush_bundle(rnd=0, fill=1, capacity=2)
        ring = ring_init(proto, capacity=8)
        ring = ring_push(ring, flush_bundle(rnd=7, fill=1, capacity=2))
        assert [e["round"] for e in ring_read(ring)] == [7]

    def test_ring_exactly_full_drains_in_push_order(self):
        """cursor wraps to 0 at exactly-full: the drain's start index is
        cursor - n = -capacity, the most negative the wraparound path
        (obs/metrics.py ring_read) ever sees."""
        proto = flush_bundle(rnd=0, fill=1, capacity=4)
        ring = ring_init(proto, capacity=4)
        for i in range(4):
            ring = ring_push(ring, flush_bundle(rnd=i, fill=1, capacity=4))
        assert int(ring.cursor) == 0  # wrapped
        assert [e["round"] for e in ring_read(ring)] == [0, 1, 2, 3]

    def test_ring_one_past_full_evicts_only_oldest(self):
        proto = flush_bundle(rnd=0, fill=1, capacity=4)
        ring = ring_init(proto, capacity=4)
        for i in range(5):
            ring = ring_push(ring, flush_bundle(rnd=i, fill=1, capacity=4))
        assert int(ring.cursor) == 1 and int(ring.total) == 5
        assert [e["round"] for e in ring_read(ring)] == [1, 2, 3, 4]

    def test_ring_many_wraps_retains_last_window(self):
        cap, pushes = 3, 11  # 3 full wraps + 2
        proto = flush_bundle(rnd=0, fill=1, capacity=cap)
        ring = ring_init(proto, capacity=cap)
        for i in range(pushes):
            ring = ring_push(ring, flush_bundle(rnd=i, fill=1, capacity=cap))
        assert [e["round"] for e in ring_read(ring)] == [8, 9, 10]
        assert int(ring.total) == pushes

    def test_ring_capacity_one(self):
        proto = flush_bundle(rnd=0, fill=1, capacity=1)
        ring = ring_init(proto, capacity=1)
        for i in range(7):
            ring = ring_push(ring, flush_bundle(rnd=i, fill=1, capacity=1))
        assert [e["round"] for e in ring_read(ring)] == [6]

    def test_ring_jitted_push_wraps_identically(self):
        """The donated jitted push and the plain push agree across a
        wraparound boundary."""
        from repro.obs import make_ring_push

        proto = flush_bundle(rnd=0, fill=1, capacity=4)
        plain = ring_init(proto, capacity=4)
        jitted = ring_init(proto, capacity=4)
        push = make_ring_push()
        for i in range(6):
            b = flush_bundle(rnd=i, fill=1, capacity=4)
            plain = ring_push(plain, b)
            jitted = push(jitted, b)
        assert [e["round"] for e in ring_read(jitted)] == [
            e["round"] for e in ring_read(plain)
        ]


# ------------------------------------------------------- spans and sinks
def _profiled_host_events(trace_dir):
    """({name: [(start_ns, end_ns)]} of the host events, the profile's
    start on the wall clock in ns) from a ``jax.profiler.trace`` dir."""
    import glob

    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events, start_ns = {}, None
    for plane in pd.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start_ns = value
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    return events, start_ns


class TestTrace:
    def test_disabled_tracer_emits_nothing(self):
        sink = MemorySink()
        with obs_trace.span("nope"):
            pass
        assert sink.events == [] and not obs_trace.tracer.enabled

    def test_disabled_span_is_the_shared_null_span(self, monkeypatch):
        """No sink, no profiler: the shared no-op span, and no event is
        built (the clock is never read)."""
        def no_clock():
            raise AssertionError("a disabled span read the clock")

        monkeypatch.setattr(obs_trace, "_now_us", no_clock)
        sp = obs_trace.span("ingest", client_id=3)
        assert sp is obs_trace._NULL_SPAN
        with sp as inner:
            inner.set(dropped=True)

    def test_profiler_alone_annotates_without_events(self, tmp_path, monkeypatch):
        """A running profiler makes a span a ``repro.<name>`` annotation;
        with no sink attached no event is built."""
        def no_clock():
            raise AssertionError("an annotation-only span read the clock")

        monkeypatch.setattr(obs_trace, "_now_us", no_clock)
        with jax.profiler.trace(str(tmp_path)):
            with obs_trace.span("outer") as sp:
                sp.set(round=1)
                with obs_trace.span("outer.inner"):
                    pass
            assert obs_trace.span("x") is not obs_trace._NULL_SPAN
        assert obs_trace.span("x") is obs_trace._NULL_SPAN  # profiler stopped
        events, _ = _profiled_host_events(tmp_path)
        (o0, o1), = events["repro.outer"]
        (i0, i1), = events["repro.outer.inner"]
        assert o0 <= i0 <= i1 <= o1

    def test_span_events_are_on_the_profilers_clock(self, tmp_path):
        """With a sink attached under a running profiler, the recorded
        span and its ``repro.`` annotation start at the same instant."""
        sink = MemorySink()
        with jax.profiler.trace(str(tmp_path)):
            with obs_trace.tracer.attached(sink):
                with obs_trace.span("flush", round=0):
                    jnp.ones(8).block_until_ready()
        events, start_ns = _profiled_host_events(tmp_path)
        (a, b), = events["repro.flush"]
        ev, = sink.spans()
        # the annotation opens just before the span's stamp is taken
        assert abs((ev["ts_us"] * 1e3 - start_ns) - a) < 5e6
        assert abs(ev["dur_us"] * 1e3 - (b - a)) < 5e6

    def test_span_nesting_and_aggregation(self):
        sink = MemorySink()
        with obs_trace.tracer.attached(sink):
            with obs_trace.span("outer"):
                with obs_trace.span("inner", step=1) as sp:
                    sp.set(extra="x")
                with obs_trace.span("inner"):
                    pass
            obs_trace.counter("drops", 3)
        assert not obs_trace.tracer.enabled  # detached cleanly
        spans = sink.spans()
        # children emit before the parent closes
        assert [s["name"] for s in spans] == ["inner", "inner", "outer"]
        outer = spans[-1]
        assert all(s["parent"] == outer["span_id"] for s in spans[:2])
        assert spans[0]["attrs"] == {"step": 1, "extra": "x"}
        agg = obs_trace.aggregate_spans(sink.events)
        assert agg["inner"]["count"] == 2
        assert agg["outer"]["total_ms"] >= agg["inner"]["total_ms"]
        assert all(s["dur_us"] >= 0 for s in spans)

    def test_events_match_published_schema(self):
        sink = MemorySink()
        with obs_trace.tracer.attached(sink):
            with obs_trace.span("s"):
                pass
            obs_trace.counter("c", 1.0)
            obs_trace.tracer.alert("div_mean", round=1, value=0.5)
        assert sorted(ev["type"] for ev in sink.events) == sorted(obs_trace.EVENT_SCHEMA)
        for ev in sink.events:
            for field in obs_trace.EVENT_SCHEMA[ev["type"]]:
                assert field in ev, (ev["type"], field)
            assert ev["v"] == obs_trace.SCHEMA_VERSION

    def test_jsonl_sink_roundtrip(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        with JsonlSink(path) as sink:
            with obs_trace.tracer.attached(sink):
                with obs_trace.span("a", round=2):
                    pass
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 1 and lines[0]["name"] == "a"
        with pytest.raises(ValueError, match="closed"):
            sink.emit({"type": "counter", "name": "x", "ts_us": 0.0, "value": 1.0})

    def test_perfetto_export_shape(self):
        sink = MemorySink()
        with obs_trace.tracer.attached(sink):
            with obs_trace.span("work"):
                pass
            obs_trace.counter("fill", 4)
        trace = perfetto_trace(sink.events, process_name="proc")
        phases = [e["ph"] for e in trace["traceEvents"]]
        assert phases[0] == "M" and "X" in phases and "C" in phases
        x = next(e for e in trace["traceEvents"] if e["ph"] == "X")
        assert x["name"] == "work" and x["dur"] >= 0


class TestProbes:
    def test_counted_calls_counts_and_restores(self):
        from repro.kernels import drag_calibrate as dk
        from repro.kernels.instrument import count_kernel_calls

        orig = dk.dot_norms
        sink = MemorySink()
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (4, 16))
        r = jax.random.normal(jax.random.fold_in(key, 1), (16,))
        with count_kernel_calls(sink=sink) as calls:
            dk.dot_norms(g, r, interpret=True)
            dk.dot_norms(g, r, interpret=True)
        assert calls["dot_norms"] == 2 and calls["blend_reduce"] == 0
        assert dk.dot_norms is orig  # monkeypatch restored
        names = {e["name"] for e in sink.counters()}
        assert "calls/dot_norms" in names

    def test_counted_calls_generic_target(self):
        class Mod:
            @staticmethod
            def f(x):
                return x + 1

        with counted_calls({"f": (Mod, "f")}) as calls:
            Mod.f(1)
        assert calls == {"f": 1}


# ------------------------------------------------------------- session
class TestSession:
    def test_host_drop_bucket_matches_device_hash(self):
        from repro.stream import buffer as buf_mod

        for cid in (0, 1, 7, 123456, 2**31 - 1, 999999937):
            assert host_drop_bucket(cid) == int(buf_mod.drop_bucket(cid))

    def test_disabled_session_is_inert(self):
        s = session_from_spec(None)
        assert not s.enabled
        s.record_drop(3)
        s.record_flush(flush_bundle(rnd=0, fill=1, capacity=1))
        assert s.summary() == {"enabled": False}
        with s:
            assert not obs_trace.tracer.enabled

    def test_session_records_and_summarises(self, tmp_path):
        jsonl = str(tmp_path / "ev.jsonl")
        perfetto = str(tmp_path / "trace.json")
        s = TelemetrySession(
            enabled=True, ring_capacity=4, jsonl=jsonl, perfetto=perfetto
        )
        with s:
            with s.span("flush", round=0):
                pass
            s.record_flush(flush_bundle(rnd=0, fill=2, capacity=2))
            s.record_drop(11)
            s.record_drop(11)
            s.record_kernel_calls({"dot_norms": 1})
        out = s.summary()
        assert out["flushes_recorded"] == 1 and out["ring"][0]["fill"] == 2
        assert out["drops_total"] == 2
        assert out["drops_by_bucket"] == {str(host_drop_bucket(11)): 2}
        assert out["spans"]["flush"]["count"] == 1
        assert out["kernel_calls_traced"] == {"dot_norms": 1}
        json.dumps(out)  # provenance blob must be JSON-safe
        assert json.load(open(perfetto))["traceEvents"]
        assert [json.loads(l)["name"] for l in open(jsonl)] == ["flush"]


# ------------------------------------ engine invariants (the acceptance)
def _flush_setup(alg: str, telemetry: bool, shards: int = 0):
    from repro.stream import buffer as buf_mod
    from repro.stream import sharded
    from repro.stream.server import StreamConfig, init_stream_state

    p = {"w": jnp.ones((24,)), "b": jnp.zeros((5,))}
    cfg = StreamConfig(
        algorithm=alg, buffer_capacity=4, trust=True, discount="poly",
        shards=shards, telemetry=telemetry,
    )
    state = init_stream_state(p, 4, cfg, n_clients=8)
    key = jax.random.PRNGKey(0)
    buf = state.buffer
    ingest = sharded.ingest if shards else buf_mod.ingest
    for i in range(4):
        g = {"w": jax.random.normal(jax.random.fold_in(key, i), (24,)),
             "b": jax.random.normal(jax.random.fold_in(key, 100 + i), (5,))}
        buf = ingest(buf, g, 0, False, client_id=i)
    return p, cfg, state, buf, key


class TestTelemetryInvariance:
    """Recording may add an ``obs`` output and nothing else."""

    @pytest.mark.parametrize("alg", ["drag", "br_drag"])
    def test_flush_numerics_bit_for_bit(self, alg):
        from repro.stream.server import flush

        outs = {}
        for telemetry in (False, True):
            p, cfg, state, buf, key = _flush_setup(alg, telemetry)
            kwargs = dict(adv_state=state.adversary, trust_state=state.trust)
            if alg == "br_drag":
                kwargs["reference"] = {"w": jnp.ones((24,)) * 0.1,
                                       "b": jnp.ones((5,)) * 0.1}
            outs[telemetry] = flush(
                None, cfg, state.params, state.drag, state.round, buf, key,
                **kwargs,
            )
        m_off, m_on = outs[False][-1], outs[True][-1]
        assert "obs" not in m_off and "obs" in m_on
        obs = m_on.pop("obs")
        assert isinstance(obs, MetricsBundle)
        assert int(obs.fill) == 4 and math.isfinite(float(obs.dod_mean))
        assert m_off.keys() == m_on.keys()
        # params, drag state, and every shared metric: bit-for-bit equal
        for a, b in zip(jax.tree.leaves((outs[False][:4], m_off)),
                        jax.tree.leaves((outs[True][:4], m_on))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_flush_off_jaxpr_has_no_obs_outputs(self):
        """telemetry=False leaves the traced flush signature unchanged:
        same output count and no obs key — the off path IS the pre-obs
        program."""
        from repro.stream.server import flush

        jaxprs = {}
        for telemetry in (False, True):
            p, cfg, state, buf, key = _flush_setup("drag", telemetry)

            def fn(params, dstate, rnd, buf, key):
                out = flush(None, cfg, params, dstate, rnd, buf, key,
                            adv_state=state.adversary,
                            trust_state=state.trust)
                return out

            jaxprs[telemetry] = jax.make_jaxpr(fn)(
                state.params, state.drag, state.round, buf, key
            )
        n_off = len(jaxprs[False].jaxpr.outvars)
        n_on = len(jaxprs[True].jaxpr.outvars)
        assert n_on > n_off  # the bundle leaves are the ONLY addition
        extra = len(jax.tree.leaves(flush_bundle(rnd=0, fill=1, capacity=1)))
        assert n_on == n_off + extra

    def test_recorded_flush_is_still_minimum_kernel_passes(self):
        from repro.kernels.instrument import count_kernel_calls, expected_flush_calls
        from repro.stream.server import flush

        p, cfg, state, buf, key = _flush_setup("drag", telemetry=True)
        with count_kernel_calls() as calls:
            out = flush(None, cfg, state.params, state.drag, state.round,
                        buf, key, adv_state=state.adversary,
                        trust_state=state.trust)
        # d = 29, K = 4 -> VMEM-resident: one fused_flush, nothing else
        assert calls == expected_flush_calls(4, 29), calls
        assert calls["fused_flush"] == 1 and calls["blend"] == 0, calls
        assert "obs" in out[-1]

    def test_recorded_sharded_flush_is_still_one_psum(self):
        from repro.kernels import instrument
        from repro.stream.server import flush

        shards = 2
        p, cfg, state, buf, key = _flush_setup("drag", True, shards=shards)
        with instrument.count_collective_calls() as coll:
            with instrument.count_kernel_calls() as kern:
                out = flush(None, cfg, state.params, state.drag, state.round,
                            buf, key, adv_state=state.adversary,
                            trust_state=state.trust)
        assert coll == instrument.ONE_PSUM_CALLS, coll
        # each pod's sub-stack is VMEM-resident -> one fused_flush per pod
        assert kern["fused_flush"] == shards and kern["blend"] == 0
        obs = out[-1]["obs"]
        assert obs.pod_fill.shape == (shards,)
        assert int(obs.pod_fill.sum()) == 4

    @pytest.mark.parametrize("alg", ["drag", "fedavg"])
    def test_sync_round_numerics_bit_for_bit(self, alg):
        from repro.fl.round import (
            RoundConfig,
            init_server_state,
            make_round_fn,
        )

        def loss_fn(p, batch):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

        key = jax.random.PRNGKey(0)
        batches = {
            "x": jax.random.normal(key, (4, 1, 2, 3)),
            "y": jax.random.normal(jax.random.fold_in(key, 1), (4, 1, 2, 1)),
        }
        outs = {}
        for telemetry in (False, True):
            cfg = RoundConfig(algorithm=alg, local_steps=1, lr=0.1,
                              telemetry=telemetry)
            state = init_server_state({"w": jnp.zeros((3, 1))}, 4, cfg)
            fn = make_round_fn(loss_fn, cfg, with_root=False)
            outs[telemetry] = fn(
                state, batches, jnp.arange(4, dtype=jnp.int32),
                jnp.zeros((4,), bool), key,
            )
        (s_off, m_off), (s_on, m_on) = outs[False], outs[True]
        assert "obs" not in m_off
        m_on = dict(m_on)
        obs = m_on.pop("obs")
        assert int(obs.fill) == 4
        for a, b in zip(jax.tree.leaves(s_off.params), jax.tree.leaves(s_on.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the params stay bit-for-bit; a metric is a reduction that XLA
        # may fuse differently once the bundle also reads its inputs, which
        # moves its last bit or two (update_norm_mean: 1 ULP on jax 0.9)
        assert m_off.keys() == m_on.keys()
        for k in m_off:
            np.testing.assert_array_max_ulp(
                np.asarray(m_off[k]), np.asarray(m_on[k]), maxulp=2)


class TestEndToEnd:
    def test_recorded_async_run_produces_full_telemetry(self, tmp_path):
        """A recorded stream run yields the span-attributed wall-clock
        breakdown + metrics ring + JSONL + Perfetto (the acceptance
        artifact), and an unrecorded run leaves no trace."""
        from repro.api import (
            AggregationSpec,
            AsyncRegime,
            DataSpec,
            ExperimentSpec,
            ModelSpec,
            TelemetrySpec,
        )
        from repro.api import compile as api_compile

        jsonl = str(tmp_path / "ev.jsonl")
        perfetto = str(tmp_path / "trace.json")
        spec = ExperimentSpec(
            data=DataSpec(dataset="emnist", n_workers=6),
            model=ModelSpec("mlp"),
            aggregation=AggregationSpec("drag"),
            regime=AsyncRegime(flushes=2, concurrency=4, buffer_capacity=3,
                               local_steps=1, batch_size=4, eval_every=10),
            telemetry=TelemetrySpec(enabled=True, ring_capacity=8,
                                    jsonl=jsonl, perfetto=perfetto),
            seed=0,
        )
        h = api_compile(spec).run()
        tel = h["telemetry"]
        assert tel["flushes_recorded"] == 2
        for name in ("ingest", "flush", "client_update"):
            assert tel["spans"][name]["count"] >= 1, name
        assert all(math.isfinite(b["dod_mean"]) for b in tel["ring"])
        events = [json.loads(l) for l in open(jsonl)]
        assert any(e["name"] == "flush" for e in events)
        assert json.load(open(perfetto))["traceEvents"]
        assert not obs_trace.tracer.enabled  # session detached

        # off by default: no summary, no files, tracer untouched
        import dataclasses

        h_off = api_compile(
            dataclasses.replace(spec, telemetry=TelemetrySpec())
        ).run()
        assert "telemetry" not in h_off
        assert h_off["accuracy"] == h["accuracy"]  # recording is invisible
