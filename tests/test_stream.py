"""Async streaming engine tests: events, buffer, staleness calibration,
the async server loop, and the sync-bridge bit-for-bit equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators, br_drag, drag
from repro.core import pytree as pt
from repro.stream import buffer as buf_mod
from repro.stream import staleness as stale
from repro.stream.events import Constant, EventStream, Straggler, make_latency
from repro.stream.server import (
    AsyncStreamServer,
    StreamConfig,
    StreamExperimentConfig,
    flush,
    run_stream_experiment,
)


# ------------------------------------------------------------------ events
class TestEvents:
    def test_zero_latency_fifo(self):
        es = EventStream(100, "zero", seed=0)
        ids = [es.dispatch(0, client_id=i).client_id for i in range(10)]
        got = [es.next_completion().client_id for _ in range(10)]
        assert got == ids  # FIFO tie-breaking at equal completion times

    def test_virtual_clock_monotone(self):
        es = EventStream(1000, "exponential", seed=1)
        for _ in range(50):
            es.dispatch(0)
        last = 0.0
        for _ in range(50):
            ev = es.next_completion()
            assert ev.completion_time >= last
            assert es.now == ev.completion_time
            last = ev.completion_time

    def test_millions_of_clients_lazy(self):
        """O(in-flight) memory: 10M virtual clients, nothing materialised."""
        es = EventStream(10_000_000, "exponential", seed=2, malicious_fraction=0.3)
        for _ in range(64):
            es.dispatch(0)
        seen = set()
        for _ in range(64):
            ev = es.next_completion()
            seen.add(ev.client_id)
            es.dispatch(1)
        assert es.in_flight() == 64
        assert max(seen) < 10_000_000
        # hash-derived Byzantine flags approximate the configured fraction
        frac = np.mean([es.is_malicious(i) for i in range(5000)])
        assert 0.25 < frac < 0.35

    def test_malicious_deterministic_and_lookup(self):
        es = EventStream(100, "zero", seed=3, malicious_fraction=0.5)
        flags = [es.is_malicious(i) for i in range(100)]
        assert flags == [es.is_malicious(i) for i in range(100)]
        mal = np.zeros(10, bool)
        mal[7] = True
        es2 = EventStream(10, "zero", malicious_lookup=lambda m: bool(mal[m]))
        assert es2.is_malicious(7) and not es2.is_malicious(3)

    def test_straggler_systematic(self):
        lat = Straggler(Constant(1.0), spread=4.0, seed=0)
        rng = np.random.RandomState(0)
        a1, a2 = lat.sample(rng, 42), lat.sample(rng, 42)
        assert a1 == a2  # same client -> same deterministic speed class
        others = {lat.sample(rng, i) for i in range(20)}
        assert len(others) > 10  # spread across clients

    def test_latency_registry(self):
        for name in ("zero", "constant", "uniform", "exponential", "lognormal"):
            m = make_latency(name)
            assert m.sample(np.random.RandomState(0), 0) >= 0.0
        with pytest.raises(KeyError):
            make_latency("nope")


# ------------------------------------------------------------------ buffer
def _params():
    return {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3), "b": jnp.ones(2)}


def _flat(tree):
    from repro.core import flat as flat_mod

    return np.asarray(flat_mod.flatten_tree(tree))


class TestBuffer:
    def test_ingest_fill_and_stack(self):
        """Slots are the flat [K, d] update plane: row i == the flattened
        i-th upload, bit-for-bit."""
        p = _params()
        buf = buf_mod.init_buffer(p, capacity=4)
        assert buf.slots.shape == (4, 8)  # d = 6 + 2
        for i in range(4):
            g = jax.tree.map(lambda x: x * (i + 1.0), p)
            buf = buf_mod.ingest(buf, g, dispatch_round=i, is_malicious=(i == 2))
        assert int(buf.count) == 4
        np.testing.assert_array_equal(np.asarray(buf.dispatch_rounds), [0, 1, 2, 3])
        np.testing.assert_array_equal(np.asarray(buf.malicious), [0, 0, 1, 0])
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(buf.slots[i]), _flat(p) * (i + 1.0)
            )

    def test_ingest_overflow_drops(self):
        p = _params()
        buf = buf_mod.init_buffer(p, capacity=2)
        for i in range(3):
            buf = buf_mod.ingest(buf, jax.tree.map(lambda x: x + i, p), i, False)
        assert int(buf.count) == 2  # third write refused
        np.testing.assert_allclose(np.asarray(buf.slots[1]), _flat(p) + 1)

    def test_reset_keeps_storage(self):
        p = _params()
        buf = buf_mod.ingest(buf_mod.init_buffer(p, 2), p, 5, True)
        buf2 = buf_mod.reset(buf)
        assert int(buf2.count) == 0
        np.testing.assert_allclose(np.asarray(buf2.slots[0]), _flat(p))

    def test_staleness_tags(self):
        p = _params()
        buf = buf_mod.init_buffer(p, 3)
        for t in (0, 2, 4):
            buf = buf_mod.ingest(buf, p, t, False)
        taus = buf_mod.staleness(buf, server_round=4)
        np.testing.assert_array_equal(np.asarray(taus), [4, 2, 0])

    def test_jitted_donated_ingest(self):
        p = _params()
        fn = buf_mod.make_ingest_fn()
        buf = buf_mod.init_buffer(p, 8)
        for i in range(8):
            buf = fn(buf, jax.tree.map(lambda x: x * i, p), i, False)
        assert int(buf.count) == 8
        np.testing.assert_allclose(np.asarray(buf.slots[3]), 3.0 * _flat(p))

    def test_ingest_accepts_already_flat_rows(self):
        """The flatten boundary is idempotent: a pre-flattened [d] row
        ingests identically to its pytree form."""
        p = _params()
        b1 = buf_mod.ingest(buf_mod.init_buffer(p, 2), p, 0, False)
        from repro.core import flat as flat_mod

        b2 = buf_mod.ingest(
            buf_mod.init_buffer(p, 2), flat_mod.flatten_tree(p), 0, False
        )
        np.testing.assert_array_equal(np.asarray(b1.slots), np.asarray(b2.slots))

    def test_packed_write_matches_ingest_field_for_field(self):
        """The packed write leaves every BufferState field as ingest does,
        through a full buffer and a refused write (counted in drops)."""
        from repro.core import flat as flat_mod

        p = jax.tree.map(np.asarray, _params())
        tags = [(0, False, 0), (7, True, 1), (2**31 - 2, False, 1999),
                (3, True, 2**31 - 1)]
        a = buf_mod.init_buffer(p, capacity=3)
        b = buf_mod.init_buffer(p, capacity=3)
        packed = buf_mod.make_ingest_packed_fn()
        for i, (rnd, mal, cid) in enumerate(tags):  # the 4th is refused
            g = jax.tree.map(lambda x: x * (i + 1.5), p)
            a = buf_mod.ingest(a, g, rnd, mal, cid)
            b = packed(b, jnp.asarray(flat_mod.pack_upload(g, rnd, mal, cid)))
            for name, x, y in zip(a._fields, a, b):
                assert x.dtype == y.dtype, name
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
        assert int(b.count) == 3 and int(b.drops.sum()) == 1

    def test_packed_write_takes_the_buffer_and_one_row(self):
        """No host scalar is an argument of the packed write: its jitted
        signature is the buffer's arrays and the packed row, nothing else."""
        import inspect

        from repro.core import flat as flat_mod

        p = jax.tree.map(np.asarray, _params())
        buf = buf_mod.init_buffer(p, 2)
        row = flat_mod.pack_upload(p, 4, True, 9)
        assert list(inspect.signature(buf_mod.ingest_packed).parameters) == ["buf", "row"]
        jaxpr = jax.make_jaxpr(buf_mod.ingest_packed)(buf, row)
        avals = [v.aval for v in jaxpr.jaxpr.invars]
        assert len(avals) == len(buf) + 1
        assert avals[-1].shape == row.shape and avals[-1].dtype == jnp.float32

    def test_as_stack_round_trips_metadata(self):
        from repro.core import flat as flat_mod
        from repro.stream import buffer as bm

        p = _params()
        buf = bm.init_buffer(p, 3)
        for i, t in enumerate((0, 2, 4)):
            buf = bm.ingest(buf, p, t, False, client_id=10 + i)
        stack = bm.as_stack(buf, flat_mod.spec_of(p), server_round=4)
        np.testing.assert_array_equal(np.asarray(stack.staleness), [4, 2, 0])
        np.testing.assert_array_equal(np.asarray(stack.client_ids), [10, 11, 12])
        back = stack.row_tree(1)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------- staleness
class TestStaleness:
    def test_phi_of_zero_is_one(self):
        tau = jnp.zeros(5, jnp.int32)
        for name in stale.DISCOUNTS:
            np.testing.assert_allclose(
                np.asarray(stale.make_discount(name, 0.7)(tau)), 1.0
            )

    def test_phi_monotone_decreasing(self):
        tau = jnp.arange(10, dtype=jnp.int32)
        for name in ("poly", "exp"):
            phi = np.asarray(stale.make_discount(name, 0.5)(tau))
            assert np.all(np.diff(phi) < 0) and phi[0] == 1.0

    def test_fresh_updates_match_sync_drag_bitwise(self):
        """discounts == 1 -> staleness round step IS drag.round_step."""
        key = jax.random.PRNGKey(0)
        p = {"w": jax.random.normal(key, (4, 3))}
        ups = {"w": jax.random.normal(jax.random.fold_in(key, 1), (6, 4, 3))}
        state = drag.DragState(
            reference={"w": jax.random.normal(jax.random.fold_in(key, 2), (4, 3))},
            initialized=jnp.asarray(True),
        )
        ones = jnp.ones(6, jnp.float32)
        p1, s1, m1 = drag.round_step(p, state, ups, alpha=0.25, c=0.3)
        p2, s2, m2 = stale.drag_round_step(p, state, ups, ones, alpha=0.25, c=0.3)
        np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(p2["w"]))
        np.testing.assert_array_equal(
            np.asarray(s1.reference["w"]), np.asarray(s2.reference["w"])
        )

    def test_stale_updates_calibrated_less(self):
        """phi < 1 shrinks the DoD: a divergent stale update keeps more of
        its raw direction than the same update fresh."""
        key = jax.random.PRNGKey(3)
        r = {"w": jnp.ones(8)}
        g = {"w": jax.random.normal(key, (8,)) - 1.0}  # misaligned
        lam_fresh = drag.degree_of_divergence(g, r, 0.5, 1.0)
        lam_stale = drag.degree_of_divergence(g, r, 0.5, 0.25)
        assert float(lam_stale) < float(lam_fresh)

    def test_br_drag_norm_clamp_survives_discount(self):
        """BR-DRAG's ||v|| <= ||r|| bound (Appendix B) holds for any
        phi in (0, 1]: lam stays in [0, 2c] and the clamp is by scale."""
        key = jax.random.PRNGKey(4)
        r = {"w": jax.random.normal(key, (16,))}
        ups = {"w": 100.0 * jax.random.normal(jax.random.fold_in(key, 1), (5, 16))}
        disc = jnp.asarray([1.0, 0.5, 0.25, 0.125, 1.0])
        _, lams = stale.br_drag_aggregate(ups, r, 0.5, disc)
        vs = jax.vmap(lambda g, lam: pt.tree_norm(br_drag.calibrate(g, r, lam)))(ups, lams)
        rn = float(pt.tree_norm(r))
        assert np.all(np.asarray(vs) <= rn * (1.0 + 1e-5))


# ---------------------------------------------------------- flush registry
def test_flush_through_every_nonreference_rule():
    """The buffer flushes through ANY rule in aggregators.AGGREGATORS."""
    key = jax.random.PRNGKey(0)
    p = {"w": jnp.zeros((4, 2))}
    rules = sorted(set(aggregators.AGGREGATORS) - aggregators.NEEDS_REFERENCE)
    for rule in rules:
        cfg = StreamConfig(algorithm=rule, buffer_capacity=6, n_byzantine_hint=1)
        buf = buf_mod.init_buffer(p, 6)
        for i in range(6):
            g = {"w": jax.random.normal(jax.random.fold_in(key, i), (4, 2))}
            buf = buf_mod.ingest(buf, g, i, False)
        params, _, rnd, buf2, _, _, metrics = flush(
            None, cfg, p, drag.init_state(p), jnp.int32(6), buf, key
        )
        assert int(rnd) == 7 and int(buf2.count) == 0
        assert np.isfinite(float(metrics["delta_norm"])), rule
        assert float(pt.tree_norm(params)) > 0.0, rule
    # client-variant algorithms must be rejected, not silently run as
    # fedavg (stream clients are plain SGD)
    buf = buf_mod.init_buffer(p, 2)
    buf = buf_mod.ingest(buf, p, 0, False)
    buf = buf_mod.ingest(buf, p, 0, False)
    for alg in ("fedprox", "scaffold", "fedacg"):
        with pytest.raises(ValueError, match="client-variant"):
            flush(None, StreamConfig(algorithm=alg), p, drag.init_state(p),
                  jnp.int32(0), buf, key)
    # drag flushes too (reference maintained internally)
    cfg = StreamConfig(algorithm="drag", buffer_capacity=6)
    buf = buf_mod.init_buffer(p, 6)
    for i in range(6):
        buf = buf_mod.ingest(buf, {"w": jnp.ones((4, 2))}, i, False)
    params, dstate, _, _, _, _, metrics = flush(
        None, cfg, p, drag.init_state(p), jnp.int32(0), buf, key
    )
    assert bool(dstate.initialized) and float(metrics["delta_norm"]) > 0.0


# ------------------------------------------------------- bridge equivalence
def _mlp_setup(n_workers=12, mal=0.0, attack="none"):
    from repro.data.pipeline import build_federated_data
    from repro.models import cnn

    data = build_federated_data(
        "emnist", n_workers, 0.3, malicious_fraction=mal, attack=attack, seed=0
    )
    init_fn, apply_fn = cnn.MODELS["mlp"]
    in_dim = int(np.prod(data.x.shape[1:]))
    params = init_fn(jax.random.PRNGKey(0), in_dim, 64, data.n_classes)

    def loss_fn(p, b):
        return cnn.classification_loss(apply_fn, p, b)

    return data, params, loss_fn


class TestBridgeEquivalence:
    # tier-1 keeps one algorithm (drag — the richest path: calibration +
    # bootstrap + reference EMA); the other two ride the weekly slow tier
    @pytest.mark.parametrize("alg", [
        pytest.param("fedavg", marks=pytest.mark.slow),
        "drag",
        pytest.param("br_drag", marks=pytest.mark.slow),
    ])
    def test_bit_for_bit_vs_federated_round(self, alg):
        """ISSUE acceptance: capacity-S, zero-latency, phi=none stream ==
        synchronous federated_round, exactly, over a 3-round trajectory."""
        from repro.fl import bridge
        from repro.fl.round import RoundConfig, federated_round, init_server_state

        data, params, loss_fn = _mlp_setup()
        with_root = alg == "br_drag"
        cfg = RoundConfig(algorithm=alg, local_steps=2, lr=0.05)
        s_sync = init_server_state(params, 12)
        s_str = init_server_state(params, 12)
        rng = np.random.RandomState(1)
        k = jax.random.PRNGKey(7)
        for _ in range(3):
            sel = rng.choice(12, size=5, replace=False)
            bn = data.sample_round(rng, sel, 2, 4)
            batches = {"x": jnp.asarray(bn["x"]), "y": jnp.asarray(bn["y"])}
            mask = jnp.asarray(data.malicious[sel])
            k, kr = jax.random.split(k)
            root = None
            if with_root:
                rn = data.root_batches(rng, 2, 4, 500)
                root = {"x": jnp.asarray(rn["x"]), "y": jnp.asarray(rn["y"])}
            args = [batches, jnp.asarray(sel, jnp.int32), mask, kr]
            s_sync, _ = federated_round(loss_fn, s_sync, cfg, *args, root_batches=root)
            s_str, _ = bridge.streamed_round(
                loss_fn, s_str, cfg, *args, root_batches=root, jit_client=False
            )
            for a, b in zip(jax.tree.leaves(s_sync.params), jax.tree.leaves(s_str.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree.leaves(s_sync.drag.reference),
                jax.tree.leaves(s_str.drag.reference),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(s_str.round) == 3

    def test_state_conversion_roundtrip(self):
        from repro.fl import bridge
        from repro.fl.round import init_server_state

        _, params, _ = _mlp_setup()
        s = init_server_state(params, 12)
        st = bridge.to_stream_state(s, capacity=5)
        back = bridge.to_sync_state(st, n_workers=12)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(s.params)[0]),
            np.asarray(jax.tree.leaves(back.params)[0]),
        )
        assert int(back.round) == 0

    def test_client_variant_algorithms_rejected(self):
        from repro.fl import bridge
        from repro.fl.round import RoundConfig

        with pytest.raises(ValueError):
            bridge.stream_config_from_round(RoundConfig(algorithm="scaffold"), 4)


# ------------------------------------------------------------ async server
class TestAsyncServer:
    def test_flush_threshold_and_reset(self):
        def loss_fn(p, batch):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

        p = {"w": jnp.zeros((3, 1))}
        cfg = StreamConfig(algorithm="fedavg", buffer_capacity=3, local_steps=2, lr=0.1)
        server = AsyncStreamServer(loss_fn, p, cfg)
        key = jax.random.PRNGKey(0)
        batch = {
            "x": jax.random.normal(key, (2, 4, 3)),
            "y": jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 1)),
        }
        for i in range(2):
            g = server.client_update(server.params, batch)
            server.ingest(g, 0, False)
            assert server.flush_if_ready(key) is None  # below threshold
        g = server.client_update(server.params, batch)
        server.ingest(g, 0, False)
        metrics = server.flush_if_ready(key)
        assert metrics is not None and server.t == 1
        assert int(server.state.round) == 1
        assert int(server.state.buffer.count) == 0
        assert float(metrics["staleness_mean"]) == 0.0

    def test_numpy_upload_lands_as_its_device_twin(self):
        """An upload off the wire (numpy leaves, flattened on the host)
        fills the same slot, bit for bit, as the same upload on device."""
        p = _params()
        cfg = StreamConfig(algorithm="fedavg", buffer_capacity=2)
        up = {"w": np.linspace(-1, 1, 6, dtype=np.float32).reshape(2, 3),
              "b": np.array([0.5, -2.0], np.float32)}
        slots = []
        for g in (up, jax.tree.map(jnp.asarray, up)):
            server = AsyncStreamServer(lambda p_, b: 0.0, p, cfg)
            assert server.ingest(g, 0, False, client_id=3)
            slots.append(np.asarray(server.state.buffer.slots[0]))
        np.testing.assert_array_equal(slots[0], slots[1])
        np.testing.assert_array_equal(slots[0], _flat(up))

    @staticmethod
    def _drag_server(p):
        cfg = StreamConfig(algorithm="drag", buffer_capacity=3, discount="poly",
                           trust=True)
        return AsyncStreamServer(lambda p_, b: 0.0, p, cfg, n_clients=40)

    @staticmethod
    def _uploads(n):
        rng = np.random.RandomState(0)
        return [{"w": rng.randn(2, 3).astype(np.float32),
                 "b": rng.randn(2).astype(np.float32)} for _ in range(n)]

    def test_numpy_and_device_uploads_flush_identically(self):
        """Packed numpy uploads and the same uploads on the device (the
        scalar-tagged write) give the same params, trust table and
        metrics, flush for flush: client ids and staleness tags cross
        exactly."""
        p = _params()
        ups = self._uploads(12)
        runs = []
        for on_device in (False, True):
            server = self._drag_server(p)
            flushes = []
            for i, up in enumerate(ups):
                g = jax.tree.map(jnp.asarray, up) if on_device else up
                assert server.ingest(g, max(server.t - i % 2, 0), False,
                                     client_id=5 + i % 4)
                met = server.flush_if_ready(jax.random.PRNGKey(0))
                if met is not None:
                    flushes.append(jax.tree.map(
                        np.asarray, (server.params, server.state.trust, met)))
            runs.append(flushes)
        assert len(runs[0]) == 4
        for a, b in zip(*runs):
            jax.tree.map(np.testing.assert_array_equal, a, b)

    def test_packed_ingests_count_numpy_uploads_only(self):
        """Every accepted numpy upload takes the packed write, no device
        upload does, and the ``ingest`` span says which it took."""
        from repro.obs import trace as obs_trace
        from repro.obs.sinks import MemorySink

        server = self._drag_server(_params())
        ups = self._uploads(4)
        sink = MemorySink()
        with obs_trace.tracer.attached(sink):
            for up in ups[:2]:
                assert server.ingest(up, 0, False, client_id=1)
            assert server.ingest(jax.tree.map(jnp.asarray, ups[2]), 0, False, 2)
            assert not server.ingest(ups[3], 0, False, client_id=3)  # full
        assert server.packed_ingests == 2 and server.dropped == 1
        attrs = [s.get("attrs", {}) for s in sink.spans() if s["name"] == "ingest"]
        assert [a.get("packed") for a in attrs] == [True, True, False, None]
        assert attrs[-1]["dropped"] is True

    def test_run_stream_experiment_drag_poly(self):
        exp = StreamExperimentConfig(
            n_workers=10, concurrency=8, flushes=6, buffer_capacity=4,
            latency="exponential", local_steps=2, batch_size=4,
            algorithm="drag", discount="poly", eval_every=3, seed=0,
        )
        h = run_stream_experiment(exp)
        assert h["flush"] and h["flush"][-1] == 6
        assert np.isfinite(h["final_accuracy"])
        assert all(s >= 0.0 for s in h["staleness_mean"])
        assert h["updates_total"] >= 6 * 4
        assert h["virtual_time"][-1] > 0.0

    def test_async_br_drag_under_attack(self):
        """All attack scenarios run asynchronously: BR-DRAG + sign flip."""
        exp = StreamExperimentConfig(
            n_workers=10, concurrency=8, flushes=6, buffer_capacity=4,
            latency="uniform", local_steps=2, batch_size=4,
            algorithm="br_drag", attack="sign_flipping", malicious_fraction=0.4,
            discount="exp", eval_every=6, root_samples=300, seed=1,
        )
        h = run_stream_experiment(exp)
        assert np.isfinite(h["final_accuracy"])
        assert h["final_accuracy"] > 0.0

    def test_stale_dispatch_tags_propagate(self):
        """With heavy latency spread, flushed buffers contain genuinely
        stale updates (tau > 0 shows up in the metrics)."""
        exp = StreamExperimentConfig(
            n_workers=10, concurrency=12, flushes=8, buffer_capacity=3,
            latency="straggler", local_steps=1, batch_size=4,
            algorithm="fedavg", eval_every=1, seed=2,
        )
        h = run_stream_experiment(exp)
        assert max(h["staleness_mean"]) > 0.0

    def test_client_ids_ride_the_buffer(self):
        p = _params()
        buf = buf_mod.init_buffer(p, 3)
        for cid in (11, 5, 7):
            buf = buf_mod.ingest(buf, p, 0, False, client_id=cid)
        np.testing.assert_array_equal(np.asarray(buf.client_ids), [11, 5, 7])

    def test_async_attack_with_trust_runs(self):
        """Async-native attack + trust-weighted BR-DRAG end to end on the
        real data pipeline."""
        exp = StreamExperimentConfig(
            n_workers=10, concurrency=8, flushes=6, buffer_capacity=4,
            latency="uniform", local_steps=2, batch_size=4,
            algorithm="br_drag", attack="staleness_camouflage",
            malicious_fraction=0.3, trust=True,
            discount="poly", eval_every=6, root_samples=300, seed=3,
        )
        h = run_stream_experiment(exp)
        assert np.isfinite(h["final_accuracy"]) and h["final_accuracy"] > 0.0


# ------------------------------------------------------ root-reference cache
class TestRootReferenceCache:
    def _setup(self, **cfg_kw):
        from repro.stream.server import AsyncStreamServer, StreamConfig

        def loss_fn(p, batch):
            return jnp.mean((p["w"] - batch["x"]) ** 2)

        p = {"w": jnp.arange(8.0)}
        cfg = StreamConfig(algorithm="br_drag", buffer_capacity=2,
                           local_steps=2, lr=0.1, **cfg_kw)
        server = AsyncStreamServer(loss_fn, p, cfg)
        root = {"x": jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8))}
        return server, root

    def test_hit_serves_bitwise_identical_reference(self):
        """Cache-hit and cache-miss agree bit-for-bit at one version."""
        server, root = self._setup()
        r_miss = server.root_reference(root)
        assert (server.root_cache.misses, server.root_cache.hits) == (1, 0)
        r_hit = server.root_reference(root)
        assert server.root_cache.hits == 1
        np.testing.assert_array_equal(np.asarray(r_miss["w"]), np.asarray(r_hit["w"]))
        # a cold recompute (cache cleared) is also bitwise identical
        server.root_cache.clear()
        r_cold = server.root_reference(root)
        np.testing.assert_array_equal(np.asarray(r_hit["w"]), np.asarray(r_cold["w"]))

    def test_refresh_every_amortises_the_root_pass(self):
        server, root = self._setup(root_refresh_every=3)
        key = jax.random.PRNGKey(1)
        for t in range(6):
            for i in range(2):
                g = {"w": jax.random.normal(jax.random.fold_in(key, 10 * t + i), (8,))}
                server.ingest(g, server.t, False, client_id=i)
            assert server.flush_if_ready(key, root) is not None
        # versions 0-5 with refresh 3 -> D_root pass at {0,1,2}->1, {3,4,5}->1
        assert server.root_cache.misses == 2
        assert server.root_cache.hits == 4

    def test_cache_on_off_parity_bit_for_bit(self):
        """ISSUE satellite: a cached run (refresh_every=1, the exact
        setting) and an uncached run produce the identical trajectory."""
        hists = []
        for cache in (True, False):
            exp = StreamExperimentConfig(
                n_workers=8, concurrency=6, flushes=5, buffer_capacity=3,
                latency="exponential", local_steps=2, batch_size=4,
                algorithm="br_drag", discount="poly", eval_every=1,
                root_samples=200, seed=4, root_cache=cache,
            )
            hists.append(run_stream_experiment(exp))
        a, b = hists
        assert a["accuracy"] == b["accuracy"]  # exact float equality
        assert a["update_norm"] == b["update_norm"]
        assert a["root_cache_misses"] == 5 and b["root_cache_misses"] == 5
