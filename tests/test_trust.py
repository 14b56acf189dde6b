"""Trust layer tests: divergence-history EMAs, reputation weights,
quarantine, weighted aggregation, and integration with both serving
regimes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import br_drag
from repro.core import pytree as pt
from repro.trust import reputation as trust


CFG = trust.TrustConfig()


class TestHistory:
    def test_first_observation_seeds_ema(self):
        st = trust.init_trust(4)
        idx = jnp.array([1, 3], jnp.int32)
        st = trust.observe(st, idx, jnp.array([1.8, 0.2]), jnp.array([2.0, 1.0]), CFG)
        np.testing.assert_allclose(np.asarray(st.div_ema), [0.0, 1.8, 0.0, 0.2])
        np.testing.assert_allclose(np.asarray(st.seen), [0, 1, 0, 1])

    def test_ema_decay(self):
        st = trust.init_trust(2)
        idx = jnp.array([0], jnp.int32)
        st = trust.observe(st, idx, jnp.array([2.0]), jnp.array([1.0]), CFG)
        st = trust.observe(st, idx, jnp.array([0.0]), jnp.array([1.0]), CFG)
        # 0.8 * 2.0 + 0.2 * 0.0
        np.testing.assert_allclose(np.asarray(st.div_ema)[0], 1.6, rtol=1e-6)

    def test_gate_false_is_noop(self):
        st = trust.init_trust(3)
        idx = jnp.array([0, 1], jnp.int32)
        st2 = trust.observe(
            st, idx, jnp.array([2.0, 2.0]), jnp.array([9.0, 9.0]), CFG,
            gate=jnp.asarray(False),
        )
        np.testing.assert_array_equal(np.asarray(st2.div_ema), np.asarray(st.div_ema))
        np.testing.assert_array_equal(np.asarray(st2.seen), np.asarray(st.seen))

    def test_duplicate_ids_in_one_flush_count_once(self):
        """A client filling several buffer slots of one flush is one
        observation — it must not burn warmup protection early."""
        st = trust.init_trust(4)
        idx = jnp.array([2, 2, 1], jnp.int32)
        st = trust.observe(
            st, idx, jnp.array([2.0, 2.0, 0.1]), jnp.ones(3), CFG
        )
        np.testing.assert_allclose(np.asarray(st.seen), [0, 1, 1, 0])

    def test_id_folding_bounds_the_table(self):
        """Lazy-stream client ids far beyond the table fold in modulo M."""
        st = trust.init_trust(8)
        idx = jnp.array([8 * 1000 + 5], jnp.int32)
        st = trust.observe(st, idx, jnp.array([1.5]), jnp.array([1.0]), CFG)
        assert float(st.div_ema[5]) == 1.5


class TestReputation:
    def test_warmup_gives_benefit_of_the_doubt(self):
        st = trust.init_trust(2)
        idx = jnp.array([0, 1], jnp.int32)
        st = trust.observe(st, idx, jnp.array([2.0, 0.1]), jnp.array([1.0, 1.0]), CFG)
        w = trust.reputation(st, idx, CFG)
        np.testing.assert_allclose(np.asarray(w), [1.0, 1.0])  # seen < warmup

    def test_persistent_divergence_decays_reputation(self):
        st = trust.init_trust(2)
        idx = jnp.array([0, 1], jnp.int32)
        for _ in range(5):
            st = trust.observe(st, idx, jnp.array([2.0, 0.3]), jnp.array([1.0, 1.0]), CFG)
        w = np.asarray(trust.reputation(st, idx, CFG))
        assert w[0] < 0.05  # sign-flip-grade divergence (cos = -1)
        assert w[1] == 1.0  # heterogeneity-grade divergence stays trusted

    def test_norm_inflation_decays_reputation(self):
        st = trust.init_trust(2)
        idx = jnp.array([0, 1], jnp.int32)
        for _ in range(5):
            st = trust.observe(st, idx, jnp.array([0.1, 0.1]), jnp.array([40.0, 1.5]), CFG)
        w = np.asarray(trust.reputation(st, idx, CFG))
        assert w[0] < 1e-6 and w[1] == 1.0

    def test_quarantine_is_sticky_and_zero_weight(self):
        st = trust.init_trust(2)
        idx = jnp.array([0], jnp.int32)
        for _ in range(5):
            st = trust.observe(st, idx, jnp.array([2.0]), jnp.array([1.0]), CFG)
        assert bool(st.quarantined[0])
        # even after the EMA would recover, the flag holds
        for _ in range(50):
            st = trust.observe(st, idx, jnp.array([0.0]), jnp.array([1.0]), CFG)
        w = np.asarray(trust.reputation(st, idx, CFG))
        assert w[0] == 0.0

    def test_weighted_mean_fallback_uniform_when_all_zero(self):
        stacked = {"w": jnp.arange(6.0).reshape(3, 2)}
        out = trust.weighted_mean(stacked, jnp.zeros(3))
        np.testing.assert_allclose(np.asarray(out["w"]), [2.0, 3.0])

    def test_weighted_br_drag_downweights_flagged_worker(self):
        key = jax.random.PRNGKey(0)
        r = {"w": jax.random.normal(key, (16,))}
        ups = {"w": jnp.stack([r["w"]] * 3 + [-5.0 * r["w"]])}
        uniform, _ = br_drag.aggregate(ups, r, 0.5)
        weighted, _ = br_drag.aggregate(
            ups, r, 0.5, weights=jnp.array([1.0, 1.0, 1.0, 0.0])
        )
        d_uni = float(pt.tree_norm(pt.tree_sub(uniform, r)))
        d_wei = float(pt.tree_norm(pt.tree_sub(weighted, r)))
        # excluding the attacker lands no farther from r.  BR-DRAG maps the
        # fully flipped row onto r itself (lam = 1: a = 0, b = 1), so both
        # aggregates equal r in exact arithmetic and differ by rounding:
        # the bound is 4 float32 eps of ||r||
        tol = 4 * np.finfo(np.float32).eps * float(pt.tree_norm(r))
        assert d_wei <= d_uni + tol, (d_wei, d_uni, tol)
        # weights=None stays bit-for-bit the paper mean
        again, _ = br_drag.aggregate(ups, r, 0.5)
        np.testing.assert_array_equal(np.asarray(uniform["w"]), np.asarray(again["w"]))


class TestIntegration:
    def _round_setup(self, algorithm, trust_on, n=6):
        from repro.fl.round import RoundConfig, init_server_state, make_round_fn

        def loss_fn(p, batch):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

        params = {"w": jnp.zeros((3, 1))}
        cfg = RoundConfig(
            algorithm=algorithm, attack="sign_flipping", local_steps=2, lr=0.1,
            trust=trust_on,
        )
        state = init_server_state(params, n, cfg)
        fn = make_round_fn(loss_fn, cfg, with_root=algorithm == "br_drag")
        key = jax.random.PRNGKey(0)
        # every client and the root share one clean regression task, so
        # honest updates align with r^t and sign-flipped ones oppose it
        x = jax.random.normal(key, (2, 4, 3))
        w_true = jax.random.normal(jax.random.fold_in(key, 1), (3, 1))
        y = x @ w_true
        batches = {
            "x": jnp.broadcast_to(x[None], (n, 2, 4, 3)),
            "y": jnp.broadcast_to(y[None], (n, 2, 4, 1)),
        }
        root = {"x": x, "y": y}
        return fn, state, batches, root, key

    def test_sync_br_drag_trust_accumulates_history(self):
        fn, state, batches, root, key = self._round_setup("br_drag", True)
        mask = jnp.array([True, True, False, False, False, False])
        sel = jnp.arange(6, dtype=jnp.int32)
        for i in range(4):
            state, metrics = fn(state, batches, sel, mask, jax.random.fold_in(key, i), root)
        div = np.asarray(state.trust.div_ema)
        # sign-flipped workers show ~2x the divergence of honest ones
        assert div[:2].min() > div[2:].max()
        assert "trust_weight_mean" in metrics

    def test_trust_requires_reference_algorithm(self):
        from repro.fl.round import RoundConfig, federated_round, init_server_state

        cfg = RoundConfig(algorithm="fedavg", trust=True)
        state = init_server_state({"w": jnp.zeros((3, 1))}, 4, cfg)
        with pytest.raises(ValueError, match="reference direction"):
            federated_round(
                lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2),
                state, cfg,
                {"x": jnp.zeros((4, 1, 2, 3)), "y": jnp.zeros((4, 1, 2, 1))},
                jnp.arange(4, dtype=jnp.int32), jnp.zeros(4, bool),
                jax.random.PRNGKey(0),
            )

    def test_async_flush_trust_indexes_buffer_client_ids(self):
        from repro.core import drag
        from repro.stream import buffer as buf_mod
        from repro.stream.server import StreamConfig, flush, init_stream_state
        from repro.trust import reputation as trust_mod

        p = {"w": jnp.ones((8,))}
        cfg = StreamConfig(algorithm="drag", buffer_capacity=4, trust=True)
        state = init_stream_state(p, 4, cfg, n_clients=10)
        key = jax.random.PRNGKey(0)
        # two flushes: bootstrap (gated, no observation), then observed
        for rnd in range(2):
            buf = state.buffer
            for i in range(4):
                g = {"w": jax.random.normal(jax.random.fold_in(key, 10 * rnd + i), (8,))}
                buf = buf_mod.ingest(buf, g, rnd, i == 0, client_id=i + 3)
            params, dstate, r2, buf, adv, trust_state, m = flush(
                None, cfg, state.params, state.drag, state.round, buf, key,
                adv_state=state.adversary, trust_state=state.trust,
            )
            state = state._replace(
                params=params, drag=dstate, round=r2, buffer=buf, trust=trust_state
            )
        seen = np.asarray(state.trust.seen)
        assert seen[3:7].sum() == 4  # exactly the buffered ids, exactly once
        assert seen[[0, 1, 2, 7, 8, 9]].sum() == 0

    def test_scenario_trust_beats_fedavg_under_ipm(self):
        """End to end on the scenario lab: trust-weighted BR-DRAG keeps
        final loss below plain FedAvg under aggregate-reversing IPM."""
        from repro.adversary.scenarios import Scenario, run_scenario

        kw = dict(attack="ipm", attack_kw=(("eps", 2.0),), rounds=30, seed=3)
        fed = run_scenario(Scenario(aggregator="fedavg", **kw))
        tru = run_scenario(Scenario(aggregator="br_drag_trust", **kw))
        assert tru["final_loss"] < fed["final_loss"]
