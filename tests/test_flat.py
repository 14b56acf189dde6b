"""Flat update plane tests (ISSUE 3 tentpole).

Pins the serving representation (``repro.core.flat`` + the flat
aggregator tier + the fused kernel flush) against the retained pytree
oracle, and asserts the two-HBM-pass kernel call structure of a full
stream flush with trust + staleness enabled.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators, br_drag, drag
from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.kernels import ops
from repro.trust import reputation as trust_mod


def _ups(key, s=10):
    return {
        "conv": jax.random.normal(key, (s, 3, 5, 2)),
        "w": jax.random.normal(jax.random.fold_in(key, 1), (s, 37, 11)),
        "b": jax.random.normal(jax.random.fold_in(key, 2), (s, 13)),
    }


def _ref(key):
    one = _ups(key, s=1)
    return jax.tree.map(lambda x: x[0], one)


class TestUpdateStack:
    def test_row_equals_tree_flatten_vector(self):
        """Row s of the stack == flatten of worker s's pytree, bit-for-bit
        (the property that makes sync round and async ingest agree)."""
        key = jax.random.PRNGKey(0)
        ups = _ups(key, s=6)
        stack = flat_mod.stack_updates(ups)
        for i in range(6):
            row_tree = pt.tree_index(ups, i)
            np.testing.assert_array_equal(
                np.asarray(stack.data[i]), np.asarray(pt.tree_flatten_vector(row_tree))
            )

    def test_round_trip_bit_for_bit(self):
        key = jax.random.PRNGKey(1)
        ups = _ups(key, s=4)
        stack = flat_mod.stack_updates(ups)
        back = stack.to_stacked_pytree()
        assert jax.tree.structure(back) == jax.tree.structure(ups)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(ups)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_metadata_round_trip(self):
        key = jax.random.PRNGKey(2)
        ups = _ups(key, s=3)
        cids = jnp.array([7, 100003, 42], jnp.int32)
        taus = jnp.array([0, 5, 2], jnp.int32)
        stack = flat_mod.stack_updates(ups, client_ids=cids, staleness=taus)
        # UpdateStack is a pytree: metadata survives jit/tree operations
        stack2 = jax.jit(lambda s: s)(stack)
        np.testing.assert_array_equal(np.asarray(stack2.client_ids), np.asarray(cids))
        np.testing.assert_array_equal(np.asarray(stack2.staleness), np.asarray(taus))
        assert stack2.spec == stack.spec

    def test_mixed_dtype_leaves(self):
        """bf16/f32 mixed leaves: f32 staging is lossless for bf16."""
        key = jax.random.PRNGKey(3)
        ups = {
            "h": jax.random.normal(key, (4, 8, 3)).astype(jnp.bfloat16),
            "w": jax.random.normal(jax.random.fold_in(key, 1), (4, 5)),
        }
        stack = flat_mod.stack_updates(ups)
        back = stack.to_stacked_pytree()
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(ups)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            )

    def test_unflatten_tree_single_vector(self):
        key = jax.random.PRNGKey(4)
        tree = _ref(key)
        spec = flat_mod.spec_of(tree)
        vec = flat_mod.flatten_tree(tree)
        back = flat_mod.unflatten_tree(vec, spec)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_flatten_host_is_flatten_tree(self, dtype):
        """A numpy upload flattened on the host is the device row, bit for
        bit; a tree of device arrays passes through untouched."""
        tree = jax.tree.map(lambda x: np.asarray(x[0], dtype), _ups(jax.random.PRNGKey(4)))
        row = flat_mod.flatten_host(tree)
        assert isinstance(row, np.ndarray) and row.dtype == np.float32
        np.testing.assert_array_equal(row, np.asarray(flat_mod.flatten_tree(tree)))
        on_device = jax.tree.map(jnp.asarray, tree)
        assert flat_mod.flatten_host(on_device) is on_device


class TestFlatOracleParity:
    """ISSUE acceptance: flat path numerically matches the pytree oracle
    for drag, br_drag, fltrust, and trimmed_mean (atol/rtol 1e-5)."""

    def setup_method(self):
        key = jax.random.PRNGKey(10)
        self.ups = _ups(key, s=10)
        self.r = _ref(jax.random.fold_in(key, 99))
        self.stack = flat_mod.stack_updates(self.ups)
        self.r_flat = flat_mod.flatten_tree(self.r)

    def _close(self, flat_delta, tree_delta):
        np.testing.assert_allclose(
            np.asarray(flat_delta),
            np.asarray(flat_mod.flatten_tree(tree_delta)),
            rtol=1e-5, atol=1e-5,
        )

    @pytest.mark.parametrize("discounts", [None, "poly"])
    @pytest.mark.parametrize("weights", [None, "ramp"])
    def test_drag(self, discounts, weights):
        disc = jnp.linspace(1.0, 0.25, 10) if discounts else None
        w = jnp.linspace(0.05, 1.0, 10) if weights else None
        d_flat, lam_f, _ = drag.aggregate_flat(
            self.stack.data, self.r_flat, 0.3, discounts=disc, weights=w
        )
        d_core, lam_c = drag.aggregate(self.ups, self.r, 0.3, discounts=disc, weights=w)
        self._close(d_flat, d_core)
        np.testing.assert_allclose(lam_f, lam_c, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("discounts", [None, "poly"])
    def test_br_drag(self, discounts):
        disc = jnp.linspace(1.0, 0.25, 10) if discounts else None
        d_flat, lam_f, _ = br_drag.aggregate_flat(
            self.stack.data, self.r_flat, 0.5, discounts=disc
        )
        d_core, lam_c = br_drag.aggregate(self.ups, self.r, 0.5, discounts=disc)
        self._close(d_flat, d_core)
        np.testing.assert_allclose(lam_f, lam_c, rtol=1e-5, atol=1e-6)

    def test_fltrust(self):
        d_flat = aggregators.fltrust_flat(self.stack.data, self.r_flat)
        d_core = aggregators.fltrust(self.ups, self.r)
        self._close(d_flat, d_core)

    @pytest.mark.parametrize(
        "rule", ["fedavg", "fedexp", "median", "trimmed_mean", "krum",
                 "multi_krum", "bulyan", "geomed"]
    )
    def test_registry_tier(self, rule):
        kw = aggregators.rule_kwargs(rule, n_byzantine=2, geomed_iters=4)
        d_flat = aggregators.FLAT_AGGREGATORS[rule](self.stack.data, **kw)
        d_core = aggregators.AGGREGATORS[rule](self.ups, **kw)
        np.testing.assert_allclose(
            np.asarray(d_flat),
            np.asarray(flat_mod.flatten_tree(d_core)),
            rtol=1e-4, atol=1e-5,
        )

    def test_trimmed_mean_trim_zero_is_mean(self):
        d_flat = aggregators.trimmed_mean_flat(self.stack.data, 0)
        np.testing.assert_allclose(
            np.asarray(d_flat), np.asarray(jnp.mean(self.stack.data, 0)),
            rtol=1e-6,
        )

    def test_trust_signals_from_stats_match_oracle(self):
        """trust becomes free: the phase-1 scalars reproduce
        divergence_signals without a second stack pass."""
        dots, gsq, rsq = ops.dot_norms_stats(self.stack.data, self.r_flat)
        div_f, nr_f = trust_mod.signals_from_stats(dots, gsq, rsq)
        div_c, nr_c = trust_mod.divergence_signals(self.ups, self.r)
        np.testing.assert_allclose(div_f, div_c, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(nr_f, nr_c, rtol=1e-5, atol=1e-6)

    def test_drag_round_step_flat_matches_oracle_trajectory(self):
        """Bootstrap + 2 calibrated rounds: flat round step vs pytree
        round step stay allclose on params and reference."""
        key = jax.random.PRNGKey(11)
        params = _ref(key)
        s_flat = drag.init_state(params)
        s_tree = drag.init_state(params)
        p_flat, p_tree = params, params
        for t in range(3):
            ups = _ups(jax.random.fold_in(key, t), s=6)
            stack = flat_mod.stack_updates(ups)
            p_flat, s_flat, m_f, _ = drag.round_step_flat(
                p_flat, s_flat, stack, alpha=0.25, c=0.2
            )
            p_tree, s_tree, m_t = drag.round_step(
                p_tree, s_tree, ups, alpha=0.25, c=0.2
            )
            np.testing.assert_allclose(
                np.asarray(flat_mod.flatten_tree(p_flat)),
                np.asarray(flat_mod.flatten_tree(p_tree)),
                rtol=1e-5, atol=1e-5,
            )
            np.testing.assert_allclose(
                np.asarray(flat_mod.flatten_tree(s_flat.reference)),
                np.asarray(flat_mod.flatten_tree(s_tree.reference)),
                rtol=1e-5, atol=1e-5,
            )
            np.testing.assert_allclose(
                float(m_f["dod_mean"]), float(m_t["dod_mean"]), rtol=1e-4, atol=1e-6
            )


class TestTwoPassFlush:
    """ISSUE acceptance: a stream flush with trust + staleness enabled
    performs the MINIMUM kernel passes over the stacked updates — a
    single ``fused_flush`` here (the [K, d] stack is VMEM-resident), and
    NO other kernel/oracle walk of the stack (trust reuses the phase-1
    scalars)."""

    @pytest.mark.parametrize("alg", ["drag", "br_drag"])
    def test_flush_is_minimum_kernel_passes(self, alg, monkeypatch):
        from repro.kernels.instrument import count_kernel_calls, expected_flush_calls
        from repro.stream import buffer as buf_mod
        from repro.stream.server import StreamConfig, flush, init_stream_state
        from repro.trust import reputation as trust_mod_

        # fail if anything walks the stack through the PYTREE oracle
        def no_oracle(*a, **kw):
            raise AssertionError("pytree divergence_signals called on the flat path")

        monkeypatch.setattr(trust_mod_, "divergence_signals", no_oracle)

        p = {"w": jnp.ones((8,)), "b": jnp.zeros((3,))}
        cfg = StreamConfig(
            algorithm=alg, buffer_capacity=4, trust=True, discount="poly",
        )
        state = init_stream_state(p, 4, cfg, n_clients=8)
        key = jax.random.PRNGKey(0)
        buf = state.buffer
        for i in range(4):
            g = {"w": jax.random.normal(jax.random.fold_in(key, i), (8,)),
                 "b": jax.random.normal(jax.random.fold_in(key, 100 + i), (3,))}
            buf = buf_mod.ingest(buf, g, 0, False, client_id=i)
        kwargs = dict(adv_state=state.adversary, trust_state=state.trust)
        if alg == "br_drag":
            kwargs["reference"] = {"w": jnp.ones((8,)) * 0.1, "b": jnp.ones((3,)) * 0.1}
        with count_kernel_calls() as calls:
            out = flush(
                None, cfg, state.params, state.drag, state.round, buf, key, **kwargs
            )
        assert np.isfinite(float(out[-1]["delta_norm"]))
        # d = 11, K = 4 -> VMEM-resident: one fused_flush, no blend —
        # V:[S,d] never materialised
        assert calls == expected_flush_calls(4, 11), calls
        assert calls["fused_flush"] == 1 and calls["blend"] == 0, calls


class TestFlatAttackPath:
    def test_schedule_attack_through_flat_round(self):
        """Regression: StackSpec rides through lax.switch (Schedule) —
        it must be a STATIC pytree node, not an invalid JAX leaf."""
        from repro.fl.round import RoundConfig, init_server_state, make_round_fn

        def loss_fn(p, batch):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

        params = {"w": jnp.zeros((3, 1))}
        cfg = RoundConfig(
            algorithm="fedavg", local_steps=1, lr=0.1,
            attack="schedule", attack_kw=(("phases", ((0, "sign_flipping"),)),),
        )
        state = init_server_state(params, 4, cfg)
        fn = make_round_fn(loss_fn, cfg, with_root=False)
        key = jax.random.PRNGKey(0)
        batches = {
            "x": jax.random.normal(key, (4, 1, 2, 3)),
            "y": jax.random.normal(jax.random.fold_in(key, 1), (4, 1, 2, 1)),
        }
        state, metrics = fn(
            state, batches, jnp.arange(4, dtype=jnp.int32),
            jnp.array([True, False, False, False]), key,
        )
        assert np.isfinite(float(metrics["delta_norm"]))

    def test_spec_is_static_pytree_node(self):
        spec = flat_mod.spec_of({"w": jnp.zeros((2, 3))})
        assert jax.tree.leaves(spec) == []  # zero traced leaves
        out = jax.jit(lambda s: s)(spec)
        assert out == spec


class TestLaneBlocks:
    def test_lane_block_respects_cap_below_unit(self):
        """Regression: cap < 1024 must force the 128 unit, not silently
        return a >= 1024 tile that blows the caller's VMEM budget."""
        assert ops._lane_block(4096, cap=512) == 512
        assert ops._lane_block(4096, cap=128) == 128
        assert ops._lane_block(12672, cap=1 << 16) == 12672
        # large-d pad target guarantees a big divisible tile
        d_pad = 102403 + (-102403) % ops._lane_mult(102403)
        assert ops._lane_block(d_pad) >= 8192
        assert d_pad % ops._lane_block(d_pad) == 0


class TestBlendReduceKernel:
    @pytest.mark.parametrize("shape", [(8, 128), (16, 2048), (4, 384), (10, 96), (7, 130)])
    def test_matches_ref(self, shape):
        from repro.kernels import ref

        key = jax.random.PRNGKey(5)
        s, d = shape
        g = jax.random.normal(key, shape)
        r = jax.random.normal(jax.random.fold_in(key, 1), (d,))
        aw = jax.random.uniform(jax.random.fold_in(key, 2), (s,))
        bw = jax.random.uniform(jax.random.fold_in(key, 3), (s,)) - 0.5
        got = ops.blend_reduce(g, r, aw, bw)
        want = ref.blend_reduce_ref(g, r, aw, bw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)

    def test_calibrate_reduce_equals_blend_then_mean(self):
        """drag_calibrate_reduce == the unfused (blend + mean) pipeline."""
        from repro.kernels import ref

        key = jax.random.PRNGKey(6)
        g = jax.random.normal(key, (12, 512))
        r = jax.random.normal(jax.random.fold_in(key, 1), (512,))
        for mode in ("drag", "br_drag"):
            delta, lam, _ = ops.drag_calibrate_reduce(g, r, 0.4, mode)
            v_ref, lam_ref = ref.drag_calibrate_ref(g, r, 0.4, mode)
            np.testing.assert_allclose(
                np.asarray(delta), np.asarray(jnp.mean(v_ref, 0)), rtol=1e-4, atol=1e-5
            )
            np.testing.assert_allclose(lam, lam_ref, rtol=1e-5, atol=1e-6)

    def test_weight_fallback_uniform_when_all_zero(self):
        """All-quarantined weights degrade to the uniform mean (mirrors
        tree_weighted_mean), not a zero/NaN step."""
        key = jax.random.PRNGKey(7)
        g = jax.random.normal(key, (6, 64))
        r = jax.random.normal(jax.random.fold_in(key, 1), (64,))
        d0, _, _ = ops.drag_calibrate_reduce(g, r, 0.3, "drag", weights=jnp.zeros(6))
        d1, _, _ = ops.drag_calibrate_reduce(g, r, 0.3, "drag", weights=None)
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)
