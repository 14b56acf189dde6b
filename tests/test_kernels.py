"""Per-kernel shape/dtype sweeps asserting allclose against ref.py oracles
(assignment deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import drag_calibrate as dk
from repro.kernels import ops, ref
from repro.kernels import trimmed_mean as tk
from repro.kernels import weiszfeld as wk

SHAPES = [(8, 128), (8, 1024), (16, 2048), (32, 4096), (4, 384), (40, 1152)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _gr(shape, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.normal(k1, shape).astype(dtype)
    r = jax.random.normal(k2, (shape[1],)).astype(dtype)
    return g, r


def _tols(dtype):
    return {"rtol": 2e-2, "atol": 2e-2} if dtype == jnp.bfloat16 else {"rtol": 2e-5, "atol": 2e-5}


class TestDotNorms:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sweep(self, shape, dtype):
        g, r = _gr(shape, dtype)
        s, d = shape
        bs = 8 if s % 8 == 0 else s
        bd = 128 if d % 128 == 0 else d
        dots, gsq, rsq = dk.dot_norms(g, r, block_s=bs, block_d=bd, interpret=True)
        dots_r, gsq_r, rsq_r = ref.dot_norms_ref(g, r)
        tol = _tols(dtype)
        np.testing.assert_allclose(dots, dots_r, **tol)
        np.testing.assert_allclose(gsq, gsq_r, **tol)
        np.testing.assert_allclose(rsq, rsq_r, **tol)


class TestBlend:
    @pytest.mark.parametrize("shape", SHAPES[:4])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sweep(self, shape, dtype):
        g, r = _gr(shape, dtype, seed=1)
        s, d = shape
        a = jnp.linspace(0.1, 0.9, s)
        b = jnp.linspace(-0.5, 0.5, s)
        bs = 8 if s % 8 == 0 else s
        bd = 128 if d % 128 == 0 else d
        v = dk.blend(g, r, a, b, block_s=bs, block_d=bd, interpret=True)
        vr = ref.blend_ref(g, r, a, b)
        np.testing.assert_allclose(
            np.asarray(v, np.float32), np.asarray(vr, np.float32), **_tols(dtype)
        )


class TestFusedCalibrate:
    @pytest.mark.parametrize("mode", ["drag", "br_drag"])
    @pytest.mark.parametrize("c", [0.1, 0.5, 1.0])
    def test_modes(self, mode, c):
        g, r = _gr((16, 1024), jnp.float32, seed=2)
        v, lam, delta = ops.drag_calibrate(g, r, c, mode, interpret=True)
        vr, lamr = ref.drag_calibrate_ref(g, r, c, mode)
        np.testing.assert_allclose(v, vr, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(lam, lamr, rtol=1e-5)
        np.testing.assert_allclose(delta, jnp.mean(vr, 0), rtol=1e-4, atol=1e-5)

    def test_br_mode_norm_clamp(self):
        """Kernel output obeys the ||v|| <= ||r|| defense property."""
        g, r = _gr((8, 512), jnp.float32, seed=3)
        g = g * 100.0  # inflated attacker updates
        v, _, _ = ops.drag_calibrate(g, r, 0.5, "br_drag", interpret=True)
        vn = jnp.linalg.norm(v, axis=1)
        rn = jnp.linalg.norm(r)
        assert bool(jnp.all(vn <= rn * 1.001))


class TestWeiszfeld:
    @pytest.mark.parametrize("shape", SHAPES[:4])
    def test_sq_dists(self, shape):
        g, z = _gr(shape, jnp.float32, seed=4)
        s, d = shape
        bs = 8 if s % 8 == 0 else s
        bd = 128 if d % 128 == 0 else d
        d2 = wk.sq_dists(g, z, block_s=bs, block_d=bd, interpret=True)
        np.testing.assert_allclose(d2, ref.weiszfeld_distances_ref(g, z), rtol=1e-4)

    @pytest.mark.parametrize("shape", SHAPES[:4])
    def test_weighted_sum(self, shape):
        g, _ = _gr(shape, jnp.float32, seed=5)
        s, d = shape
        w = jax.random.uniform(jax.random.PRNGKey(9), (s,)) + 0.1
        bs = 8 if s % 8 == 0 else s
        bd = 128 if d % 128 == 0 else d
        out = wk.weighted_sum(g, w, block_s=bs, block_d=bd, interpret=True)
        np.testing.assert_allclose(out, w @ g, rtol=1e-4)

    def test_full_iteration_converges_to_median(self):
        """Geometric median resists one far outlier; the mean does not."""
        key = jax.random.PRNGKey(6)
        g = jax.random.normal(key, (16, 256)) * 0.1
        g = g.at[0].set(1000.0)  # Byzantine outlier
        z = ops.geometric_median(g, iters=12, interpret=True)
        assert float(jnp.linalg.norm(z)) < 1.0
        assert float(jnp.linalg.norm(jnp.mean(g, 0))) > 50.0


class TestTrimmedMean:
    @pytest.mark.parametrize("s,trim", [(8, 1), (16, 3), (32, 8), (10, 2)])
    @pytest.mark.parametrize("d", [128, 1024])
    def test_sweep(self, s, trim, d):
        g = jax.random.normal(jax.random.PRNGKey(7), (s, d))
        out = tk.trimmed_mean(g, trim, block_d=128, interpret=True)
        np.testing.assert_allclose(out, ref.trimmed_mean_ref(g, trim), rtol=1e-4, atol=1e-5)

    def test_outlier_removal(self):
        g = jax.random.normal(jax.random.PRNGKey(8), (10, 64)) * 0.1
        g = g.at[0].set(100.0).at[1].set(-100.0)
        out = tk.trimmed_mean(g, 2, block_d=64, interpret=True)
        assert float(jnp.max(jnp.abs(out))) < 1.0


class TestPadding:
    """ISSUE 3 satellite: audit of the padding paths in ``kernels.ops``.

    Two classes of padding exist: ``_pad_to`` on the sequence axis of
    flash attention (padded rows must be masked/sliced, never averaged),
    and the d-padding in ``_stack_flatten`` (padded columns must never
    leak into means/norms).  The flush ops pad S and d to aligned tiles
    (``ops._pad_grid``) with zero rows and zero coefficients, and these
    tests pin the S/d-not-multiple-of-block cases.
    """

    @pytest.mark.parametrize("shape", [(10, 96), (7, 130), (13, 257), (6, 1024)])
    def test_drag_calibrate_odd_shapes(self, shape):
        """S and d coprime with the default blocks: exact-divisor tiling
        must reproduce the oracle with no padded contributions."""
        g, r = _gr(shape, jnp.float32, seed=20)
        v, lam, delta = ops.drag_calibrate(g, r, 0.3, "drag", interpret=True)
        vr, lamr = ref.drag_calibrate_ref(g, r, 0.3, "drag")
        np.testing.assert_allclose(v, vr, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(lam, lamr, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(delta, jnp.mean(vr, 0), rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("shape", [(10, 96), (13, 257)])
    def test_dot_norms_stats_odd_shapes(self, shape):
        g, r = _gr(shape, jnp.float32, seed=21)
        dots, gsq, rsq = ops.dot_norms_stats(g, r, interpret=True)
        dots_r, gsq_r, rsq_r = ref.dot_norms_ref(g, r)
        np.testing.assert_allclose(dots, dots_r, rtol=1e-4)
        np.testing.assert_allclose(gsq, gsq_r, rtol=1e-4)
        np.testing.assert_allclose(rsq, rsq_r, rtol=1e-4)

    def test_zero_weight_rows_are_excluded_from_reduction(self):
        """Explicitly padded worker rows with zero blend coefficients
        contribute EXACTLY nothing — the invariant that makes S-padding
        safe when a caller does pad (e.g. for TPU sublane alignment)."""
        key = jax.random.PRNGKey(22)
        g = jax.random.normal(key, (5, 64))
        r = jax.random.normal(jax.random.fold_in(key, 1), (64,))
        aw = jax.random.uniform(jax.random.fold_in(key, 2), (5,))
        bw = jax.random.uniform(jax.random.fold_in(key, 3), (5,))
        # pad S 5 -> 8 with garbage rows but ZERO weights
        g_pad = jnp.concatenate([g, 1e6 * jnp.ones((3, 64))], axis=0)
        aw_pad = jnp.concatenate([aw, jnp.zeros(3)])
        bw_pad = jnp.concatenate([bw, jnp.zeros(3)])
        got = ops.blend_reduce(g_pad, r, aw_pad, bw_pad, interpret=True)
        want = ops.blend_reduce(g, r, aw, bw, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    def test_padded_d_columns_do_not_leak(self):
        """d-padding (as `_stack_flatten` does): zero columns on g AND r
        leave dots/norms/delta identical to the unpadded problem."""
        key = jax.random.PRNGKey(23)
        g = jax.random.normal(key, (8, 100))
        r = jax.random.normal(jax.random.fold_in(key, 1), (100,))
        g_pad, _ = ops._pad_to(g, 128, axis=1)
        r_pad, _ = ops._pad_to(r, 128, axis=0)
        dots, gsq, rsq = ops.dot_norms_stats(g_pad, r_pad, interpret=True)
        dots_r, gsq_r, rsq_r = ref.dot_norms_ref(g, r)
        np.testing.assert_allclose(dots, dots_r, rtol=1e-4)
        np.testing.assert_allclose(gsq, gsq_r, rtol=1e-4)
        np.testing.assert_allclose(rsq, rsq_r, rtol=1e-4)
        delta, _, _ = ops.drag_calibrate_reduce(g_pad, r_pad, 0.3, "drag")
        delta_u, _, _ = ops.drag_calibrate_reduce(g, r, 0.3, "drag")
        np.testing.assert_allclose(delta[:100], delta_u, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(delta[100:], 0.0, atol=1e-7)  # stay zero

    def test_trimmed_mean_padded_columns_sliced(self):
        """Padded d-columns through the trimmed-mean kernel are dropped by
        the unflatten slice, not averaged into real coordinates."""
        from repro.core import aggregators

        key = jax.random.PRNGKey(24)
        ups = {"w": jax.random.normal(key, (10, 100))}  # d=100, pads to 128
        got = ops.trimmed_mean_pytree(ups, trim=2)
        want = aggregators.trimmed_mean(ups, 2)
        np.testing.assert_allclose(
            np.asarray(got["w"]), np.asarray(want["w"]), rtol=1e-4, atol=1e-5
        )

    @pytest.mark.parametrize("sq", [100, 37])
    def test_flash_attention_s_padding(self, sq):
        """`_pad_to` on S in flash attention: padded q rows are sliced
        off and padded k positions masked — output matches the oracle on
        the true length."""
        key = jax.random.PRNGKey(25)
        b, h, dh = 1, 2, 32
        q = jax.random.normal(key, (b, h, sq, dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, sq, dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, sq, dh))
        out = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                                  interpret=True)
        assert out.shape == (b, h, sq, dh)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestKernelCallStructure:
    """ISSUE acceptance: the fused serving flush is AT MOST two kernel
    invocations over G — a single ``fused_flush`` when the stack is
    VMEM-resident, else dot_norms + blend_reduce; never ``blend`` (V is
    never materialised).  The full stream-flush variant (trust +
    staleness) lives in tests/test_flat.py::TestTwoPassFlush."""

    def test_drag_calibrate_reduce_is_single_pass_when_resident(self):
        from repro.kernels.instrument import (
            SINGLE_PASS_CALLS, count_kernel_calls, expected_flush_calls)

        g, r = _gr((16, 512), jnp.float32, seed=30)
        assert ops.flush_path(16, 512) == "fused"
        assert expected_flush_calls(16, 512) == SINGLE_PASS_CALLS
        with count_kernel_calls() as calls:
            delta, lam, stats = ops.drag_calibrate_reduce(
                g, r, 0.3, "drag",
                discounts=jnp.linspace(1.0, 0.5, 16),
                weights=jnp.linspace(0.1, 1.0, 16),
            )
        assert np.isfinite(np.asarray(delta)).all()
        assert calls == SINGLE_PASS_CALLS

    def test_drag_calibrate_reduce_is_two_passes_beyond_vmem(self):
        from repro.kernels.instrument import (
            TWO_PASS_CALLS, count_kernel_calls, expected_flush_calls)

        s, d = 16, 73728  # padded [16, 73728] f32 = 4.5 MiB > FUSED_VMEM_BYTES
        assert ops.flush_path(s, d) == "two_pass"
        assert expected_flush_calls(s, d) == TWO_PASS_CALLS
        g, r = _gr((s, d), jnp.float32, seed=31)
        with count_kernel_calls() as calls:
            delta, lam, stats = ops.drag_calibrate_reduce(g, r, 0.3, "drag")
        assert np.isfinite(np.asarray(delta)).all()
        assert calls == TWO_PASS_CALLS


class TestPytreeOps:
    def test_drag_matches_core(self):
        from repro.core import drag as cdrag
        from repro.core import pytree as pt

        key = jax.random.PRNGKey(10)
        ups = {
            "w": jax.random.normal(key, (8, 37, 11)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 13)),
        }
        r = pt.tree_index(ups, 0)
        d_kernel, lam_k = ops.drag_calibrate_pytree(ups, r, 0.3, "drag")
        d_core, lam_c = cdrag.aggregate(ups, r, 0.3)
        np.testing.assert_allclose(
            pt.tree_flatten_vector(d_kernel), pt.tree_flatten_vector(d_core), rtol=1e-4, atol=1e-6
        )
        # row 0 IS r: lam = c (1 - cos) is 0 up to the rounding of one dot
        # and two norms, a few float32 eps times c < one eps
        np.testing.assert_allclose(lam_k, lam_c, rtol=1e-4,
                                   atol=np.finfo(np.float32).eps)

    def test_drag_pytree_mixed_dtype_leaves(self):
        """ISSUE 3 satellite: bf16 + f32 leaves through the padded
        [S, d] staging — per-leaf dtypes restored, values matching the
        core oracle at bf16-appropriate tolerance."""
        from repro.core import drag as cdrag
        from repro.core import pytree as pt

        key = jax.random.PRNGKey(12)
        ups = {
            "h": jax.random.normal(key, (8, 33, 5)).astype(jnp.bfloat16),
            "w": jax.random.normal(jax.random.fold_in(key, 1), (8, 70)),
        }
        r = pt.tree_index(ups, 0)
        d_kernel, lam_k = ops.drag_calibrate_pytree(ups, r, 0.3, "drag")
        d_core, lam_c = cdrag.aggregate(ups, r, 0.3)
        assert d_kernel["h"].dtype == jnp.bfloat16
        assert d_kernel["w"].dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(pt.tree_flatten_vector(d_kernel)),
            np.asarray(pt.tree_flatten_vector(d_core)),
            rtol=2e-2, atol=2e-2,
        )
        np.testing.assert_allclose(lam_k, lam_c, rtol=2e-2, atol=2e-2)

    def test_geomed_matches_core(self):
        from repro.core import aggregators
        from repro.core import pytree as pt

        key = jax.random.PRNGKey(11)
        ups = {"w": jax.random.normal(key, (8, 130))}
        z_k = ops.geometric_median_pytree(ups, iters=8)
        z_c = aggregators.geometric_median(ups, iters=8)
        np.testing.assert_allclose(
            pt.tree_flatten_vector(z_k), pt.tree_flatten_vector(z_c), rtol=1e-3, atol=1e-5
        )
