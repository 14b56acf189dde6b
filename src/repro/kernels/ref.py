"""Pure-jnp oracles for every Pallas kernel (allclose targets).

These are also the *algorithmic* reference: the kernels must match these
bit-for-bit up to float reassociation.  The aggregation references use
no matmul: each contraction is an elementwise product and a sum, exact
float32 on every backend.  On a TPU v5e an XLA float32 matmul over a long contraction
lost ~2.6e-5 relative even at ``Precision.HIGHEST``, where the Mosaic
kernels held ~3e-7, so a matmul reference would be the less exact side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12


def dot_norms_ref(g: jnp.ndarray, r: jnp.ndarray):
    """g: [S, d], r: [d] -> (dots [S], g_sq [S], r_sq [])  (f32 accum)."""
    gf = g.astype(jnp.float32)
    rf = r.astype(jnp.float32)
    dots = jnp.sum(gf * rf[None, :], axis=1)
    g_sq = jnp.sum(gf * gf, axis=1)
    r_sq = jnp.sum(rf * rf)
    return dots, g_sq, r_sq


def calibrate_coeffs(dots, g_sq, r_sq, c: float, mode: str, discounts=None):
    """Per-worker blend coefficients (a, b, lam): v = a*g + b*r.

    ``discounts`` (optional [S] f32) are staleness factors phi(tau_m)
    folded into the DoD: lam = c * (1 - cos) * phi.  None means fresh
    updates — phi = 1, bit-exact the synchronous coefficients.
    """
    gn = jnp.sqrt(g_sq + EPS)
    rn = jnp.sqrt(r_sq + EPS)
    cos = dots / (gn * rn)
    lam = c * (1.0 - cos)
    if discounts is not None:
        lam = lam * jnp.asarray(discounts, jnp.float32)
    if mode == "drag":  # eq. (11)
        a = 1.0 - lam
        b = lam * gn / rn
    elif mode == "br_drag":  # eq. (15)
        a = (1.0 - lam) * rn / gn
        b = lam
    else:
        raise ValueError(mode)
    return a, b, lam


def blend_ref(g, r, a, b):
    """v[s] = a[s] * g[s] + b[s] * r   -> [S, d]."""
    return (
        a[:, None] * g.astype(jnp.float32) + b[:, None] * r.astype(jnp.float32)
    ).astype(g.dtype)


def drag_calibrate_ref(g, r, c: float, mode: str = "drag"):
    """Full fused op: returns (v [S,d], lam [S])."""
    dots, g_sq, r_sq = dot_norms_ref(g, r)
    a, b, lam = calibrate_coeffs(dots, g_sq, r_sq, c, mode)
    return blend_ref(g, r, a, b), lam


def blend_reduce_ref(g, r, aw, bw):
    """Delta = sum_s (aw_s g_s + bw_s r)  -> [d]  (f32)."""
    gf = g.astype(jnp.float32)
    rf = r.astype(jnp.float32)
    return jnp.sum(aw.astype(jnp.float32)[:, None] * gf, axis=0) + jnp.sum(
        bw.astype(jnp.float32)
    ) * rf


def weiszfeld_distances_ref(g, z):
    """[S,d], [d] -> squared distances [S]."""
    diff = g.astype(jnp.float32) - z.astype(jnp.float32)[None, :]
    return jnp.sum(diff * diff, axis=1)


def weighted_mean_ref(g, w):
    """[S,d], [S] -> sum_s w_s g_s / sum_s w_s."""
    wf = w.astype(jnp.float32)
    num = jnp.sum(wf[:, None] * g.astype(jnp.float32), axis=0)
    return (num / jnp.sum(wf)).astype(g.dtype)


def weiszfeld_step_ref(g, z, eps: float = 1e-8):
    d2 = weiszfeld_distances_ref(g, z)
    w = 1.0 / jnp.maximum(jnp.sqrt(d2), eps)
    return weighted_mean_ref(g, w).astype(z.dtype)


def trimmed_mean_ref(g, trim: int):
    """[S, d] -> [d]: coordinate-wise mean after dropping `trim` hi/lo."""
    s = g.shape[0]
    gs = jnp.sort(g.astype(jnp.float32), axis=0)
    return jnp.mean(gs[trim : s - trim], axis=0).astype(g.dtype)


def trimmed_mean_masked_ref(g, trim: int):
    """Non-finite-aware trimmed mean oracle (Byzantine overflow rows).

    NaN/inf entries are excluded outright; the ``trim`` largest/smallest
    among the FINITE entries are dropped and the divisor is the true
    per-column keep count.  Columns with fewer than ``2*trim + 1`` finite
    entries yield 0.0.  On all-finite stacks this equals
    :func:`trimmed_mean_ref` exactly (multiset trim, ties included).
    """
    gf = g.astype(jnp.float32)
    valid = jnp.isfinite(gf)
    nval = jnp.sum(valid.astype(jnp.float32), axis=0)
    total = jnp.sum(jnp.where(valid, gf, 0.0), axis=0)
    # sorts push invalid entries to the far end of each side; slice the
    # trim extremes and mask out any sentinel that leaked in (columns
    # with < trim finite entries)
    hi = jnp.sort(jnp.where(valid, gf, -jnp.inf), axis=0)[g.shape[0] - trim:]
    lo = jnp.sort(jnp.where(valid, gf, jnp.inf), axis=0)[:trim]
    hi_sum = jnp.sum(jnp.where(jnp.isfinite(hi), hi, 0.0), axis=0)
    lo_sum = jnp.sum(jnp.where(jnp.isfinite(lo), lo, 0.0), axis=0)
    keep = nval - 2.0 * trim
    kept = total - hi_sum - lo_sum
    return jnp.where(keep >= 1.0, kept / jnp.maximum(keep, 1.0), 0.0).astype(g.dtype)


def pairwise_sq_dists_ref(g):
    """[S, d] -> [S, S] squared distances (Gram identity, f32)."""
    f32 = g.astype(jnp.float32)
    sq = jnp.sum(f32 * f32, axis=-1)
    gram = jax.lax.map(lambda row: jnp.sum(f32 * row, axis=-1), f32)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """Materialised-softmax attention with GQA + causal/window masking.

    q: [B, H, Sq, dh]; k, v: [B, Hkv, Sk, dh] -> [B, H, Sq, dh].
    """
    b, h, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = h // hkv
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    scale = scale if scale is not None else dh ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * scale
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    row_has_any = jnp.any(mask, axis=-1)  # [Sq]
    p = jnp.where(row_has_any[None, None, :, None], p, 0.0)  # all-masked rows -> 0
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def linear_recurrence_ref(a, g):
    """Sequential oracle: h_t = a_t h_{t-1} + g_t over [B, S, w]."""
    af = a.astype(jnp.float32)
    gf = g.astype(jnp.float32)

    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    h0 = jnp.zeros((af.shape[0], af.shape[2]), jnp.float32)  # [B, w]
    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(af, 1, 0), jnp.moveaxis(gf, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)


def selective_scan_ref(dt, x, b, c, a):
    """Sequential diagonal SSM scan oracle.

    dt, x: [B, S, di]; b, c: [B, S, ds]; a: [di, ds] -> y [B, S, di].
    """
    bsz, s, di = dt.shape
    ds = b.shape[-1]
    dtf = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    af = a.astype(jnp.float32)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp  # [B,di],[B,di],[B,ds],[B,ds]
        a_bar = jnp.exp(dt_t[..., None] * af[None])  # [B,di,ds]
        bx = (dt_t * x_t)[..., None] * b_t[:, None, :]
        h = a_bar * h + bx
        y = jnp.einsum("bin,bn->bi", h, c_t)
        return h, y

    h0 = jnp.zeros((bsz, di, ds), jnp.float32)
    _, ys = jax.lax.scan(
        step,
        h0,
        (
            jnp.moveaxis(dtf, 1, 0),
            jnp.moveaxis(xf, 1, 0),
            jnp.moveaxis(bf, 1, 0),
            jnp.moveaxis(cf, 1, 0),
        ),
    )
    return jnp.moveaxis(ys, 0, 1).astype(dt.dtype)
