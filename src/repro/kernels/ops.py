"""Jitted public wrappers around the Pallas kernels.

On CPU (this container) kernels run in ``interpret=True`` mode — the
kernel body executes eagerly in Python per grid step, which validates
the block decomposition and the math against ``ref.py``.  On a real TPU
the same calls compile to Mosaic.

``*_pytree`` variants apply the fused ops to stacked update *pytrees*
(the FL aggregation interface): leaves are flattened into a padded
[S, d] matrix once, processed in two HBM passes, and unflattened.
"""
from __future__ import annotations

import math
import os
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import drag_calibrate as dk
from repro.kernels import flash_attention as fk
from repro.kernels import krum as kk
from repro.kernels import linear_recurrence as lrk
from repro.kernels import selective_scan as sk
from repro.kernels import trimmed_mean as tk
from repro.kernels import weiszfeld as wk
from repro.kernels.ref import calibrate_coeffs


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# ------------------------------------------------------- matrix-level ops

#: lane-tile ceiling: bs=8 x 65536 x f32 = 2 MiB per G tile — comfortably
#: inside the ~16 MiB VMEM budget with r/out tiles and double buffering
_MAX_LANE_TILE = 1 << 16

#: joint (bs x bd) G-tile budget for STREAMING kernels (double-buffered
#: against HBM): the default 8 x 65536 x f32 tile exactly
TILE_BUDGET = _MAX_LANE_TILE * 8 * 4

#: [S, bd] working-set budget for RESIDENT kernels (gram / trimmed_mean,
#: whole worker axis in one tile).  Larger than TILE_BUDGET because these
#: pipeline only the d-axis: no r/V tiles alongside, one accumulator
RESIDENT_BUDGET = 1 << 22

#: ops whose kernels need the whole worker axis tile-resident
_RESIDENT_OPS = ("gram", "trimmed_mean")


def _lane_mult(d: int) -> int:
    """Lane-padding target for a d-lane problem.

    Small problems pad to one aligned tile (multiple of 128); large ones
    pad to a multiple of 8 KiLanes so ``_lane_block`` is guaranteed a
    >= 8192 tile that divides d_pad — padding to the bare next 128/1024
    multiple can land on a prime-ish quotient whose only aligned
    divisor is the 128/1024 unit itself, exploding the grid.
    """
    return 128 if d <= _MAX_LANE_TILE else (1 << 13)


def _lane_block(d: int, cap: int = _MAX_LANE_TILE) -> int:
    """Largest lane tile that divides an ALIGNED d, capped for VMEM.

    Lane-dim multiples of 128 are a hard Mosaic tiling requirement; a
    big tile additionally keeps the grid small (fewer accumulator
    revisits — and far less per-step overhead in interpret mode).
    """
    unit = 1024 if d % 1024 == 0 and cap >= 1024 else 128
    n = d // unit
    best, i = 1, 1
    while i * i <= n:
        if n % i == 0:
            for m in (i, n // i):
                if m > best and m * unit <= cap:
                    best = m
        i += 1
    return best * unit


def _block_sizes(s: int, d: int) -> tuple[int, int]:
    """Clean (worker, lane) tile sizes for an ALIGNED [S, d] problem.

    Callers align first (``_pad_grid``): S to a multiple of 8 once it
    exceeds one sublane tile, d to a lane-aligned multiple — real-TPU
    Mosaic tiling needs lane-dim multiples of 128 and f32 sublane
    multiples of 8 (or the whole axis), and an unaligned fallback tile
    of bd = d would also blow the VMEM budget for large models.
    """
    if s > 8 and s % 8:
        raise ValueError(f"worker axis {s} is not sublane-aligned; pad it first")
    return min(s, 8), _lane_block(d) if d % 128 == 0 else d


# ------------------------------------------------------- autotune cache
# Measured per-(op, S, d, dtype) block-size selection for the two flush
# kernels (``dot_norms`` / ``blend_reduce``), memoized in-process.
#
# OPT-IN ONLY (``REPRO_AUTOTUNE=1`` or :func:`set_autotune`): the block
# split IS the f32 reduction order, so a measured tile that differs from
# the static ``_block_sizes`` choice changes results by reassociation
# ULPs — which would break the bit-for-bit oracles (sync<->async bridge,
# megastep-vs-unrolled) if it were ever on by default.
_AUTOTUNE = os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0", "false")
_AUTOTUNE_CACHE: dict = {}  # (op, s, d, dtype) -> (block_s, block_d)
_AUTOTUNE_TRIALS = 3


def set_autotune(enabled: bool) -> None:
    """Toggle measured block-size selection (process-wide)."""
    global _AUTOTUNE
    _AUTOTUNE = bool(enabled)


def autotune_report() -> dict:
    """JSON-safe provenance of every measured choice this process made —
    benchmarks attach it next to their timing cells."""
    return {
        f"{op}[{s}x{d}:{dt}]": {"block_s": bs, "block_d": bd}
        for (op, s, d, dt), (bs, bd) in sorted(_AUTOTUNE_CACHE.items())
    }


def _resident_lane_block(s: int, d: int) -> int:
    """Lane tile for a resident op: [s, bd] f32 within RESIDENT_BUDGET."""
    return _lane_block(d, cap=max(128, (RESIDENT_BUDGET // 4) // s))


def _block_candidates(s: int, d: int, *, bs_fixed: int | None = None,
                      budget: int = TILE_BUDGET) -> list[tuple[int, int]]:
    """Legal (bs, bd) tiles for an ALIGNED [s, d] problem: bs from the
    sublane ladder (divisors of s), bd from the aligned-128 divisor set
    under the lane cap — every candidate satisfies the same Mosaic
    constraints ``_block_sizes`` does, AND the joint bs*bd*4 VMEM tile
    budget (a wide bs must shrink bd with it — 32 x 65536 x f32 is 8 MiB,
    quadruple the streaming budget).  ``bs_fixed`` pins the worker axis
    (resident ops, which must see every row per tile)."""
    if bs_fixed is not None:
        bss = {bs_fixed}
        bds = {_resident_lane_block(s, d)}
    else:
        bs0, bd0 = _block_sizes(s, d)
        bss = {bs0} | {b for b in (8, 16, 32) if s % b == 0}
        bds = {bd0}
    if d % 128 == 0:
        for bd in (128, 1024, 8192, _MAX_LANE_TILE, d):
            if bd <= min(d, _MAX_LANE_TILE) and d % bd == 0:
                bds.add(bd)
    out = [(bs, bd) for bs in sorted(bss) for bd in sorted(bds)
           if bs * bd * 4 <= budget]
    return out or [(min(bss), min(bds))]


def _time_call(fn) -> float:
    jax.block_until_ready(fn())  # compile + warm outside the timer
    best = math.inf
    for _ in range(_AUTOTUNE_TRIALS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _tuned_blocks(op: str, s: int, d: int, dtype, interpret: bool) -> tuple[int, int]:
    """The measured (block_s, block_d) for one kernel shape, cached.

    Measurement runs EAGERLY on synthetic inputs of the caller's shape —
    only shapes/dtypes are read from the (possibly traced) caller
    arrays, so this is safe to hit from inside a jit trace."""
    key = (op, s, d, str(dtype))
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    g1 = jnp.ones((s, d), dtype)
    r1 = jnp.ones((d,), dtype)
    w1 = jnp.ones((s,), jnp.float32)

    def call(bs, bd):
        if op == "dot_norms":
            return dk.dot_norms(g1, r1, block_s=bs, block_d=bd, interpret=interpret)
        if op == "blend":
            return dk.blend(g1, r1, w1, w1, block_s=bs, block_d=bd,
                            interpret=interpret)
        if op == "weiszfeld":
            return wk.sq_dists(g1, r1, block_s=bs, block_d=bd, interpret=interpret)
        if op == "gram":
            return kk.gram(g1, block_d=bd, interpret=interpret)
        if op == "trimmed_mean":
            return tk.trimmed_mean(g1, 1, block_d=bd, interpret=interpret)
        return dk.blend_reduce(g1, r1, w1, w1, block_s=bs, block_d=bd,
                               interpret=interpret)

    resident = op in _RESIDENT_OPS
    if resident:
        cands = _block_candidates(s, d, bs_fixed=s, budget=RESIDENT_BUDGET)
        best = (s, _resident_lane_block(s, d))
    else:
        cands = _block_candidates(s, d)
        best = _block_sizes(s, d)
    best_t = math.inf
    for bs, bd in cands:
        t = _time_call(lambda: call(bs, bd))
        if t < best_t:
            best, best_t = (bs, bd), t
    _AUTOTUNE_CACHE[key] = best
    return best


def _select_blocks(op: str, gp, interpret: bool) -> tuple[int, int]:
    """One selection point for EVERY matrix-level op's tiling: the static
    policy (``_block_sizes``, or the resident-budget lane block for
    gram/trimmed_mean), or the measured choice when autotune is on."""
    s, d = gp.shape
    if _AUTOTUNE:
        return _tuned_blocks(op, s, d, gp.dtype, interpret)
    if op in _RESIDENT_OPS:
        return s, _resident_lane_block(s, d)
    return _block_sizes(s, d)


def _pad_grid(g, r):
    """Zero-pad G (rows and/or lanes) and r (lanes) to tile-aligned shapes.

    Lanes pad to a multiple of 1024 (128 for small d) so ``_lane_block``
    always finds a large aligned tile.  Padding with ZEROS is exact for
    every op in this file that uses it: zero lanes add 0.0 to
    dots/norms/blends (r is padded alongside g), and zero rows are
    sliced off / carry zero reduction weights — the invariants pinned by
    the padding regression tests.  Alignment costs one extra copy of G
    only when the model size is not already aligned; callers slice
    outputs back to the true (S, d).
    """
    s, d = g.shape
    lane_mult = _lane_mult(d)
    g, _ = _pad_to(g, lane_mult, axis=1)
    r, _ = _pad_to(r, lane_mult, axis=0)
    if s > 8:
        g, _ = _pad_to(g, 8, axis=0)
    return g, r, s, d


# ------------------------------------------------------- flush-path policy

#: padded [S, d] f32 working-set ceiling for the single-pass flush: the
#: whole stack must be VMEM-resident (the blend coefficients need global
#: d-reductions, so no per-tile Delta can be emitted before they finish)
FUSED_VMEM_BYTES = 1 << 22

_PATH_CACHE: dict = {}  # (s, d) -> "fused" | "two_pass" (autotuned)


def _padded_shape(s: int, d: int) -> tuple[int, int]:
    """The [S, d] shape ``_pad_grid`` would produce, arithmetically."""
    d_pad = d + (-d) % _lane_mult(d)
    s_pad = s + ((-s) % 8 if s > 8 else 0)
    return s_pad, d_pad


def flush_path(s: int, d: int) -> str:
    """Which flush a [s, d] stack takes: ``"fused"`` (one ``fused_flush``
    kernel, VMEM-resident) or ``"two_pass"`` (``dot_norms`` +
    ``blend_reduce``).  Deterministic in the shape — every call site
    (flat engines, sharded pods, instrumentation, benchmarks) resolves
    through here, so the bit-for-bit oracles stay path-consistent.  With
    autotune on, an eligible shape is measured both ways instead.

    The budget counts at least 8 rows: VMEM tiles f32 in 8-sublane
    units, so an [S < 8, d] block occupies as much as an [8, d] one.
    """
    s_pad, d_pad = _padded_shape(s, d)
    if max(s_pad, 8) * d_pad * 4 > FUSED_VMEM_BYTES:
        return "two_pass"
    if _AUTOTUNE:
        return _tuned_path(s, d)
    return "fused"


def _tuned_path(s: int, d: int) -> str:
    """Measured fused-vs-two-pass choice for one eligible shape, cached.

    Same eager-on-synthetic-inputs contract as ``_tuned_blocks``."""
    key = (s, d)
    if key in _PATH_CACHE:
        return _PATH_CACHE[key]
    g1 = jnp.ones((s, d), jnp.float32)
    r1 = jnp.ones((d,), jnp.float32)
    w1 = jnp.full((s,), 1.0 / s, jnp.float32)
    interpret = _interpret_default()
    t_fused = _time_call(lambda: _flush_fused(
        g1, r1, 0.5, "drag", w=w1, discounts=None, init=None, boot_aw=None,
        interpret=interpret))
    t_two = _time_call(lambda: _flush_two_pass(
        g1, r1, 0.5, "drag", w=w1, discounts=None, init=None, boot_aw=None,
        interpret=interpret))
    path = "fused" if t_fused <= t_two else "two_pass"
    _PATH_CACHE[key] = path
    return path


def _flush_two_pass(g, r, c: float, mode: str, *, w, discounts, init,
                    boot_aw, interpret):
    """dot_norms + blend_reduce — the exact pre-existing op sequence
    (bit-for-bit with what the callers previously inlined)."""
    dots, gsq, rsq = dot_norms_stats(g, r, interpret=interpret)
    if mode == "mean":
        a = jnp.ones_like(dots)
        b = jnp.zeros_like(dots)
        lam = jnp.zeros_like(dots)
    else:
        a, b, lam = calibrate_coeffs(dots, gsq, rsq, c, mode, discounts)
    wf = jnp.asarray(w, jnp.float32)
    aw, bw = wf * a, wf * b
    if init is not None:
        u = jnp.zeros_like(aw) if boot_aw is None else jnp.asarray(boot_aw, jnp.float32)
        aw = jnp.where(init, aw, u)
        bw = jnp.where(init, bw, 0.0)
        lam = jnp.where(init, lam, 0.0)
    delta = blend_reduce(g, r, aw, bw, interpret=interpret)
    return delta, lam, (dots, gsq, rsq)


def _flush_fused(g, r, c: float, mode: str, *, w, discounts, init, boot_aw,
                 interpret):
    """One ``fused_flush`` kernel over the padded stack."""
    s, d = g.shape
    gp, rp, _, _ = _pad_grid(g, r)
    sp = gp.shape[0]
    phi = (jnp.ones((s,), jnp.float32) if discounts is None
           else jnp.asarray(discounts, jnp.float32))
    wf = jnp.asarray(w, jnp.float32)
    u = (jnp.zeros((s,), jnp.float32) if boot_aw is None
         else jnp.asarray(boot_aw, jnp.float32))
    if sp != s:  # padded rows: w = u = 0 -> exact-zero contribution
        phi, _ = _pad_to(phi, sp, axis=0)
        wf, _ = _pad_to(wf, sp, axis=0)
        u, _ = _pad_to(u, sp, axis=0)
    sel = (jnp.ones((1,), jnp.float32) if init is None
           else jnp.asarray(init).astype(jnp.float32).reshape(1))
    delta, dots, gsq, rsq = dk.fused_flush(
        gp, rp, phi, wf, u, sel, c=c, mode=mode, interpret=interpret)
    dots, gsq = dots[:s], gsq[:s]
    if mode == "mean":
        lam = jnp.zeros((s,), jnp.float32)
    else:
        # same formula on the same kernel-reduced scalars the in-kernel
        # coefficients used — bit-identical lam, no second HBM pass
        _, _, lam = calibrate_coeffs(dots, gsq, rsq, c, mode, discounts)
    if init is not None:
        lam = jnp.where(init, lam, 0.0)
    return delta[:d], lam, (dots, gsq, rsq)


def calibrated_reduce(g, r, c: float, mode: str, *, w, discounts=None,
                      init=None, boot_aw=None, interpret: bool | None = None):
    """The whole calibrated flush over flat G:[S,d] — fused or two-pass.

    The ONE entry point every flush takes (flat engines, async stream,
    sharded pods): ``flush_path`` picks single-pass ``fused_flush`` for
    VMEM-resident stacks, else the streaming ``dot_norms`` +
    ``blend_reduce`` pair.

    ``w``: ALREADY-normalised [S] aggregation weights (callers own
    normalisation — the sharded plane normalises globally, then slices).
    ``mode``: "drag" / "br_drag" / "mean" (a=1, b=0, lam=0).
    ``init`` (optional bool scalar): DRAG bootstrap switch — when falsy
    the flush reduces with ``boot_aw`` (e.g. uniform 1/S) instead of
    ``w * a`` and zero r-coefficients/lam (eq. 5a).

    Returns (delta [d] f32, lam [S], (dots, g_sq, r_sq)).
    """
    interpret = _interpret_default() if interpret is None else interpret
    s, d = g.shape
    if flush_path(s, d) == "fused":
        return _flush_fused(g, r, c, mode, w=w, discounts=discounts,
                            init=init, boot_aw=boot_aw, interpret=interpret)
    return _flush_two_pass(g, r, c, mode, w=w, discounts=discounts,
                           init=init, boot_aw=boot_aw, interpret=interpret)


@partial(jax.jit, static_argnames=("c", "mode", "interpret"))
def drag_calibrate(g, r, c: float, mode: str = "drag", interpret: bool | None = None):
    """Fused eqs. (10)+(11)/(15) over G:[S,d], r:[d].

    Returns (v [S,d], lam [S], delta [d]) where delta = mean_s v_s.
    """
    interpret = _interpret_default() if interpret is None else interpret
    gp, rp, s, d = _pad_grid(g, r)
    bs, bd = _select_blocks("blend", gp, interpret)
    dots, gsq, rsq = dk.dot_norms(gp, rp, block_s=bs, block_d=bd, interpret=interpret)
    a, b, lam = calibrate_coeffs(dots[:s], gsq[:s], rsq, c, mode)
    if gp.shape[0] != s:  # padded rows blend with zero coefficients
        a, _ = _pad_to(a, gp.shape[0], axis=0)
        b, _ = _pad_to(b, gp.shape[0], axis=0)
    v = dk.blend(gp, rp, a, b, block_s=bs, block_d=bd, interpret=interpret)
    v = v[:s, :d]
    delta = jnp.mean(v, axis=0)
    return v, lam, delta


def dot_norms_stats(g, r, interpret: bool | None = None):
    """Phase-1 scalars over G:[S,d], r:[d] — one HBM pass.

    Returns (dots [S], g_sq [S], r_sq []): everything the DoD
    calibration, the trust layer's divergence signals, AND the flush
    metrics need — computed once and shared (``repro.trust``'s
    ``signals_from_stats`` is the other consumer).
    """
    interpret = _interpret_default() if interpret is None else interpret
    gp, rp, s, _ = _pad_grid(g, r)
    bs, bd = _select_blocks("dot_norms", gp, interpret)
    dots, gsq, rsq = dk.dot_norms(gp, rp, block_s=bs, block_d=bd, interpret=interpret)
    return dots[:s], gsq[:s], rsq  # padded zero rows sliced off


def blend_reduce(g, r, aw, bw, interpret: bool | None = None):
    """Phase-2 fused blend + reduction — one HBM pass, Delta [d] out.

    Padded worker rows (alignment) get ZERO coefficients, so they are
    excluded from the reduction exactly, not approximately.
    """
    interpret = _interpret_default() if interpret is None else interpret
    gp, rp, s, d = _pad_grid(g, r)
    if gp.shape[0] != s:
        aw, _ = _pad_to(aw, gp.shape[0], axis=0)
        bw, _ = _pad_to(bw, gp.shape[0], axis=0)
    bs, bd = _select_blocks("blend_reduce", gp, interpret)
    out = dk.blend_reduce(gp, rp, aw, bw, block_s=bs, block_d=bd, interpret=interpret)
    return out[:d]


def normalize_weights(weights, s: int) -> jnp.ndarray:
    """[S] aggregation weights summing to 1; None = uniform mean.

    Mirrors ``pytree.tree_weighted_mean``: near-zero total (every client
    quarantined) falls back to uniform rather than a zero/NaN step.
    """
    if weights is None:
        return jnp.full((s,), 1.0 / s, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)
    wsum = jnp.sum(w)
    eps = 1e-12
    return jnp.where(wsum > eps, w / jnp.maximum(wsum, eps), jnp.full((s,), 1.0 / s))


def drag_calibrate_reduce(
    g, r, c: float, mode: str = "drag", discounts=None, weights=None,
    interpret: bool | None = None,
):
    """The whole DRAG/BR-DRAG flush over flat G:[S,d].

    Normalises the aggregation weights (uniform / trust reputations) and
    defers to :func:`calibrated_reduce` — one ``fused_flush`` pass for
    VMEM-resident stacks, else ``dot_norms`` + ``blend_reduce``.

    Returns (delta [d] f32, lam [S], (dots, g_sq, r_sq)).
    """
    w = normalize_weights(weights, g.shape[0])
    return calibrated_reduce(g, r, c, mode, w=w, discounts=discounts,
                             interpret=interpret)


@partial(jax.jit, static_argnames=("iters", "interpret"))
def geometric_median(g, iters: int = 8, eps: float = 1e-8, interpret: bool | None = None):
    """Weiszfeld iterations over G:[S,d] using the two Pallas kernels."""
    interpret = _interpret_default() if interpret is None else interpret
    # padded zero COLUMNS stay exactly zero through the iteration; padded
    # zero ROWS would enter the Weiszfeld weights, so their weights are
    # masked to exactly zero (no share of the numerator or of sum(w))
    gp, d0 = _pad_to(g, _lane_mult(g.shape[1]), axis=1)
    z = jnp.mean(gp.astype(jnp.float32), axis=0)
    s = g.shape[0]
    if s > 8:
        gp, _ = _pad_to(gp, 8, axis=0)
    live = jnp.arange(gp.shape[0]) < s
    bs, bd = _select_blocks("weiszfeld", gp, interpret)

    def body(z, _):
        d2 = wk.sq_dists(gp, z, block_s=bs, block_d=bd, interpret=interpret)
        w = jnp.where(live, 1.0 / jnp.maximum(jnp.sqrt(d2), eps), 0.0)
        num = wk.weighted_sum(gp, w, block_s=bs, block_d=bd, interpret=interpret)
        return num / jnp.sum(w), None

    z, _ = jax.lax.scan(body, z, None, length=iters)
    return z[:d0].astype(g.dtype)


#: regime gate for the trimmed-mean cascade kernel: the unrolled
#: compare-exchange network is O(s * trim) min/max per coordinate and
#: O(s * trim) trace size — past this, rank selection wins
_CASCADE_MAX = 512


@partial(jax.jit, static_argnames=("trim", "interpret"))
def trimmed_mean(g, trim: int, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    s = g.shape[0]
    if s * trim > _CASCADE_MAX:  # large-S regime: lax.top_k rank selection
        return tk.trimmed_mean_rank(g, trim)
    # lane-align; padded zero columns are trimmed/averaged among
    # themselves and sliced off — real coordinates never see them
    gp, d0 = _pad_to(g, _lane_mult(g.shape[1]), axis=1)
    _, bd = _select_blocks("trimmed_mean", gp, interpret)
    return tk.trimmed_mean(gp, trim, block_d=bd, interpret=interpret)[:d0]


@partial(jax.jit, static_argnames=("interpret",))
def pairwise_sq_dists(g, interpret: bool | None = None):
    """All-pairs ||g_i - g_j||^2 over G:[S,d] — one Gram pass, [S,S] f32.

    The Krum-family front half: d2 = sq_i + sq_j - 2 * (G @ G.T) with the
    row sq-norms read off the Gram diagonal, clamped at 0 (reassociation
    can push tiny true distances negative).
    """
    interpret = _interpret_default() if interpret is None else interpret
    s = g.shape[0]
    gp, _ = _pad_to(g.astype(jnp.float32), _lane_mult(g.shape[1]), axis=1)
    if s > 8:  # zero rows: zero Gram entries, sliced off below
        gp, _ = _pad_to(gp, 8, axis=0)
    _, bd = _select_blocks("gram", gp, interpret)
    gm = kk.gram(gp, block_d=bd, interpret=interpret)[:s, :s]
    sq = jnp.diagonal(gm)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gm, 0.0)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
):
    """Flash attention over [B, H, S, dh] with GQA k/v [B, Hkv, S, dh].

    Pads Sq/Sk up to the block sizes (padded k positions are masked by
    the causal/window tests; padded q rows are sliced off).
    """
    interpret = _interpret_default() if interpret is None else interpret
    b, h, sq, dh = q.shape
    sk_len = k.shape[2]
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(sk_len, 8))
    qp, _ = _pad_to(q, bq, axis=2)
    kp, _ = _pad_to(k, bk, axis=2)
    vp, _ = _pad_to(v, bk, axis=2)
    # padded kv positions have kpos > any real qpos - masked iff causal;
    # for non-causal, mask by windowing on the true length
    win = window
    if not causal and kp.shape[2] != sk_len:
        raise ValueError("non-causal padding unsupported; pad upstream")
    out = fk.flash_attention(
        qp, kp, vp, causal=causal, window=win,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return out[:, :, :sq]


@partial(jax.jit, static_argnames=("block_di", "chunk", "interpret"))
def selective_scan(dt, x, b, c, a, *, block_di: int = 512, chunk: int = 256,
                   interpret: bool | None = None):
    """Diagonal selective SSM scan (Mamba-1) — see kernels.selective_scan."""
    interpret = _interpret_default() if interpret is None else interpret
    di = dt.shape[-1]
    s = dt.shape[1]
    bdi = block_di if di % block_di == 0 else (128 if di % 128 == 0 else di)
    ck = chunk if s % chunk == 0 else s
    return sk.selective_scan(dt, x, b, c, a, block_di=bdi, chunk=ck, interpret=interpret)


@partial(jax.jit, static_argnames=("block_w", "chunk", "interpret"))
def linear_recurrence(a, g, *, block_w: int = 512, chunk: int = 256,
                      interpret: bool | None = None):
    """h_t = a_t h_{t-1} + g_t over [B, S, w] (RG-LRU) — Pallas kernel."""
    interpret = _interpret_default() if interpret is None else interpret
    w, s = a.shape[-1], a.shape[1]
    bw = block_w if w % block_w == 0 else (128 if w % 128 == 0 else w)
    ck = chunk if s % chunk == 0 else s
    return lrk.linear_recurrence(a, g, block_w=bw, chunk=ck, interpret=interpret)


# ------------------------------------------------------- pytree-level ops
# Convenience wrappers for callers still holding stacked pytrees.  The
# SERVING path does not use these: it flattens once at the boundary
# (repro.core.flat) and calls the matrix-level ops above directly.

def _stack_flatten(updates_stacked):
    """Stacked pytree (leading S axis) -> [S, d_padded] matrix + meta."""
    leaves = jax.tree.leaves(updates_stacked)
    s = leaves[0].shape[0]
    flat = jnp.concatenate(
        [x.reshape(s, -1).astype(jnp.float32) for x in leaves], axis=1
    )
    flat, d = _pad_to(flat, 128, axis=1)
    return flat, d


def _unflatten_like(vec, like_single):
    leaves, treedef = jax.tree.flatten(like_single)
    out, off = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(vec[off : off + n].reshape(leaf.shape).astype(leaf.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def drag_calibrate_pytree(updates_stacked, reference, c: float, mode: str = "drag"):
    """Fused DRAG aggregation over stacked update pytrees.

    Returns (delta pytree, lam [S]).  Numerically identical (up to f32
    reassociation) to ``repro.core.drag.aggregate`` /
    ``repro.core.br_drag.aggregate``.
    """
    g, _ = _stack_flatten(updates_stacked)
    r_flat, _ = _stack_flatten(jax.tree.map(lambda x: x[None], reference))
    r = r_flat[0]
    _, lam, delta = drag_calibrate(g, r, c, mode)
    single = jax.tree.map(lambda x: x[0], updates_stacked)
    return _unflatten_like(delta, single), lam


def geometric_median_pytree(updates_stacked, iters: int = 8):
    g, _ = _stack_flatten(updates_stacked)
    z = geometric_median(g, iters=iters)
    single = jax.tree.map(lambda x: x[0], updates_stacked)
    return _unflatten_like(z, single)


def trimmed_mean_pytree(updates_stacked, trim: int):
    g, _ = _stack_flatten(updates_stacked)
    tm = trimmed_mean(g, trim)
    single = jax.tree.map(lambda x: x[0], updates_stacked)
    return _unflatten_like(tm, single)
