"""Fused DRAG/BR-DRAG calibration Pallas TPU kernels.

The aggregation math of eqs. (10)/(11)/(15) over a stacked update matrix
``G:[S, d]`` (d = model parameter count, tens of GB at assigned scales)
is memory-bound: naive jnp issues four HBM passes over G (dot, norm,
scale, blend).  Two kernels bring that to two passes:

  * ``dot_norms``  — one pass: per-worker <g_m, r>, ||g_m||^2 and ||r||^2
    accumulated in VMEM scratch across d-tiles (grid = (S/bs, d/bd),
    f32 accumulators).
  * ``blend``      — one pass: v_m = a_m * g_m + b_m * r with the per-
    worker coefficients a, b computed on-host from the phase-1 scalars
    (a [S]-sized vector; negligible).
  * ``blend_reduce`` — one pass: the *serving* epilogue.  Instead of
    materialising V:[S, d] (an extra [S, d] HBM write nobody reads —
    the flush only needs Delta), it folds the weighted-mean reduction
    into the blend: Delta = sum_s aw_s * g_s + (sum_s bw_s) * r, where
    aw = w * a and bw = w * b carry the staleness discounts and trust
    weights pre-multiplied into the blend coefficients on-host.  A
    whole DRAG/BR-DRAG flush is then exactly two HBM passes over G:
    dot_norms + blend_reduce.

  * ``fused_flush`` — ONE pass: for stacks whose [S, d] working set fits
    the VMEM budget (small-S serving regimes, exactly where per-kernel
    launch overhead dominates the two-pass path) the whole flush runs as
    a single kernel: phase-1 scalars reduced over the resident block,
    blend coefficients formed IN-KERNEL from the already-reduced scalars
    (same ``calibrate_coeffs`` formulas as the host path — the oracle
    pins parity at 1e-5), bootstrap select applied, and Delta emitted —
    G is read from HBM exactly once.  Eligibility/selection lives in
    ``kernels.ops`` (``_select_blocks``-style policy + autotune).

Block sizes default to (8, 1024): G tile 8x1024xf32 = 32 KiB VMEM, r
tile 4 KiB — well inside the ~16 MiB VMEM budget, lane-dim 1024 is a
multiple of 128 for clean vectorisation.

Mosaic layout rules the kernels follow: per-worker vectors (dots, norms,
blend coefficients, weights) travel as ``[S, 1]`` columns, so their
blocks tile like G's rows; lane vectors (r, Delta) stay rank 1 with
128-multiple blocks.  No product is rank-1 x rank-2, which Mosaic does
not lower: per-row dots are an elementwise multiply plus a lane
reduction, and the weighted row sums are a 2-D contraction
(:func:`row_weighted_sum`) at ``HIGHEST`` precision, so the MXU keeps
f32 operands instead of rounding them to one bf16 pass.  The public
signatures keep ``[S]`` vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import calibrate_coeffs

DEF_BS = 8  # workers per tile (sublane dim)
DEF_BD = 1024  # parameter-dim tile (lane dim, multiple of 128)


def column(x):
    """[S] -> [S, 1] f32: the Mosaic-tileable per-worker layout."""
    return jnp.asarray(x, jnp.float32).reshape(-1, 1)


def row_weighted_sum(w_col, g):
    """sum_s w[s] * g[s] for a ``[bs, 1]`` weight column and a
    ``[bs, bd]`` tile -> ``[bd]`` f32."""
    return jax.lax.dot_general(
        w_col, g, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[0]


def row_block(bs: int, row_axis: int):
    """[bs, 1] block of a per-worker column; the row-tile index is grid
    axis ``row_axis``."""
    return pl.BlockSpec((bs, 1), lambda *ij: (ij[row_axis], 0))


# ------------------------------------------------------------ dot_norms

def _dot_norms_kernel(g_ref, r_ref, dots_ref, gsq_ref, rsq_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        gsq_ref[...] = jnp.zeros_like(gsq_ref)

    @pl.when((i == 0) & (j == 0))
    def _init_r():
        rsq_ref[...] = jnp.zeros_like(rsq_ref)

    g = g_ref[...].astype(jnp.float32)  # [bs, bd]
    r = r_ref[...].astype(jnp.float32)  # [bd]
    dots_ref[...] += jnp.sum(g * r[None, :], axis=1, keepdims=True)
    gsq_ref[...] += jnp.sum(g * g, axis=1, keepdims=True)
    # accumulate ||r||^2 once per d-tile (only on the first worker row)
    @pl.when(i == 0)
    def _racc():
        rsq_ref[...] += jnp.sum(r * r)


def dot_norms(g, r, *, block_s: int = DEF_BS, block_d: int = DEF_BD, interpret: bool = False):
    s, d = g.shape
    bs, bd = min(block_s, s), min(block_d, d)
    assert s % bs == 0 and d % bd == 0, (s, d, bs, bd)
    grid = (s // bs, d // bd)
    dots, gsq, rsq = pl.pallas_call(
        _dot_norms_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=[
            row_block(bs, 0),
            row_block(bs, 0),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(g, r)
    return dots[:, 0], gsq[:, 0], rsq[0, 0]


# ---------------------------------------------------------------- blend

def _blend_kernel(g_ref, r_ref, a_ref, b_ref, v_ref):
    g = g_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    v_ref[...] = (a_ref[...] * g + b_ref[...] * r[None, :]).astype(v_ref.dtype)


def blend(g, r, a, b, *, block_s: int = DEF_BS, block_d: int = DEF_BD, interpret: bool = False):
    s, d = g.shape
    bs, bd = min(block_s, s), min(block_d, d)
    assert s % bs == 0 and d % bd == 0
    grid = (s // bs, d // bd)
    return pl.pallas_call(
        _blend_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
            row_block(bs, 0),
            row_block(bs, 0),
        ],
        out_specs=pl.BlockSpec((bs, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s, d), g.dtype),
        interpret=interpret,
    )(g, r, column(a), column(b))


# --------------------------------------------------------- blend_reduce

def _blend_reduce_kernel(g_ref, r_ref, aw_ref, bw_ref, out_ref):
    i = pl.program_id(1)  # worker-tile index (reduction axis, innermost)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)  # [bs, bd]
    r = r_ref[...].astype(jnp.float32)  # [bd]
    aw = aw_ref[...]  # [bs, 1]
    bw = bw_ref[...]  # [bs, 1]
    # sum_s aw_s g_s + (sum_s bw_s) r, accumulated per worker tile; the
    # [bd] output block stays VMEM-resident across the inner i loop
    out_ref[...] += row_weighted_sum(aw, g) + jnp.sum(bw) * r


def blend_reduce(g, r, aw, bw, *, block_s: int = DEF_BS, block_d: int = DEF_BD,
                 interpret: bool = False):
    """Fused blend + weighted reduction: Delta = sum_s (aw_s g_s + bw_s r).

    The calibrated stack V is never materialised — one HBM read pass
    over G, one [d] write.  ``aw``/``bw`` are the blend coefficients
    with the aggregation weights (uniform 1/S, staleness discounts,
    trust reputations) already multiplied in on-host.
    """
    s, d = g.shape
    bs, bd = min(block_s, s), min(block_d, d)
    assert s % bs == 0 and d % bd == 0, (s, d, bs, bd)
    grid = (d // bd, s // bs)  # d outer so the out tile stays resident
    return pl.pallas_call(
        _blend_reduce_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bd), lambda j, i: (i, j)),
            pl.BlockSpec((bd,), lambda j, i: (j,)),
            row_block(bs, 1),
            row_block(bs, 1),
        ],
        out_specs=pl.BlockSpec((bd,), lambda j, i: (j,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(g, r, column(aw), column(bw))


# ---------------------------------------------------------- fused_flush

def _fused_flush_kernel(g_ref, r_ref, phi_ref, w_ref, u_ref, sel_ref,
                        delta_ref, dots_ref, gsq_ref, rsq_ref,
                        *, c: float, mode: str):
    # the whole [S, d] block is VMEM-resident: phase-1 scalars reduce
    # over it in place of the separate dot_norms pass...
    g = g_ref[...].astype(jnp.float32)  # [S, d]
    r = r_ref[...].astype(jnp.float32)  # [d]
    dots = jnp.sum(g * r[None, :], axis=1, keepdims=True)  # [S, 1]
    gsq = jnp.sum(g * g, axis=1, keepdims=True)
    rsq = jnp.sum(r * r)
    # ...and the blend coefficients come straight from the just-reduced
    # scalars — the exact host-side formulas (eqs. (11)/(15)), so the
    # two-pass path and the pytree oracle stay 1e-5 targets
    if mode == "mean":
        a = jnp.ones_like(dots)
        b = jnp.zeros_like(dots)
    else:
        a, b, _ = calibrate_coeffs(dots, gsq, rsq, c, mode, phi_ref[...])
    sel = sel_ref[...] > 0.5  # [1, 1] DRAG bootstrap switch (eq. 5a)
    aw = jnp.where(sel, w_ref[...] * a, u_ref[...])
    bw = jnp.where(sel, w_ref[...] * b, 0.0)
    delta_ref[...] = row_weighted_sum(aw, g) + jnp.sum(bw) * r
    dots_ref[...] = dots
    gsq_ref[...] = gsq
    rsq_ref[...] = jnp.full((1, 1), rsq)


def fused_flush(g, r, phi, w, u, sel, *, c: float, mode: str,
                interpret: bool = False):
    """Single-pass DRAG/BR-DRAG flush for VMEM-resident stacks.

    One HBM read of ``G:[S, d]`` produces (delta [d], dots [S], gsq [S],
    rsq []): the phase-1 scalars, the in-kernel coefficients, the
    bootstrap select ``aw = sel ? w*a : u`` / ``bw = sel ? w*b : 0`` and
    the fused weighted reduction.  ``phi`` are staleness discounts
    (ones when fresh), ``w`` the normalised aggregation weights, ``u``
    the bootstrap fallback weights (zeros disable), ``sel`` a [1] f32
    switch (1 = calibrated, 0 = bootstrap).  Padded rows must carry
    w = u = 0 so they drop out of the reduction exactly.  Eligibility
    (the VMEM fit) is the caller's job — see ``ops.flush_path``.
    """
    s, d = g.shape
    delta, dots, gsq, rsq = pl.pallas_call(
        functools.partial(_fused_flush_kernel, c=c, mode=mode),
        out_shape=[
            jax.ShapeDtypeStruct((d,), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(g, r, column(phi), column(w), column(u), column(sel))
    return delta, dots[:, 0], gsq[:, 0], rsq[0, 0]
