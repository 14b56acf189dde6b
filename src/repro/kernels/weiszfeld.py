"""Weiszfeld geometric-median iteration Pallas kernels (RFA/RAGA reducers).

Per iteration over ``G:[S, d]`` and current estimate ``z:[d]``:
  w_s = 1 / max(||g_s - z||, eps);  z' = sum_s w_s g_s / sum_s w_s

Kernel 1 (``sq_dists``): per-worker squared distances, one HBM pass over
G with VMEM accumulation across d-tiles.
Kernel 2 (``weighted_sum``): one HBM pass producing the reweighted sum
with the [S] weight vector resident in VMEM.

Per-worker vectors use the ``[S, 1]`` column layout and the weighted sum
of ``kernels.drag_calibrate``, under the same Mosaic layout rules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.drag_calibrate import column, row_block, row_weighted_sum

DEF_BS = 8
DEF_BD = 1024


def _sq_dists_kernel(g_ref, z_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    diff = g - z[None, :]
    out_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)


def sq_dists(g, z, *, block_s=DEF_BS, block_d=DEF_BD, interpret=False):
    s, d = g.shape
    bs, bd = min(block_s, s), min(block_d, d)
    assert s % bs == 0 and d % bd == 0
    out = pl.pallas_call(
        _sq_dists_kernel,
        grid=(s // bs, d // bd),
        in_specs=[
            pl.BlockSpec((bs, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=row_block(bs, 0),
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.float32),
        interpret=interpret,
    )(g, z)
    return out[:, 0]


def _weighted_sum_kernel(g_ref, w_ref, out_ref):
    i = pl.program_id(1)  # worker-tile index (reduction axis)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)  # [bs, bd]
    out_ref[...] += row_weighted_sum(w_ref[...], g)  # w: [bs, 1]


def weighted_sum(g, w, *, block_s=DEF_BS, block_d=DEF_BD, interpret=False):
    """sum_s w_s g_s  -> [d]  (normalisation done by the caller)."""
    s, d = g.shape
    bs, bd = min(block_s, s), min(block_d, d)
    assert s % bs == 0 and d % bd == 0
    return pl.pallas_call(
        _weighted_sum_kernel,
        grid=(d // bd, s // bs),  # d outer so the out tile stays resident
        in_specs=[
            pl.BlockSpec((bs, bd), lambda j, i: (i, j)),
            row_block(bs, 1),
        ],
        out_specs=pl.BlockSpec((bd,), lambda j, i: (j,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(g, column(w))
