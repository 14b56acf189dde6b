"""Tiled Gram-matrix Pallas kernel for the Krum family (krum /
multi_krum / bulyan, [26] and El Mhamdi et al. 2018).

Krum scores need all pairwise squared distances
``||g_i - g_j||^2 = ||g_i||^2 + ||g_j||^2 - 2 <g_i, g_j>`` — everything
derives from the Gram matrix ``G @ G.T`` (the row sq-norms are its
diagonal), so one HBM pass over ``G:[S, d]`` accumulating
``[S, S]``-sized partial Grams per d-tile is all the kernel work; the
O(S^2 log S) distance sort happens host-side on the S^2-sized result
(KiBs at serving scales, never an HBM concern).

The whole worker axis is tile-resident (the output block must see every
row pair), so the lane tile is capped by the resident-block VMEM budget
in ``kernels.ops``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_BD = 1024


def _gram_kernel(g_ref, gram_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)

    g = g_ref[...].astype(jnp.float32)  # [S, bd]
    # [S, S] accumulator stays VMEM-resident across the d-grid; HIGHEST
    # keeps the MXU from rounding the f32 operands to one bf16 pass
    gram_ref[...] += jax.lax.dot_general(
        g, g, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def gram(g, *, block_d: int = DEF_BD, interpret: bool = False):
    """``G @ G.T`` over ``G:[S, d]`` in one HBM pass — [S, S] f32 out."""
    s, d = g.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    return pl.pallas_call(
        _gram_kernel,
        grid=(d // bd,),
        in_specs=[pl.BlockSpec((s, bd), lambda j: (0, j))],
        out_specs=pl.BlockSpec((s, s), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, s), jnp.float32),
        interpret=interpret,
    )(g)
