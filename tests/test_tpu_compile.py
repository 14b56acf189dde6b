"""The main-path kernels compile for a TPU v5e that is described, not attached.

Interpret mode on the CPU checks the kernels' math; only the chip's own
compiler checks that Mosaic accepts their block shapes, products and
VMEM use.  Every test here lowers a kernel (or a whole flush) for one
chip of a described ``v5e:2x2`` topology, compiles it, and asserts a
Mosaic kernel (``tpu_custom_call``) in the program: at the CIFAR-10 CNN's
update width (S = 10 clients, d = 579,402) and at one S <= 8 shape.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.  The persistent
compilation cache is off around these compiles, since a program compiled
for a described chip cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import drag_calibrate as dk
from repro.kernels import krum as kk
from repro.kernels import ops
from repro.kernels import trimmed_mean as tk
from repro.kernels import weiszfeld as wk
from repro.stream import sharded

#: (S, d) cells: the paper's CIFAR-10 setup and a small-S serving shape
SHAPES = {"cifar10": (10, 579402), "s5": (5, 100000)}
#: fused_flush only takes VMEM-resident stacks: the budget's edge instead
#: of the CIFAR-10 width, the same small-S shape, and the edge at S = 2
#: (VMEM pads rows to 8, so a [2, 524288] stack would not fit)
FUSED_SHAPES = {"vmem_edge": (8, 131072), "s5": (5, 100000), "s2_edge": (2, 131072)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """The compiled program's text; asserts a Mosaic kernel in it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _padded(shape):
    """The aligned [S, d] a flush op hands its kernels, and their tiles."""
    s_pad, d_pad = ops._padded_shape(*shape)
    return s_pad, d_pad, ops._block_sizes(s_pad, d_pad)


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_dot_norms(one_chip, cell):
    s, d, (bs, bd) = _padded(SHAPES[cell])
    f32 = jnp.float32
    _compile(lambda g, r: dk.dot_norms(g, r, block_s=bs, block_d=bd),
             jax.ShapeDtypeStruct((s, d), f32, sharding=one_chip),
             jax.ShapeDtypeStruct((d,), f32, sharding=one_chip))


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_blend_reduce(one_chip, cell):
    s, d, (bs, bd) = _padded(SHAPES[cell])
    row = jax.ShapeDtypeStruct((s,), jnp.float32, sharding=one_chip)
    _compile(lambda g, r, a, b: dk.blend_reduce(g, r, a, b, block_s=bs, block_d=bd),
             jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip), row, row)


@pytest.mark.parametrize("cell", sorted(FUSED_SHAPES))
def test_fused_flush(one_chip, cell):
    assert ops.flush_path(*FUSED_SHAPES[cell]) == "fused"
    s, d, _ = _padded(FUSED_SHAPES[cell])
    row = jax.ShapeDtypeStruct((s,), jnp.float32, sharding=one_chip)
    for mode in ("drag", "br_drag"):
        _compile(lambda g, r, phi, w, u, sel: dk.fused_flush(
                     g, r, phi, w, u, sel, c=0.25, mode=mode),
                 jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one_chip),
                 jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip),
                 row, row, row,
                 jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_sq_dists(one_chip, cell):
    s, d, (bs, bd) = _padded(SHAPES[cell])
    _compile(lambda g, z: wk.sq_dists(g, z, block_s=bs, block_d=bd),
             jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_weighted_sum(one_chip, cell):
    s, d, (bs, bd) = _padded(SHAPES[cell])
    _compile(lambda g, w: wk.weighted_sum(g, w, block_s=bs, block_d=bd),
             jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((s,), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_gram(one_chip, cell):
    s, d, _ = _padded(SHAPES[cell])
    bd = ops._resident_lane_block(s, d)
    _compile(lambda g: kk.gram(g, block_d=bd),
             jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_trimmed_mean(one_chip, cell):
    s0, d0 = SHAPES[cell]
    d = d0 + (-d0) % ops._lane_mult(d0)  # lanes only: rows are never padded
    bd = ops._resident_lane_block(s0, d)
    _compile(lambda g: tk.trimmed_mean(g, 2, block_d=bd),
             jax.ShapeDtypeStruct((s0, d), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("mode", ["drag", "br_drag", "mean"])
@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_calibrated_reduce(one_chip, cell, mode):
    """The whole flush every DRAG/BR-DRAG engine takes, padding included,
    with trust weights, staleness discounts and the bootstrap switch."""
    s, d = SHAPES[cell]
    row = jax.ShapeDtypeStruct((s,), jnp.float32, sharding=one_chip)
    _compile(lambda g, r, w, phi, init: ops.calibrated_reduce(
                 g, r, 0.25, mode, w=w, discounts=phi, init=init, boot_aw=w,
                 interpret=False),
             jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip), row, row,
             jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip))


def test_hierarchical_flush_four_pods_one_all_reduce(topo):
    """The 4-pod sharded flush: a kernel per pod and exactly one
    cross-pod all-reduce carrying the [d] partial and the row scalars."""
    mesh = Mesh(np.array(topo.devices[:4]), ("pod",))
    kp, d = 2, SHAPES["cifar10"][1]
    slots = NamedSharding(mesh, P("pod", None, None))
    rep = NamedSharding(mesh, P())
    text = _compile(
        lambda s3, r, disc, w: sharded.hierarchical_flush(
            s3, r, mode="drag", c=0.25, discounts2=disc, weights=w, init=True,
            mesh=mesh, interpret=False),
        jax.ShapeDtypeStruct((4, kp, d), jnp.float32, sharding=slots),
        jax.ShapeDtypeStruct((d,), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((4, kp), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((4 * kp,), jnp.float32, sharding=rep),
    )
    assert len(re.findall(r"\ball-reduce(?:-start)?\(", text)) == 1


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_moonlight_held_experts_grouped_products(one_chip):
    """The dropless held-expert layer at Moonlight's widths (8 of 64
    experts of width 1,408 on d = 2,048, 6 per token), forward and input
    gradient over 512 tokens: grouped-product kernels."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import moe

    cfg = get_arch("moonlight-16b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=8))
    params = jax.eval_shape(lambda k: moe.init_moe(k, cfg, jnp.float32), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                          params)
    x = jax.ShapeDtypeStruct((1, 512, cfg.d_model), jnp.float32, sharding=one_chip)
    txt = _compile_text(jax.grad(lambda p, x: jnp.sum(moe.moe_mlp(p, cfg, x)[0]), argnums=1),
                        params, x)
    assert "ragged-dot" in txt and "tpu_custom_call" in txt


def test_moonlight_mla_flash_attention(one_chip):
    """MLA's causal attention (q/k head 192, v head 128, 16 heads, 4,096
    positions) through the Pallas flash-attention kernel, forward and
    backward."""
    from repro.models import layers

    q = jax.ShapeDtypeStruct((1, 4096, 16, 192), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.float32, sharding=one_chip)
    txt = _compile_text(jax.grad(lambda q, k, v: jnp.sum(layers._tpu_flash_causal(q, k, v)),
                                 argnums=(0, 1, 2)), q, q, v)
    assert txt.count("tpu_custom_call") >= 3


def test_held_experts_row_ladder_keeps_its_buffers_small(one_chip):
    """The input gradient of one held MoE layer (smoke widths, 4,096
    tokens, 2 experts a token) holding 1 of 8 experts, whose row ladder
    has three rungs, needs at most half the temporaries of the layer that
    holds all 8 at the same T and k: the switch between rungs saves only
    its inputs, and none of the larger rungs' buffers."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import moe

    def temp_bytes(held):
        cfg = get_arch("moonlight-16b-a3b", smoke=True)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, experts_held=held))
        assert len(moe.row_ladder(4096, cfg.moe.top_k, held, cfg.moe.n_experts)) == (
            3 if held == 1 else 1)
        params = jax.eval_shape(lambda k: moe.init_moe(k, cfg, jnp.float32),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                             sharding=one_chip), params)
        x = jax.ShapeDtypeStruct((1, 4096, cfg.d_model), jnp.float32, sharding=one_chip)
        grad = jax.grad(lambda x, p: jnp.sum(moe.moe_mlp(p, cfg, x)[0] ** 2))
        compiled = jax.jit(grad).lower(x, params).compile()
        assert "ragged-dot" in compiled.as_text()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temp_bytes(1) <= temp_bytes(8) / 2
