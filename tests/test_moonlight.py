"""The MLA + MoE architecture (Moonlight-16B-A3B's family) as a
federated LoRA client, against the plain float32 reference
(``repro.models.reference``) at the smoke size on seeded random weights."""
from __future__ import annotations

import dataclasses
import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AdapterSpec, AggregationSpec, AttackSpec, DataSpec, ExperimentSpec,
                       ModelSpec, SyncRegime)
from repro.configs import get_arch
from repro.models import factory, lora
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import reference as ref
from repro.models import transformer as T

ARCH = "moonlight-16b-a3b"
RANK, ALPHA = 4, 8.0


def ref_cfg(cfg, expert_offset=0):
    return dict(d_model=cfg.d_model, n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                top_k=cfg.moe.top_k, routed_scaling=cfg.moe.routed_scaling,
                expert_offset=expert_offset, lora_scale=ALPHA / RANK)


@pytest.fixture(scope="module")
def cfg():
    return get_arch(ARCH, smoke=True)


@pytest.fixture(scope="module")
def base(cfg):
    return jax.jit(lambda k: T.init_params(k, cfg))(jax.random.PRNGKey(7))


def _moe_params(base, i=0):
    return jax.tree.map(lambda a: a[i], base["stack"]["slot0"]["mlp"])


def _x(cfg, n=24, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, cfg.d_model), jnp.float32)


def test_mla_forward_matches_reference(cfg, base):
    p = jax.tree.map(lambda a: a[0], base["dense"]["slot0"]["attn"])
    x = _x(cfg)
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)[None]
    got = L.mla_block(p, T.mla_config(cfg), x[None], pos)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.mla(p, None, x, ref_cfg(cfg))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_held_share_matches_dense_reference(cfg, base):
    """The dropless held dispatch (sort, grouped products) against every
    held expert on every token, masked to each token's top k."""
    p = _moe_params(base)
    # a bias that takes part in the choice only
    p = dict(p, router_bias=jax.random.normal(jax.random.PRNGKey(3), p["router_bias"].shape))
    x = _x(cfg)
    y, _, counts = MOE.moe_mlp(p, cfg, x[None])
    with jax.default_matmul_precision("highest"):
        want = ref.swiglu(p["shared"], None, x, 0.0) + ref.held_experts(p, x, ref_cfg(cfg))
    np.testing.assert_allclose(y[0], want, rtol=2e-5, atol=2e-5)
    assert int(jnp.sum(counts["assignments"])) == x.shape[0] * cfg.moe.top_k


def test_expert_shares_add_up_to_the_uncut_layer(cfg, base):
    """Four devices holding two experts each: their outputs, with the
    shared experts counted once, add up to the uncut reference layer."""
    p = _moe_params(base)
    x = _x(cfg, seed=2)
    n_e, held = cfg.moe.n_experts, 2
    total = jnp.zeros_like(x)
    for off in range(0, n_e, held):
        share_cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, experts_held=held, expert_offset=off))
        share = dict(p, **{k: p[k][off: off + held] for k in ("w_gate", "w_up", "w_down")})
        y, _, _ = MOE.moe_mlp(share, share_cfg, x[None])
        total = total + y[0]
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(p["shared"], None, x, 0.0)
        whole = shared + ref.held_experts(p, x, ref_cfg(cfg))
    np.testing.assert_allclose(total - (n_e // held - 1) * shared, whole, rtol=2e-5, atol=2e-5)


def test_no_token_dropped_when_routing_piles_onto_one_expert(cfg, base):
    """Every token on expert 0: all of them are computed there (a
    capacity dispatch would keep 1.25 x its fair share) and the layer's
    output is the dense reference's, each token with all its experts."""
    p = _moe_params(base)
    bias = jnp.zeros_like(p["router_bias"]).at[0].set(100.0)
    p = dict(p, router_bias=bias)
    x = _x(cfg, n=64, seed=4)
    y, _, counts = MOE.moe_mlp(p, cfg, x[None])
    assert int(counts["assignments"][0]) == x.shape[0]
    with jax.default_matmul_precision("highest"):
        want = ref.swiglu(p["shared"], None, x, 0.0) + ref.held_experts(p, x, ref_cfg(cfg))
    np.testing.assert_allclose(y[0], want, rtol=2e-5, atol=2e-5)


def _held_share(cfg, p, held, offset):
    share_cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, experts_held=held, expert_offset=offset))
    share = dict(p, **{k: p[k][offset: offset + held] for k in ("w_gate", "w_up", "w_down")})
    return share_cfg, share


@pytest.mark.parametrize("held, bias, rows", [
    (2, 0.0, 384),  # the router alone: ~T·k/4 held assignments, the lowest rung
    (2, 0.2, 768),  # a bias that piles most tokens onto the held experts: a middle rung
    (2, 100.0, 1024),  # every token on both held experts: the top rung, T·k
    (4, 0.0, 768),
    (4, 100.0, 1024),
])
def test_held_dispatch_is_exact_on_every_rung(cfg, base, held, bias, rows):
    """The held dispatch in a row buffer of the capacity the routing
    needs: the output and its gradient with respect to x are the dense
    reference's, and every held assignment is counted."""
    offset = held
    share_cfg, share = _held_share(cfg, _moe_params(base), held, offset)
    share["router_bias"] = jnp.zeros_like(share["router_bias"]).at[offset: offset + held].set(bias)
    x = _x(cfg, n=512, seed=11)
    ct = jax.random.normal(jax.random.PRNGKey(12), x.shape)
    rc = ref_cfg(cfg, expert_offset=offset)
    (y, counts), vjp = jax.vjp(lambda x: MOE._dispatch_held(share, share_cfg, x), x)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(lambda x: ref.held_experts(share, x, rc), x)
        routed = ref.route(share, x, rc)[:, offset: offset + held]
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(vjp((ct, jax.tree.map(jnp.zeros_like, counts)))[0],
                                   want_vjp(ct)[0], rtol=2e-5, atol=2e-5)
    assert MOE.row_ladder(x.shape[0], cfg.moe.top_k, held, cfg.moe.n_experts)[-1] == 1024
    assert int(counts["expert_rows"]) == rows
    assert int(jnp.sum(counts["assignments"])) == int(jnp.sum(routed > 0))


def test_every_expert_held_runs_one_rung_without_a_switch(cfg, base):
    """smoke()'s layer holds all its experts: one rung of T·k rows, no
    switch in the program; holding fewer brings the switch in."""
    p = _moe_params(base)
    x = _x(cfg, n=512)[None]
    assert cfg.moe.experts_held == cfg.moe.n_experts
    assert MOE.row_ladder(512, cfg.moe.top_k, cfg.moe.n_experts, cfg.moe.n_experts) == (1024,)
    whole = str(jax.make_jaxpr(lambda x: MOE.moe_mlp(p, cfg, x))(x))
    assert "ragged_dot" in whole and "cond[" not in whole
    share_cfg, share = _held_share(cfg, p, 2, 0)
    assert "cond[" in str(jax.make_jaxpr(lambda x: MOE.moe_mlp(share, share_cfg, x))(x))
    _, _, counts = MOE.moe_mlp(p, cfg, x)
    assert int(counts["expert_rows"]) == 512 * cfg.moe.top_k


def test_fresh_adapters_leave_the_base_as_it_is(cfg, base):
    ad = lora.init_adapters(jax.random.PRNGKey(5), base, lora.TARGETS, RANK)
    assert all(float(jnp.abs(b).max()) == 0.0 for b in jax.tree.leaves(
        jax.tree.map(lambda g: g["b"], ad, is_leaf=lambda g: set(g) == {"a", "b"})))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 0, cfg.vocab)
    plain = T.forward(base, cfg, tokens)[0]
    adapted = T.forward(lora.merge(base, ad, ALPHA / RANK), cfg, tokens)[0]
    np.testing.assert_array_equal(plain, adapted)


def test_model_forward_matches_reference_with_trained_adapters(cfg, base):
    ad = lora.init_adapters(jax.random.PRNGKey(5), base, lora.TARGETS, RANK)
    ad = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(8), a.shape), ad)
    model = factory.build(ModelSpec(ARCH, smoke=True, adapters=AdapterSpec(RANK, ALPHA)))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 0, cfg.vocab)
    got = model.logits(ad, tokens, base)[0]
    want = ref.forward(base, ad, tokens[0], ref_cfg(cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def _spec(seed=7):
    return ExperimentSpec(
        data=DataSpec(dataset="topics", n_workers=8, beta=0.1, malicious_fraction=0.25,
                      root_samples=8, seq_len=16),
        model=ModelSpec(ARCH, smoke=True, adapters=AdapterSpec(RANK, ALPHA)),
        aggregation=AggregationSpec(algorithm="br_drag", c_br=0.5),
        attack=AttackSpec("alie", {"z": 1.5}),
        regime=SyncRegime(rounds=1, n_selected=4, local_steps=2, batch_size=1, lr=0.05,
                          eval_every=1),
        seed=seed)


def test_brdrag_alie_round_matches_reference_round(cfg):
    """The second round, once the ``b`` factors have moved off zero (in
    the first, every ``a`` factor's gradient is zero): each adapter leaf's
    change against the reference round's, by its own norm."""
    from repro.fl.server import SyncExperiment

    run = SyncExperiment(_spec())
    run.round(0)
    before = jax.tree.map(np.asarray, run.state.params)
    metrics = run.round(1)
    run.wait()
    inp = run.inputs
    assert inp["malicious"].any() and not inp["malicious"].all()
    rc = ref_cfg(cfg)
    lr = run.spec.regime.lr
    b = inp["batches"]
    step = jax.jit(lambda p, x, y: ref.sgd_step(p, run.frozen, x, y, lr, rc))

    def train(x, y):
        return ref.local_sgd(before, run.frozen, x, y, lr, rc, step=lambda p, _b, x_, y_, *_:
                             step(p, x_, y_))

    rows = [train(b["x"][i], b["y"][i]) for i in range(len(inp["selected"]))]
    root = train(inp["root"]["x"], inp["root"]["y"])
    want, delta, lams = ref.sync_round(before, rows, root, inp["malicious"], z=1.5, c=0.5)

    for got, w, b0 in zip(jax.tree.leaves(run.state.params), jax.tree.leaves(want),
                          jax.tree.leaves(before)):
        d_got, d_want = np.asarray(got) - b0, np.asarray(w) - b0
        assert np.linalg.norm(d_want) > 0
        assert np.linalg.norm(d_got - d_want) <= 1e-4 * np.linalg.norm(d_want)
    np.testing.assert_allclose(metrics["dod"], lams, atol=1e-5)
    np.testing.assert_allclose(float(metrics["delta_norm"]),
                               float(jnp.sqrt(ref._vdot(delta, delta))), rtol=1e-4)
    s, u = run.spec.regime.n_selected, run.spec.regime.local_steps
    assert int(metrics["tokens_trained"]) == (s + 1) * u * 16
    assert int(metrics["expert_assignments"].sum()) == (s + 1) * u * 16 * cfg.moe.top_k
    # every expert held: each training's layer ran the one rung of T·k rows
    np.testing.assert_array_equal(metrics["expert_rows"], [(s + 1) * u * 16 * cfg.moe.top_k])


def test_runs_through_compile_run():
    from repro.api import compile

    h = compile(_spec()).run()
    assert 0.0 <= h["final_accuracy"] <= 1.0
    assert h["params"]["stack"]["slot0"]["attn"]["wq"]["a"].shape[-1] == RANK


def test_adapter_count_at_the_benchmark_cut():
    """d of the chip's share: rank-16 adapters on the MLA projections of
    five layers, the dense SwiGLU and four shared-expert SwiGLUs."""
    spec = ModelSpec(ARCH, overrides={"n_layers": 5, "vocab": 20480, "moe.experts_held": 8},
                     adapters=AdapterSpec(16, 32.0))
    cfg = factory.arch_config(spec)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    ad = jax.eval_shape(lambda: lora.init_adapters(jax.random.PRNGKey(0), shapes,
                                                   lora.TARGETS, 16))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ad)) == 2_888_704
    assert 568e6 < sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) < 569e6


def test_spec_round_trips_with_adapters_and_overrides():
    spec = _spec()
    spec = dataclasses.replace(spec, model=dataclasses.replace(
        spec.model, overrides={"moe.experts_held": 4}))
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert hash(ExperimentSpec.from_json(spec.to_json())) == hash(spec)


@pytest.mark.parametrize("change, message", [
    (dict(model=ModelSpec(ARCH, smoke=True)), "adapters"),
    (dict(regime=SyncRegime(), data=DataSpec(dataset="cifar10")), "token dataset"),
    (dict(model=ModelSpec(ARCH, smoke=True, adapters=AdapterSpec(4, 8.0, ("ffn",)))), "targets"),
    (dict(model=ModelSpec(ARCH, smoke=True, overrides={"moe.n_expert": 2},
                          adapters=AdapterSpec())), "override"),
])
def test_validation_rejects(change, message):
    from repro.api import SpecError, validate

    with pytest.raises(SpecError, match=message):
        validate(dataclasses.replace(_spec(), **change))


def test_reference_copy_in_the_benchmark_is_the_same_file():
    here = os.path.dirname(__file__)
    assert filecmp.cmp(os.path.join(here, "..", "src", "repro", "models", "reference.py"),
                       os.path.join(here, "..", "bench", "reference_lora.py"), shallow=False)
