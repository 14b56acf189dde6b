"""The output contract of every engine's jitted server step.

The sync round (``make_round_fn``), the single-buffer flush and the
sharded flush at p = 1 (``make_flush_fn``) all end in
``repro.fl.aggregate.server_step``.  Each jitted step's output tree is
pinned here — its metric keys and its leaf count — so that a change to
the shared step that adds or drops an output shows up per engine: every
flush output is a fresh device allocation on each call.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.fl.round import RoundConfig, init_server_state, make_round_fn
from repro.stream.server import StreamConfig, init_stream_state, make_flush_fn

K, M = 4, 6  # rows a step, clients in the trust table

CALIBRATED = {"delta_norm", "dod_max", "dod_mean", "ref_norm", "update_norm_mean"}
TRUST = {"quarantined", "trust_weight_mean"}
STALENESS = {"staleness_mean", "staleness_max", "discount_mean"}

#: (engine, rule, telemetry) -> leaves of the jitted step's whole output
LEAVES = {
    ("sync", "drag"): (23, 46), ("sync", "br_drag"): (24, 47), ("sync", "fedavg"): (14, 37),
    ("single", "drag"): (26, 49), ("single", "br_drag"): (26, 49),
    ("single", "fedavg"): (17, 40),
    ("sharded", "drag"): (26, 49), ("sharded", "br_drag"): (26, 49),
    ("sharded", "fedavg"): (17, 40),
}


def _loss(p, b):
    return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)


def _step_outputs(engine, rule, telemetry):
    """eval_shape of the engine's jitted step; (metrics, whole output)."""
    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    trust = rule != "fedavg"  # trust needs a reference direction
    with_root = rule == "br_drag"
    if engine == "sync":
        cfg = RoundConfig(algorithm=rule, local_steps=2, trust=trust, telemetry=telemetry)
        state = init_server_state(params, M, cfg)
        args = [state, {"x": jnp.ones((K, 2, 3, 4)), "y": jnp.ones((K, 2, 3, 3))},
                jnp.arange(K, dtype=jnp.int32), jnp.zeros((K,), bool), jax.random.PRNGKey(0)]
        if with_root:
            args.append({"x": jnp.ones((2, 3, 4)), "y": jnp.ones((2, 3, 3))})
        out = jax.eval_shape(make_round_fn(_loss, cfg, with_root), *args)
        return out[1], out
    cfg = StreamConfig(algorithm=rule, buffer_capacity=K, trust=trust, telemetry=telemetry,
                       discount="poly", shards=1 if engine == "sharded" else 0)
    st = init_stream_state(params, K, cfg, n_clients=M)
    args = [st.params, st.drag, st.round, st.buffer, jax.random.PRNGKey(0), st.adversary,
            st.trust]
    if with_root:
        args.append(params)  # the precomputed reference r^t
    args.append(st.monitor)
    out = jax.eval_shape(make_flush_fn(_loss, cfg, with_root), *args)
    return out[6], out


@pytest.mark.parametrize("telemetry", [False, True])
@pytest.mark.parametrize("rule", ["drag", "br_drag", "fedavg"])
@pytest.mark.parametrize("engine", ["sync", "single", "sharded"])
def test_step_output_contract(engine, rule, telemetry):
    metrics, out = _step_outputs(engine, rule, telemetry)
    want = {"delta_norm", "update_norm_mean"} if rule == "fedavg" else CALIBRATED | TRUST
    if engine == "sync" and rule == "br_drag":
        want |= {"dod"}  # the round reports BR-DRAG's per-client DoD
    if engine != "sync":
        want |= STALENESS
    if telemetry:
        want |= {"obs"}
    assert set(metrics) == want
    assert len(jax.tree.leaves(out)) == LEAVES[engine, rule][telemetry]


@pytest.mark.parametrize("module", ["repro.fl", "repro.fl.aggregate", "repro.stream",
                                    "repro.adversary"])
def test_engine_module_imports_first(module):
    """The engines and the step import each other's packages
    (``adversary`` -> ``stream`` -> ``fl.aggregate``); each must import
    on its own, as the first module of a fresh interpreter."""
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", f"import {module}"], check=True, env=env,
                   timeout=120)
