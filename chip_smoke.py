#!/usr/bin/env python3
"""Chip smoke run: the main path once, on a TPU, at the paper's CIFAR-10 width.

    python3 chip_smoke.py                    # phases a-d, one chip
    python3 chip_smoke.py --four-chips       # phases a and e, four chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 chip_smoke.py --four-chips --cpu-rehearsal

Phases, in order:
  a  device gate: JAX must report a TPU (``--cpu-rehearsal`` skips it);
  b  the flush kernels on a seeded [10, 579402] stack (the CIFAR-10 CNN's
     update width) and ``fused_flush`` at the VMEM edge, each lowered to a
     Mosaic kernel and checked against the float32 ``kernels/ref.py``;
  c  sync DRAG, and BR-DRAG under sign flipping, through ``repro.api``;
  d  the compiled async megastep with trust, and a short block=1 replay
     against ``stream.megastep.serve_unrolled``;
  e  (``--four-chips`` only) the sharded update plane on 4 pods against
     1 pod: parity, one all-reduce per flush, slots on 4 devices.

Each phase prints one line with its compile and run seconds and its
checks.  Any failed check raises, so the exit code is non-zero and the
JSON line that ends a good run is never printed.  One process drives the
chip and starts no other.  ``--cpu-rehearsal`` shrinks the model and the
stack to run on the CPU with the kernels in interpret mode; it is a
correctness rehearsal, never a source of timings.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

TOL = 1e-5  # kernel vs float32 reference, and replay/parity differences

#: (full, rehearsal) sizes; the full ones are the paper's CIFAR-10 setup
SIZES = {
    False: dict(d=579402, fused=(8, 131072), dataset="cifar10", model=None,
                workers=40, selected=10, local_steps=5, batch=10, root=3000,
                rounds=3, flushes=24, concurrency=16, replay=3),
    True: dict(d=3000, fused=(8, 1024), dataset="emnist", model="mlp",
               workers=12, selected=4, local_steps=2, batch=4, root=64,
               rounds=2, flushes=4, concurrency=6, replay=2),
}

class CompileClock:
    """Sums JAX's backend (XLA + Mosaic) compile durations.  Tracing and
    lowering nest inside one another, so they stay in run_s rather than
    being counted twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


class Phase:
    """Times one phase; on exit prints ``phase <name> compile_s run_s checks``."""

    clock: CompileClock  # set once in main(), before any compile

    def __init__(self, name):
        self.name, self.checks = name, {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, exc_type, *_):
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        checks = " ".join(f"{k}={v}" for k, v in self.checks.items())
        status = "" if exc_type is None else " FAILED"
        print(f"phase {self.name}{status} compile_s={comp:.1f} run_s={wall - comp:.1f} "
              f"{checks}", flush=True)

    def check(self, name, value, ok):
        self.checks[name] = value
        if not ok:
            raise AssertionError(f"phase {self.name}: {name}={value}")


def rel_err(x, ref) -> float:
    """max |x - ref| over max |ref|: error at the scale of the result."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def max_diff(a, b) -> float:
    """Largest difference over two pytrees, relative where a value exceeds 1."""
    return max(
        float(np.max(np.abs(x - y)) / max(1.0, np.max(np.abs(x))))
        for x, y in ((np.asarray(x, np.float64), np.asarray(y, np.float64))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def all_finite(tree) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(x)))) for x in jax.tree.leaves(tree))


# ------------------------------------------------------------ phase a
def device_gate(rehearsal: bool, chips: int):
    dev = jax.devices()[0]
    if not rehearsal and dev.platform != "tpu":
        print(f"device gate: found platform {dev.platform!r}, need 'tpu'", file=sys.stderr)
        raise SystemExit(2)
    if len(jax.devices()) < chips:
        print(f"device gate: found {len(jax.devices())} devices, need {chips}",
              file=sys.stderr)
        raise SystemExit(2)
    from repro.kernels import ops

    with Phase("a_device") as ph:
        ph.checks.update(platform=dev.platform, kind=repr(dev.device_kind),
                         count=len(jax.devices()))
        ph.check("interpret", ops._interpret_default(),
                 rehearsal or not ops._interpret_default())


# ------------------------------------------------------------ phase b
def _run_op(ph, name, fn, ref_fn, args, mosaic: bool):
    """Lower, compile and run ``fn``; check Mosaic and the reference."""
    lowered = jax.jit(fn).lower(*args)
    if mosaic:
        ph.check(f"{name}.mosaic", True, "tpu_custom_call" in lowered.as_text())
    out = jax.block_until_ready(lowered.compile()(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref_fn)(*args))
    errs = [rel_err(o, w) for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want))]
    ph.check(f"{name}.err", f"{max(errs):.2e}", max(errs) <= TOL)


def phase_kernels(size, seed: int, mosaic: bool):
    from repro.kernels import ops, ref

    def stack(s, d, key):
        kg, kr, kw, kp = jax.random.split(key, 4)
        r = jax.random.normal(kr, (d,), jnp.float32)
        # rows from aligned to flipped, so the DoD spans its whole range
        g = (jax.random.normal(kg, (s, d), jnp.float32)
             + jnp.linspace(-1.0, 1.0, s)[:, None] * r)
        w = ops.normalize_weights(jax.random.uniform(kw, (s,)), s)
        phi = jax.random.uniform(kp, (s,), minval=0.3, maxval=1.0)
        return g, r, w, phi

    def flush(mode, c):
        def fn(g, r, w, phi):
            delta, lam, (dots, gsq, rsq) = ops.calibrated_reduce(
                g, r, c, mode, w=w, discounts=phi)
            return delta, lam, dots, gsq, rsq

        def want(g, r, w, phi):
            dots, gsq, rsq = ref.dot_norms_ref(g, r)
            a, b, lam = ref.calibrate_coeffs(dots, gsq, rsq, c, mode, phi)
            return ref.blend_reduce_ref(g, r, w * a, w * b), lam, dots, gsq, rsq
        return fn, want

    def geomed_ref(g):
        z = jnp.mean(g, axis=0)
        for _ in range(8):
            z = ref.weiszfeld_step_ref(g, z)
        return z

    with Phase("b_kernels") as ph:
        args = stack(10, size["d"], jax.random.PRNGKey(seed))
        ph.checks["shape"] = f"10x{size['d']}:{ops.flush_path(10, size['d'])}"
        for mode, c in (("drag", 0.25), ("br_drag", 0.5)):
            _run_op(ph, mode, *flush(mode, c), args, mosaic)
        g = args[0]
        _run_op(ph, "trimmed_mean", lambda g: ops.trimmed_mean(g, 2),
                lambda g: ref.trimmed_mean_ref(g, 2), (g,), mosaic)
        _run_op(ph, "pairwise", ops.pairwise_sq_dists, ref.pairwise_sq_dists_ref,
                (g,), mosaic)
        _run_op(ph, "geomed", lambda g: ops.geometric_median(g, iters=8),
                geomed_ref, (g,), mosaic)
        s, d = size["fused"]
        ph.check("fused.path", ops.flush_path(s, d), ops.flush_path(s, d) == "fused")
        _run_op(ph, "fused", *flush("drag", 0.25),
                stack(s, d, jax.random.PRNGKey(seed + 1)), mosaic)


# ------------------------------------------------------------ phase c
def _spec(size, seed, **kw):
    from train_fl_cifar import build_spec

    from repro.api import ModelSpec

    spec = build_spec(
        dataset=size["dataset"], workers=size["workers"],
        selected=size["selected"], local_steps=size["local_steps"],
        batch_size=size["batch"], beta=0.1, alpha=0.25, c=0.25, c_br=0.5,
        seed=seed, **kw)
    spec = dataclasses.replace(
        spec, data=dataclasses.replace(spec.data, root_samples=size["root"]))
    if size["model"]:
        spec = dataclasses.replace(spec, model=ModelSpec(size["model"]))
    return spec


def _eval_loss(spec, params, data):
    from repro.models import cnn

    apply_fn = cnn.MODELS[spec.model.name][1]
    tb = data.test_batch()
    batch = {"x": jnp.asarray(tb["x"]), "y": jnp.asarray(tb["y"])}
    return float(jax.jit(lambda p: cnn.classification_loss(apply_fn, p, batch))(params))


def _data(spec):
    from repro.data.pipeline import build_federated_data

    d = spec.data
    return build_federated_data(d.dataset, d.n_workers, d.beta,
                                malicious_fraction=d.malicious_fraction,
                                attack=spec.attack.name, seed=spec.seed)


def phase_sync(size, seed: int):
    from repro.api import compile

    for name, kw in (("c_sync_drag", dict(algorithm="drag")),
                     ("c_sync_br_drag", dict(algorithm="br_drag",
                                             attack="sign_flipping", malicious=0.3))):
        spec = _spec(size, seed, rounds=size["rounds"], eval_every=1, **kw)
        data = _data(spec)
        steps = []
        with Phase(name) as ph:
            hist = compile(spec).run(data=data, progress=steps.append)
            ph.check("rounds", len(steps), len(steps) == size["rounds"])
            shifts = [m["delta_norm"] for m in steps]
            ph.check("delta_norm", f"{min(shifts):.3e}",
                     all(np.isfinite(shifts)) and min(shifts) > 0.0)
            ph.check("params_finite", all_finite(hist["params"]),
                     all_finite(hist["params"]))
            loss = _eval_loss(spec, hist["params"], data)
            ph.check("loss", f"{loss:.4f}", np.isfinite(loss))
            acc = hist.get("final_accuracy")
            ph.check("final_accuracy", acc, acc is not None and np.isfinite(acc))


# ------------------------------------------------------------ phase d
def _async_spec(size, seed, regime_cls=None, **regime_kw):
    from repro.api import AsyncRegime, TrustSpec

    base = _spec(size, seed, algorithm="drag")
    regime = (regime_cls or AsyncRegime)(
        flushes=size["flushes"], concurrency=size["concurrency"],
        buffer_capacity=size["selected"], latency="exponential",
        local_steps=size["local_steps"], batch_size=size["batch"], lr=0.01,
        discount="poly", eval_every=size["flushes"], **regime_kw)
    return dataclasses.replace(base, regime=regime, trust=TrustSpec(enabled=True))


def phase_megastep(size, seed: int):
    from repro.api import compile, lowering
    from repro.models import cnn
    from repro.stream import megastep
    from repro.stream.events import make_latency
    from repro.stream.server import AsyncStreamServer

    spec = _async_spec(size, seed, compiled=True)
    data = _data(spec)
    with Phase("d_megastep") as ph:
        hist = compile(spec).run(data=data)
        k = spec.regime.buffer_capacity
        ph.check("flushes", hist["flush"][-1], hist["flush"][-1] == size["flushes"])
        ph.check("updates_total", hist["updates_total"],
                 hist["updates_total"] == size["flushes"] * k)
        ph.check("params_finite", all_finite(hist["params"]), all_finite(hist["params"]))

    # block=1 replay: the compiled loop against its per-event host oracle
    with Phase("d_replay") as ph:
        init_fn, apply_fn = cnn.MODELS[spec.model.name]
        params = (init_fn(jax.random.PRNGKey(seed), int(np.prod(data.x.shape[1:])),
                          64, data.n_classes)
                  if spec.model.name == "mlp" else init_fn(jax.random.PRNGKey(seed)))
        cfg = lowering.stream_config(spec)
        loss_fn = lambda p, b: cnn.classification_loss(apply_fn, p, b)  # noqa: E731
        kw = dict(seed=seed, key=jax.random.PRNGKey(seed + 1),
                  concurrency=size["concurrency"], local_steps=size["local_steps"],
                  batch_size=size["batch"], latency=make_latency("exponential"),
                  root_samples=size["root"])
        n = size["replay"]
        server_a = AsyncStreamServer(loss_fn, params, cfg, n_clients=size["workers"])
        mets_a, _ = megastep.serve_unrolled(
            server_a, data, n_flushes=n, rng=np.random.RandomState(seed), **kw)
        server_b = AsyncStreamServer(loss_fn, params, cfg, n_clients=size["workers"])
        mets_b = megastep.CompiledStream(
            server_b, data, block=1, chunk=n, rng=np.random.RandomState(seed), **kw
        ).serve_flushes(n)
        diff = max_diff(
            (server_a.state.params, server_a.state.trust, mets_a),
            (server_b.state.params, server_b.state.trust,
             [{name: mets_b[name][i] for name in m} for i, m in enumerate(mets_a)]))
        ph.checks["bitwise"] = diff == 0.0
        ph.check("max_diff", f"{diff:.3e}", diff <= TOL)


# ------------------------------------------------------------ phase e
def phase_four_chips(size, seed: int):
    from repro.api import ShardedRegime, compile
    from repro.launch.mesh import make_pod_mesh
    from repro.stream import sharded

    mesh = make_pod_mesh(4)
    small = dict(size, selected=8, concurrency=8, flushes=3)
    with Phase("e_sharded") as ph:
        spec4 = _async_spec(small, seed, ShardedRegime, shards=4, emulate=False)
        spec1 = _async_spec(small, seed, ShardedRegime, shards=1, emulate=True)
        data = _data(spec4)
        h4 = compile(spec4, mesh=mesh).run(data=data)
        h1 = compile(spec1).run(data=data)
        ph.check("slot_devices", h4["slot_devices"], h4["slot_devices"] == 4)
        diff = max_diff(h4["params"], h1["params"])
        ph.check("p4_vs_p1", f"{diff:.3e}", diff <= TOL)

        kp, d = 2, sum(x.size for x in jax.tree.leaves(h4["params"]))
        slots_sh, meta_sh = sharded.buffer_layout(mesh)
        args = (jax.ShapeDtypeStruct((4, kp, d), jnp.float32, sharding=slots_sh),
                jax.ShapeDtypeStruct((d,), jnp.float32, sharding=meta_sh),
                jax.ShapeDtypeStruct((4, kp), jnp.float32, sharding=meta_sh),
                jax.ShapeDtypeStruct((4 * kp,), jnp.float32, sharding=meta_sh))
        hlo = jax.jit(lambda s, r, disc, w: sharded.hierarchical_flush(
            s, r, mode="drag", c=0.25, discounts2=disc, weights=w, init=True,
            mesh=mesh)).lower(*args).compile().as_text()
        n_ar = len(re.findall(r"\ball-reduce(?:-start)?\(", hlo))
        ph.check("all_reduce", n_ar, n_ar == 1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-pod phase (needs 4 devices)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="skip the device gate and shrink every size (CPU, interpret mode)")
    args = ap.parse_args()

    enable_compile_cache()
    Phase.clock = CompileClock()
    size = SIZES[args.cpu_rehearsal]
    device_gate(args.cpu_rehearsal, 4 if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips(size, args.seed)
    else:
        phase_kernels(size, args.seed, mosaic=not args.cpu_rehearsal)
        phase_sync(size, args.seed)
        phase_megastep(size, args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
