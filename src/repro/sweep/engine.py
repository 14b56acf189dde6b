"""The vectorized sweep engine: batched spec execution.

``run_sweep`` takes a list of :class:`~repro.api.spec.ExperimentSpec`s
and executes them in three moves:

  1. validate each DISTINCT spec once (specs are hashable — a grid that
     repeats cells pays for validation once per cell shape, not per
     cell);
  2. partition into groups that lower to the same jaxpr shape
     (:mod:`repro.sweep.grouping`) and run each batched group as ONE
     compiled program vmapped over the group axis, replaying the exact
     per-member host RNG contract of ``repro.fl.server.run_experiment``
     (same ``np.random.RandomState``/``PRNGKey`` streams, same split
     order) so a group member's history is interchangeable with its
     sequential run — ``tests/test_sweep.py`` pins bit-for-bit;
  3. reuse compiled executables across sweeps through the group-keyed
     :class:`~repro.sweep.cache.ExecutableCache`, with hit/miss counters
     in the returned provenance and a ``sweep_group`` trace span per
     group (cache=hit|miss) on the obs telemetry plane.

Async/sharded/scenario/telemetry cells fall back to sequential
execution (their event-driven host loops have no group axis), so a
mixed grid still runs end to end through one call.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import lowering
from repro.api.validation import ensure_executable, validate
from repro.data.pipeline import build_federated_data, drift_labels
from repro.fl.round import federated_round, init_server_state
from repro.models import factory
from repro.obs import trace as obs_trace
from repro.sweep import cache as cache_mod
from repro.sweep import grouping


class SyncGroupExecutable:
    """One batched sync program: jit(vmap(federated_round)) + vmapped eval.

    Built from a group's representative spec (the statics — every member
    shares them by construction of the group key); ``run`` then executes
    any member list of the same group.  The jitted callables live for
    the executable's lifetime, so a cache hit re-enters XLA's warm
    compile cache."""

    def __init__(self, spec):
        self.cfg = lowering.round_config(spec)
        self.with_root = self.cfg.algorithm in ("br_drag", "fltrust")
        self.model = factory.build(spec.model)
        if self.model.kind != "cnn":
            raise ValueError(f"sweep groups batch CNN clients; {spec.model.name!r} "
                             "runs through repro.fl.run_experiment")
        loss_fn = self.model.loss

        cfg = self.cfg
        if self.with_root:
            self.round_fn = jax.jit(jax.vmap(
                lambda st, b, s, m, k, r: federated_round(
                    loss_fn, st, cfg, b, s, m, k, root_batches=r
                )
            ))
        else:
            self.round_fn = jax.jit(jax.vmap(
                lambda st, b, s, m, k: federated_round(loss_fn, st, cfg, b, s, m, k)
            ))
        self.eval_fn = jax.jit(jax.vmap(self.model.accuracy))

    # ------------------------------------------------------------- members
    def _prime_member(self, spec, cfg):
        """Replays run_experiment's host setup EXACTLY: RandomState(seed),
        PRNGKey(seed), one split for the init key, data build, model
        init, server-state init."""
        rng = np.random.RandomState(spec.seed)
        key = jax.random.PRNGKey(spec.seed)
        d = spec.data
        data = build_federated_data(
            d.dataset, d.n_workers, d.beta,
            malicious_fraction=d.malicious_fraction, attack=spec.attack.name,
            seed=spec.seed,
        )
        key, k_init = jax.random.split(key)
        _, params = self.model.init(k_init, data)
        state = init_server_state(params, d.n_workers, cfg)
        return {"spec": spec, "rng": rng, "key": key, "data": data, "state": state}

    def run(self, specs) -> "list[dict]":
        """Executes the member specs as one vmapped trajectory; returns
        per-member history dicts schema-compatible with
        ``run_experiment`` (``wall_s`` is the GROUP's wall clock — the
        members share every device step)."""
        spec0 = specs[0]
        d0, regime = spec0.data, spec0.regime
        cfg = self.cfg
        g_n = len(specs)
        members = [self._prime_member(s, cfg) for s in specs]

        states = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[m["state"] for m in members]
        )
        drift_on = d0.drift != "none" and d0.drift_rate > 0.0
        test_np = [m["data"].test_batch() for m in members]
        test_x = jnp.stack([jnp.asarray(t["x"]) for t in test_np])
        test_y0 = np.stack([t["y"].astype(np.int32) for t in test_np])
        test_batch = {"x": test_x, "y": jnp.asarray(test_y0)}

        histories = [
            {"round": [], "accuracy": [], "update_norm": [], "wall_s": []}
            for _ in specs
        ]
        t0 = time.time()
        for t in range(regime.rounds):
            sel, xs, ys, masks, keys, roots = [], [], [], [], [], []
            for m in members:
                rng, data = m["rng"], m["data"]
                selected = rng.choice(
                    d0.n_workers, size=regime.n_selected, replace=False
                )
                batch_np = data.sample_round(
                    rng, selected, regime.local_steps, regime.batch_size
                )
                y_np = batch_np["y"]
                if drift_on:
                    y_np = drift_labels(
                        y_np, data.n_classes, t, d0.drift, d0.drift_rate
                    )
                m["key"], k_round = jax.random.split(m["key"])
                sel.append(selected)
                xs.append(batch_np["x"])
                ys.append(y_np)
                masks.append(data.malicious[selected])
                keys.append(k_round)
                if self.with_root:
                    root_np = data.root_batches(
                        rng, regime.local_steps, regime.batch_size,
                        m["spec"].data.root_samples,
                    )
                    root_y = root_np["y"]
                    if drift_on:
                        root_y = drift_labels(
                            root_y, data.n_classes, t, d0.drift, d0.drift_rate
                        )
                    roots.append({"x": root_np["x"], "y": root_y.astype(np.int32)})
            batches = {
                "x": jnp.asarray(np.stack(xs)),
                "y": jnp.asarray(np.stack(ys).astype(np.int32)),
            }
            args = [
                states, batches,
                jnp.asarray(np.stack(sel), jnp.int32),
                jnp.asarray(np.stack(masks)),
                jnp.stack(keys),
            ]
            if self.with_root:
                args.append({
                    "x": jnp.asarray(np.stack([r["x"] for r in roots])),
                    "y": jnp.asarray(np.stack([r["y"] for r in roots])),
                })
            states, metrics = self.round_fn(*args)

            if (t + 1) % regime.eval_every == 0 or t == regime.rounds - 1:
                tbatch = test_batch
                if drift_on:
                    tbatch = {
                        "x": test_x,
                        "y": jnp.asarray(drift_labels(
                            test_y0, members[0]["data"].n_classes, t,
                            d0.drift, d0.drift_rate,
                        )),
                    }
                accs = np.asarray(self.eval_fn(states.params, tbatch))
                norms = np.asarray(metrics["update_norm_mean"])
                wall = time.time() - t0
                for i, h in enumerate(histories):
                    h["round"].append(t + 1)
                    h["accuracy"].append(float(accs[i]))
                    h["update_norm"].append(float(norms[i]))
                    h["wall_s"].append(wall)
        for h in histories:
            h["final_accuracy"] = h["accuracy"][-1] if h["accuracy"] else 0.0
        return histories


def _build_executable(group: grouping.SpecGroup) -> SyncGroupExecutable:
    return SyncGroupExecutable(group.specs[0])


@dataclasses.dataclass
class SweepResult:
    """Per-spec histories (input order) + the sweep's provenance record."""

    histories: list
    provenance: dict

    def __iter__(self):
        return iter(self.histories)

    def __getitem__(self, i):
        return self.histories[i]

    def __len__(self):
        return len(self.histories)


def run_sweep(specs, *, cache=None, mesh=None, check=True) -> SweepResult:
    """Executes a grid of specs: grouped + vmapped where the statics
    allow, sequential otherwise, with compiled-executable reuse.

    ``cache=None`` uses the process-wide default
    (:func:`repro.sweep.cache.default_cache`); pass a fresh
    :class:`~repro.sweep.cache.ExecutableCache` for isolated counters.
    ``check=False`` skips validation (already-validated grids).
    """
    specs = list(specs)
    cache = cache_mod.default_cache() if cache is None else cache
    if check:
        for spec in set(specs):
            validate(spec, mesh=mesh)
            ensure_executable(spec)

    groups = grouping.group_specs(specs)
    histories: list = [None] * len(specs)
    hits0, misses0 = cache.hits, cache.misses
    group_records = []
    t_sweep = time.time()
    for group in groups:
        tg = time.time()
        if group.batched:
            had = cache.hits
            exe = cache.get_or_build(group.key, lambda: _build_executable(group))
            verdict = "hit" if cache.hits > had else "miss"
            with obs_trace.span(
                "sweep_group", size=len(group.specs), cache=verdict,
                algorithm=exe.cfg.algorithm,
            ):
                for idx, hist in zip(group.indices, exe.run(group.specs)):
                    histories[idx] = hist
        else:
            verdict = "ungrouped"
            spec = group.specs[0]
            with obs_trace.span("sweep_cell", kind=spec.regime.kind):
                if spec.regime.kind == "sync":
                    from repro.fl.server import run_experiment

                    histories[group.indices[0]] = run_experiment(spec, check=False)
                else:
                    from repro.stream.server import run_stream_experiment

                    histories[group.indices[0]] = run_stream_experiment(
                        spec, mesh=mesh, check=False
                    )
        group_records.append({
            "size": len(group.specs),
            "batched": group.batched,
            "cache": verdict,
            "wall_s": time.time() - tg,
        })

    provenance = {
        "cells": len(specs),
        "groups": len(groups),
        "batched_cells": sum(r["size"] for r in group_records if r["batched"]),
        "sequential_cells": sum(
            r["size"] for r in group_records if not r["batched"]
        ),
        "cache_hits": cache.hits - hits0,
        "cache_misses": cache.misses - misses0,
        "group_records": group_records,
        "wall_s": time.time() - t_sweep,
        **cache.counters(),
    }
    return SweepResult(histories=histories, provenance=provenance)
