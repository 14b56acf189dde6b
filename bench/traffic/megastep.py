"""Traffic generator of the compiled FedBuff megastep: ``AsyncStreamServer.serve_compiled``
with a mix's concurrency, latency law, buffer and aggregation.

One step is one call of ``chunk`` flushes, the host-visible unit of the
compiled loop: the call returns after the device has finished the chunk.
Set-up builds the server and drives it through its first step (which
compiles); the program's readings of that step are what the reference
is compared with, and the window continues the same stream.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import inputs, reference
from bench.harness import info


class Generator:
    def __init__(self, config: dict, mix: dict, seed: int, spans, clock):
        self.config, self.mix, self.seed = config, mix, seed
        self.spans, self.clock = spans, clock
        self.k = mix["buffer"]
        self.flushes_per_step = mix["chunk"]

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.api import (AggregationSpec, AsyncRegime, DataSpec, ExperimentSpec,
                               ModelSpec, TrustSpec, lowering)
        from repro.data.pipeline import FederatedData
        from repro.models import cnn
        from repro.stream.server import AsyncStreamServer

        m, cfg = self.mix, self.config
        n_clients = m["clients"]
        x, y = inputs.image_set(cfg, m["data_seed"])
        parts, topped = inputs.dirichlet_partition(y, n_clients, m["beta"], m["data_seed"])
        self.x, self.y, self.parts = x, y, parts
        self.params0 = inputs.init_params(cfg, self.seed)
        self.table = inputs.latency_table(m["latency_mu"], m["latency_sigma"],
                                          inputs.subseed(self.seed, 2))
        info(stage="inputs", clients=n_clients, workers_topped_up=topped,
             train_images=int(x.shape[0]))

        spec = ExperimentSpec(
            data=DataSpec(dataset=cfg["dataset"], n_workers=n_clients, beta=m["beta"]),
            model=ModelSpec(cfg["model"]),
            aggregation=AggregationSpec(algorithm="drag", alpha=m["alpha"], c=m["c"]),
            trust=TrustSpec(enabled=True),
            regime=AsyncRegime(concurrency=m["concurrency"], buffer_capacity=self.k,
                               local_steps=m["local_steps"], batch_size=m["batch"],
                               lr=m["lr"], discount="poly", discount_a=m["discount_a"],
                               compiled=True, compiled_chunk=m["chunk"],
                               eval_every=m["chunk"]),
            seed=m["event_seed"])
        apply_fn = cnn.MODELS[cfg["model"]][1]
        data = FederatedData(x=x, y=y, parts=parts, test=(None, None),
                             n_classes=cfg["n_classes"], malicious=np.zeros(n_clients, bool))
        self.server = AsyncStreamServer(
            lambda p, b: cnn.classification_loss(apply_fn, p, b), self.params0,
            lowering.stream_config(spec), n_clients=n_clients)
        self._serve_kw = dict(
            data=data, seed=m["event_seed"], key=jax.random.PRNGKey(inputs.subseed(self.seed, 3)),
            concurrency=m["concurrency"], local_steps=m["local_steps"],
            batch_size=m["batch"], latency=inputs.TableLatency(self.table),
            **lowering.megastep_params(spec))

        # the first step of the stream, read for the check
        c0 = self.clock.seconds
        met = self._serve()
        info(stage="first_step", compile_s=self.clock.seconds - c0)
        self.prog = {"tau_sum": np.rint(met["staleness_mean"] * self.k),
                     "params": jax.tree.map(np.asarray, self.server.state.params)}

    def _serve(self) -> dict:
        return self.server.serve_compiled(self.flushes_per_step * self.k, **self._serve_kw)

    # ---------------------------------------------------------- window
    def step(self) -> int:
        with self.spans.span("bench.chunk"):
            self._serve()
        return self.flushes_per_step * self.k

    def e2e(self, elapsed: float, updates: int) -> dict:
        return {"updates_per_s.train": updates / elapsed}

    def counts(self) -> tuple[int, int]:
        """(updates attempted, updates lost) over the whole run so far:
        every completed job is aggregated unless the buffer dropped it."""
        drops = int(np.sum(np.asarray(self.server.state.buffer.drops)))
        return self.server.t * self.k + drops, drops

    def work(self) -> dict:
        """Work of one step, for the per-layer readers."""
        m = self.mix
        return {"samples": self.flushes_per_step * self.k * m["local_steps"] * m["batch"],
                "k": self.k}

    def free(self) -> None:
        self.server = None
        self._serve_kw = None

    # ---------------------------------------------------------- the check
    def reference(self, dtype=jnp.float32) -> dict:
        """The first step, recomputed by the plain reference in ``dtype``."""
        m, cfg = self.mix, self.config
        k, n = self.k, m["chunk"]
        seqs, cids, disp = reference.event_schedule(
            seed=m["event_seed"], n_clients=m["clients"], concurrency=m["concurrency"],
            k=k, n_flushes=n, table=self.table)
        lmax = max(len(p) for p in self.parts)
        padded = np.zeros((len(self.parts), lmax), np.int64)
        for i, p in enumerate(self.parts):
            padded[i, :len(p)] = p
        idx = reference.batch_indices(seed=m["event_seed"], seqs=seqs.reshape(-1),
                                      cids=cids.reshape(-1), parts_padded=padded,
                                      part_len=[len(p) for p in self.parts],
                                      ub=m["local_steps"] * m["batch"])
        idx = idx.reshape(n, k, m["local_steps"], m["batch"])
        layers = cfg["layers"]

        @jax.jit
        def one(state, snaps, bidx, cid, tau, x, y):
            rows = jax.vmap(lambda p, a, b: reference.local_sgd(p, a, b, m["lr"], layers))(
                snaps, x[bidx], y[bidx])
            rows = [{key: v[i] for key, v in rows.items()} for i in range(k)]
            return reference.drag_flush(state, rows, cid, tau, c=m["c"], alpha=m["alpha"],
                                        discount_a=m["discount_a"])

        x, y = self.x.astype(dtype), jnp.asarray(self.y)
        p0 = {key: jnp.asarray(v, dtype) for key, v in self.params0.items()}
        state = reference.init_state(p0, m["clients"])
        hist = [p0]
        for t in range(n):
            snaps = {key: jnp.stack([hist[v][key] for v in disp[t]]) for key in p0}
            state, _ = one(state, snaps, jnp.asarray(idx[t]), jnp.asarray(cids[t]),
                           jnp.asarray(t - disp[t]), x, y)
            hist.append(state["params"])
        return {"tau_sum": (np.arange(n)[:, None] - disp).sum(axis=1),
                "params": {key: np.asarray(v, np.float32) for key, v in state["params"].items()}}

    def compare(self, prog: dict, ref: dict) -> dict:
        """The numbers compared with the reference, over the first step:
        the staleness of every update, exactly; the worst leaf's gap
        between the norms of its change (``change_gap``) and the norm of
        the difference of the changes (``change_diff``), each against the
        larger of that leaf's and the median leaf's change in the
        reference; and both again against the leaf's own change alone
        (``*_leaf``).  The cell's ``limits`` name the ones judged."""
        dp, dr = {}, {}
        for key, v in self.params0.items():
            v = np.asarray(v, np.float64)
            dp[key] = np.asarray(prog["params"][key], np.float64) - v
            dr[key] = np.asarray(ref["params"][key], np.float64) - v
        nr = {key: float(np.linalg.norm(v)) for key, v in dr.items()}
        gap = {key: abs(float(np.linalg.norm(dp[key])) - nr[key]) for key in dr}
        diff = {key: float(np.linalg.norm(dp[key] - dr[key])) for key in dr}
        floor = float(np.median(list(nr.values())))
        return {
            "tau_sum_gap": float(np.max(np.abs(prog["tau_sum"] - ref["tau_sum"]))),
            "change_gap": max(gap[key] / max(nr[key], floor) for key in dr),
            "change_diff": max(diff[key] / max(nr[key], floor) for key in dr),
            "change_gap_leaf": max(gap[key] / nr[key] for key in dr),
            "change_diff_leaf": max(diff[key] / nr[key] for key in dr),
        }
