"""Traffic generator of the host aggregation server: ``AsyncStreamServer.ingest`` and
``flush_if_ready`` as a server calls them for uploads that arrive off
the wire, under saturating load (the next upload is always ready).

Uploads are host numpy pytrees of the model's parameter shapes, drawn
from a seeded pool of distinct updates: a shared drift direction, a
per-client non-IID offset and noise.  A client always sends its pool
entry; its staleness is drawn per upload.  One step is one flush: K
ingests, the flush, and the wait for the new parameters, the moment a
server can hand the new model version out.
"""
from __future__ import annotations

import time

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

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.api import (AggregationSpec, AsyncRegime, DataSpec, ExperimentSpec,
                               ModelSpec, TrustSpec, lowering)
        from repro.models import cnn
        from repro.stream.server import AsyncStreamServer

        m, cfg = self.mix, self.config
        self.params0 = inputs.init_params(cfg, self.seed)
        self.pool = self._pool()
        rng = np.random.RandomState(inputs.subseed(self.seed, 4))
        n = m["uploads"]
        self.cids = rng.randint(0, m["clients"], size=n)
        # staleness in versions: P(tau = j) proportional to 2^-j, j < 8
        p = 0.5 ** np.arange(8)
        self.taus = rng.choice(8, size=n, p=p / p.sum())
        info(stage="inputs", pool=len(self.pool), uploads=n)

        spec = ExperimentSpec(
            data=DataSpec(dataset=cfg["dataset"], n_workers=m["clients"]),
            model=ModelSpec(cfg["model"]),
            aggregation=AggregationSpec(algorithm="drag", alpha=m["alpha"], c=m["c"]),
            trust=TrustSpec(enabled=True),
            regime=AsyncRegime(buffer_capacity=self.k, discount="poly",
                               discount_a=m["discount_a"]),
            seed=0)
        apply_fn = cnn.MODELS[cfg["model"]][1]
        self.server = AsyncStreamServer(
            lambda p_, b: cnn.classification_loss(apply_fn, p_, b), self.params0,
            lowering.stream_config(spec), n_clients=m["clients"])
        self.key = jax.random.PRNGKey(0)  # read only by attacks; there is none
        self.sent = 0
        self.latencies: list[float] = []
        self.kept: dict[int, object] = {}
        self.mets: list[dict] = []
        for _ in range(m["warm_flushes"]):
            self.step()

    def _pool(self) -> list[dict]:
        """[P] distinct uploads, made on the device in one jitted call and
        brought to the host, where a server decodes them."""
        m = self.mix
        shapes = inputs.param_shapes(self.config)
        names = sorted(shapes)
        n = m["pool"]

        @jax.jit
        def make(key):
            kd, ko, kn, kb, ka, ks = jax.random.split(key, 6)
            # along the drift: b in [-0.4, 1]; off it: a in [0.6, 2]; so the
            # divergence 1 - cos stays below 1.6 and no client is quarantined
            b = jax.random.uniform(kb, (n,), minval=-0.4, maxval=1.0)
            a = jax.random.uniform(ka, (n,), minval=0.6, maxval=2.0)
            s = m["update_scale"] * jax.random.uniform(ks, (n,), minval=0.5, maxval=2.0)
            out = {}
            for i, name in enumerate(names):
                shape = shapes[name]
                drift = jax.random.normal(jax.random.fold_in(kd, i), shape)
                off = jax.random.normal(jax.random.fold_in(ko, i), (n,) + shape)
                noise = jax.random.normal(jax.random.fold_in(kn, i), (n,) + shape)
                bx = b.reshape((n,) + (1,) * len(shape))
                ax = a.reshape((n,) + (1,) * len(shape))
                sx = s.reshape((n,) + (1,) * len(shape))
                out[name] = sx * (bx * drift + ax * off + 0.1 * noise) / np.sqrt(self._d)
            return out

        self._d = sum(int(np.prod(v)) for v in shapes.values())
        stacked = jax.device_get(make(jax.random.PRNGKey(inputs.subseed(self.seed, 5))))
        return [{k: np.ascontiguousarray(v[i]) for k, v in stacked.items()} for i in range(n)]

    # ---------------------------------------------------------- window
    def step(self) -> int:
        srv, n = self.server, len(self.cids)
        for _ in range(self.k):
            i = self.sent % n
            cid = int(self.cids[i])
            with self.spans.span("bench.ingest"):
                srv.ingest(self.pool[cid % len(self.pool)], max(srv.t - int(self.taus[i]), 0),
                           False, cid)
            self.sent += 1
        t0 = time.perf_counter()
        with self.spans.span("bench.flush"):
            met = srv.flush_if_ready(self.key)
            jax.block_until_ready(srv.state.params)
        self.latencies.append(time.perf_counter() - t0)
        self.mets.append(met)
        if srv.t % self.mix["segment"] == 0:
            self.kept[srv.t] = srv.state.params
        return self.k

    def window_start(self) -> None:
        self.latencies = []

    def e2e(self, elapsed: float, updates: int) -> dict:
        import statistics

        lat = self.latencies
        p95 = statistics.quantiles(lat, n=20)[-1] if len(lat) >= 20 else max(lat)
        return {"updates_per_s.server": updates / elapsed, "flush_p95_ms": p95 * 1e3}

    def counts(self) -> tuple[int, int]:
        return self.sent, self.server.dropped

    def work(self) -> dict:
        return {"samples": 0, "k": self.k}

    def free(self) -> None:
        """Keep the program's readings on the host and let its state go."""
        srv = self.server
        self.kept[srv.t] = srv.state.params
        self.prog = {
            "metrics": {key: np.asarray(jnp.stack([mm[key] for mm in self.mets]), np.float64)
                        for key in ("delta_norm", "trust_weight_mean")},
            "params": {t: {k: np.asarray(v) for k, v in p.items()} for t, p in self.kept.items()},
        }
        self.server = None
        self.kept, self.mets = {}, []

    # ---------------------------------------------------------- the check
    def reference(self, dtype=jnp.float32) -> dict:
        """Every flush of the run, recomputed by the plain reference in
        ``dtype`` from the same uploads, in segments of ``segment``
        flushes (the last one padded with flushes that change nothing)."""
        m, k, seg = self.mix, self.k, self.mix["segment"]
        n_flush = len(self.prog["metrics"]["delta_norm"])
        pool = {key: jnp.asarray(np.stack([u[key] for u in self.pool]), dtype)
                for key in self.pool[0]}
        n_seg = (n_flush + seg - 1) // seg
        j = np.arange(n_seg * seg * k) % len(self.cids)
        cids = self.cids[j].reshape(-1, k)
        taus = np.minimum(self.taus[j].reshape(-1, k), np.arange(n_seg * seg)[:, None])
        valid = np.arange(n_seg * seg) < n_flush

        def flush(st, c, tau, pool):
            rows = [{key: pool[key][c[i] % m["pool"]] for key in pool} for i in range(k)]
            st, met = reference.drag_flush(st, rows, c, tau, c=m["c"], alpha=m["alpha"],
                                           discount_a=m["discount_a"])
            return st, (met["delta_norm"], met["trust_weight_mean"])

        @jax.jit
        def run(state, cids, taus, valid, pool):
            def body(st, xs):
                c, tau, ok = xs
                return jax.lax.cond(ok, flush, lambda s, *_: (s, (jnp.zeros((), dtype),) * 2),
                                    st, c, tau, pool)
            return jax.lax.scan(body, state, (cids, taus, valid))

        p0 = {key: jnp.asarray(v, dtype) for key, v in self.params0.items()}
        state = reference.init_state(p0, m["clients"])
        dn, tw, params = [], [], {}
        for s0 in range(0, n_seg * seg, seg):
            sl = slice(s0, s0 + seg)
            state, (a, b) = run(state, jnp.asarray(cids[sl]), jnp.asarray(taus[sl]),
                                jnp.asarray(valid[sl]), pool)
            dn.append(np.asarray(a, np.float64))
            tw.append(np.asarray(b, np.float64))
            ver = min(s0 + seg, n_flush)
            if ver in self.prog["params"]:
                params[ver] = {key: np.asarray(v, np.float32)
                               for key, v in state["params"].items()}
        return {"metrics": {"delta_norm": np.concatenate(dn)[:n_flush],
                            "trust_weight_mean": np.concatenate(tw)[:n_flush]},
                "params": params}

    def compare(self, prog: dict, ref: dict) -> dict:
        pm, rm = prog["metrics"], ref["metrics"]
        p0 = {key: np.asarray(v, np.float64) for key, v in self.params0.items()}
        worst = 0.0
        for ver, pp in prog["params"].items():
            rp = ref["params"][ver]
            for key in p0:
                dp = np.asarray(pp[key], np.float64) - p0[key]
                dr = np.asarray(rp[key], np.float64) - p0[key]
                worst = max(worst, float(np.linalg.norm(dp - dr) / np.linalg.norm(dr)))
        dn_p, dn_r = pm["delta_norm"], rm["delta_norm"]
        return {
            "delta_norm_gap": float(np.max(np.abs(dn_p - dn_r) / dn_r)),
            "trust_weight_gap": float(np.max(np.abs(pm["trust_weight_mean"]
                                                    - rm["trust_weight_mean"]))),
            "params_gap": worst,
        }
