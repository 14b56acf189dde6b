"""Traffic generator of the synchronous engine: ``repro.fl.server.SyncExperiment``,
the object ``run_experiment`` loops over, driven one round at a time
(selection, the clients' and the root's training, the attack, the
aggregation), with no evaluation inside the window.

One step is one round: ``round`` and then ``wait`` for the new
parameters.  The configuration is either a CNN of ``repro.models.cnn``
(``model``) or an architecture trained as LoRA adapters on a frozen base
(``arch`` with ``overrides`` and ``adapters``).

The check: the first round of the window is kept, its inputs, the
adapters or parameters before it and after it, its per-client lambda and
the norm of its aggregate.  The plain reference recomputes that round
from the same inputs: each client's and the root's U SGD steps, ALIE on
the malicious rows, BR-DRAG (eq. 15).  The change is compared leaf by
leaf, each leaf's against its own change and again against the larger
of its own and the median leaf's, so a leaf left as it was (an adapter
factor, a bias) reads at least its share of the median leaf's change,
and in full by its own.  For an architecture it also compares the
logits of one client sequence at the start.  The cell's ``limits`` name
the numbers judged.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import inputs
from bench.harness import info


class Generator:
    def __init__(self, config: dict, mix: dict, seed: int, spans, clock):
        self.config, self.mix, self.seed = config, mix, seed
        self.spans, self.clock = spans, clock
        self.k = mix["selected"]
        self.lm = "arch" in config

    # ---------------------------------------------------------- set-up
    def _spec(self):
        from repro.api import (AdapterSpec, AggregationSpec, AttackSpec, DataSpec,
                               ExperimentSpec, ModelSpec, SyncRegime)

        m, cfg = self.mix, self.config
        if self.lm:
            model = ModelSpec(cfg["arch"], smoke=cfg.get("smoke", False),
                              overrides=cfg["overrides"], adapters=AdapterSpec(**cfg["adapters"]))
        else:
            model = ModelSpec(cfg["model"])
        return ExperimentSpec(
            data=DataSpec(dataset=m["dataset"], n_workers=m["clients"], beta=m["beta"],
                          malicious_fraction=m["malicious_fraction"],
                          root_samples=m["root_samples"], seq_len=m.get("seq_len", 0)),
            model=model,
            aggregation=AggregationSpec(algorithm="br_drag", c_br=m["c"]),
            attack=AttackSpec("alie", {"z": m["z"]}),
            regime=SyncRegime(rounds=1 << 30, n_selected=self.k, local_steps=m["local_steps"],
                              batch_size=m["batch"], lr=m["lr"], eval_every=1 << 30),
            seed=inputs.subseed(self.seed, 11))

    def setup(self) -> None:
        from repro.fl.server import SyncExperiment

        self.run = SyncExperiment(self._spec())
        data = self.run.data
        info(stage="inputs", samples=int(data.x.shape[0]), shape=list(data.x.shape[1:]),
             d=int(sum(np.prod(a.shape) for a in jax.tree.leaves(self.run.state.params))))
        self.t, self.mets = 0, []
        self.capture = None
        if self.lm:
            # one client sequence through the program's forward, before any round
            self.seq = np.asarray(data.x[data.parts[0][0]])
            self.adapters0 = jax.device_get(self.run.state.params)
            logits = jax.jit(self.run.model.logits)(self.run.state.params,
                                                    jnp.asarray(self.seq)[None], self.run.frozen)
            self.logits = np.asarray(logits[0])
        for _ in range(self.mix["warm_rounds"]):
            self.step()

    # ---------------------------------------------------------- window
    def step(self) -> int:
        run = self.run
        met = run.round(self.t)
        run.wait()
        if self.t == self.capture:
            self.kept_inputs = run.inputs
            self.after = jax.tree.map(jnp.copy, run.state.params)
        self.mets.append(met)
        self.t += 1
        return self.k

    def window_start(self) -> None:
        self.capture = self.t
        self.before = jax.device_get(self.run.state.params)

    def e2e(self, elapsed: float, updates: int) -> dict:
        return {self.mix["rate"]: updates / elapsed}

    def counts(self) -> tuple[int, int]:
        return self.t * self.k, 0

    def work(self) -> dict:
        """Counts per round, in order: the traced rounds are the last."""
        return self.counts_per_round

    def _count(self) -> dict:
        out = {"k": self.k}
        if self.lm:
            out["tokens"] = [int(m["tokens_trained"]) for m in self.mets]
            out["assignments"] = [int(jnp.sum(m["expert_assignments"])) for m in self.mets]
            out["train_steps_per_round"] = (self.k + 1) * self.mix["local_steps"]
        return out

    def free(self) -> None:
        """Keep the program's readings on the host and let its state go;
        the frozen base stays, as an input of the reference."""
        met = self.mets[self.capture]
        self.counts_per_round = self._count()
        self.prog = {
            "after": jax.device_get(self.after),
            "dod": np.asarray(met["dod"], np.float64),
            "delta_norm": float(met["delta_norm"]),
        }
        if self.lm:
            self.prog["logits"] = self.logits
            self.base = self.run.frozen
        self.run, self.after, self.mets = None, None, []

    # ---------------------------------------------------------- the check
    def reference(self, dtype=jnp.float32) -> dict:
        """The kept round recomputed by the plain reference in ``dtype``,
        one client (or the root) at a time."""
        from bench import reference_lora as rl

        m, inp = self.mix, self.kept_inputs
        cast = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)  # noqa: E731
        before = cast(self.before)
        if self.lm:
            base = cast(self.base)
            cfg = self._ref_config()
            step = jax.jit(lambda p, b_, x, y: rl.sgd_step(p, b_, x, y, m["lr"], cfg))
            grads = lambda x, y: rl.local_sgd(  # noqa: E731
                before, base, jnp.asarray(x), jnp.asarray(y), m["lr"], cfg,
                step=lambda p, b_, x_, y_, *_: step(p, b_, x_, y_))
        else:
            from bench import reference as rc

            layers = self.config["layers"]
            train = jax.jit(lambda p, x, y: rc.local_sgd(p, x, y, m["lr"], layers))
            grads = lambda x, y: train(before, jnp.asarray(x, dtype), jnp.asarray(y))  # noqa: E731
        b = inp["batches"]
        rows = [grads(b["x"][i], b["y"][i]) for i in range(self.k)]
        root = grads(inp["root"]["x"], inp["root"]["y"])
        after, delta, lams = jax.jit(
            lambda p, rows, r: rl.sync_round(p, rows, r, inp["malicious"], z=m["z"], c=m["c"])
        )(before, rows, root)
        out = {"after": jax.device_get(after), "dod": np.asarray(lams, np.float64),
               "delta_norm": float(jnp.sqrt(rl._vdot(delta, delta)))}
        if self.lm:
            fwd = jax.jit(lambda p, b_, t: rl.forward(b_, p, t, cfg))
            out["logits"] = np.asarray(fwd(cast(self.adapters0), base, jnp.asarray(self.seq)),
                                       np.float32)
        return out

    def _ref_config(self) -> dict:
        """The reference's numbers, read from the configuration file."""
        c, ad = self.config, self.config["adapters"]
        return dict(d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
                    kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
                    qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
                    rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
                    top_k=c["num_experts_per_tok"], routed_scaling=c["routed_scaling_factor"],
                    expert_offset=c["expert_offset"], lora_scale=ad["alpha"] / ad["rank"])

    def compare(self, prog: dict, ref: dict) -> dict:
        """The numbers compared with the reference.  Per leaf of the
        adapters (parameters): the gap between the norms of its change
        (``change_gap``) and the norm of the difference of the changes
        (``change_diff``), each over that leaf's change in the reference;
        the worst leaf's is reported, and again with the median leaf's
        change as a floor under the divisor (``*_floor``, as the megastep
        cell's ``change_gap``).  Over the whole round: the relative L2 of
        the change (``params_gap``), each client's lambda (``dod_gap``),
        the aggregate's norm (``delta_norm_gap``); and the logits of one
        sequence before it (``logits_gap``)."""
        names = ["/".join(str(getattr(k, "key", k)) for k in path)
                 for path, _ in jax.tree_util.tree_flatten_with_path(self.before)[0]]
        before = [np.asarray(a, np.float64) for a in jax.tree.leaves(self.before)]
        dp = [np.asarray(a, np.float64) - b for a, b in zip(jax.tree.leaves(prog["after"]), before)]
        dr = [np.asarray(a, np.float64) - b for a, b in zip(jax.tree.leaves(ref["after"]), before)]
        nr = np.array([np.linalg.norm(v) for v in dr])
        gap = np.array([abs(np.linalg.norm(p) - n) for p, n in zip(dp, nr)])
        diff = np.array([np.linalg.norm(p - r) for p, r in zip(dp, dr)])
        floor = np.maximum(nr, np.median(nr))
        with np.errstate(divide="ignore", invalid="ignore"):
            diff_leaf = np.where(diff == 0, 0.0, diff / nr)
            gap_leaf = np.where(gap == 0, 0.0, gap / nr)
        flat_p, flat_r = np.concatenate([v.ravel() for v in dp]), np.concatenate(
            [v.ravel() for v in dr])
        out = {
            "change_gap": float(np.max(gap_leaf)),
            "change_diff": float(np.max(diff_leaf)),
            "change_gap_floor": float(np.max(gap / floor)),
            "change_diff_floor": float(np.max(diff / floor)),
            "worst_leaf": names[int(np.argmax(diff_leaf))],
            "params_gap": float(np.linalg.norm(flat_p - flat_r) / np.linalg.norm(flat_r)),
            "dod_gap": float(np.max(np.abs(prog["dod"] - ref["dod"]))),
            "delta_norm_gap": abs(prog["delta_norm"] - ref["delta_norm"]) / ref["delta_norm"],
            "malicious_rows": int(np.sum(self.kept_inputs["malicious"])),
        }
        if self.lm:
            lp = np.asarray(prog["logits"], np.float64)
            lr = np.asarray(ref["logits"], np.float64)
            out["logits_gap"] = float(np.linalg.norm(lp - lr) / np.linalg.norm(lr))
        return out
