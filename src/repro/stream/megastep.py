"""Device-resident serving megastep: the async event loop as one lax.scan.

The legacy serving loop (``repro.stream.server.run_stream_experiment``)
drives ONE arrival at a time through jit boundaries — client update,
ingest, flush are each a host round-trip, so at small model sizes ~99%
of wall clock is host dispatch, not aggregation math.  This module
compiles the loop itself:

  * arrivals come from the hash-mode event plane (``repro.stream.events``):
    a :class:`~repro.stream.events.DeviceEventState` array-heap pops
    completions and re-dispatches inside the scan, reading latencies from
    the block-vectorized :class:`~repro.stream.events.HashArrivals` table;
  * local training samples are hash-derived gathers from a device-resident
    copy of the federated dataset (:class:`DeviceData`) — with-replacement
    draws keyed on the dispatch seq, label-flip poisoning included;
  * uploads land through ONE batched segment-scatter
    (``stream.buffer.ingest_batch``) per block instead of per-event writes;
  * the threshold flush, reference EMA, trust update, change-point monitor
    and the telemetry ring all run inside the scan — the carry is
    ``(params, buffer, trust, monitor, metrics-ring, ...)``, and thousands
    of events complete per host round-trip.

The flush itself is the host server's ``repro.stream.server.flush``,
looked up at trace time, and through it the one server step every
engine shares (``repro.fl.aggregate.server_step``) — the megastep only
removes the host from between events, so every robustness property
(adversary engine, staleness discounts, trust weighting, sharded
emulation) is inherited, and :func:`serve_unrolled` — the same
hash regime driven per-event through the host ``AsyncStreamServer``
methods — pins the compiled path bit-for-bit at ``block=1``.

Megastep boundary rules (see ROADMAP "Compiled serving loop"):

  * ON the scan carry: params, DRAG state, buffer, adversary memory,
    trust table, monitor state, PRNG key, the event heap + dispatch
    snapshots, the (possibly stale) root reference, the metrics ring.
  * AS scan inputs (precomputed per chunk, host-side): the arrivals
    slice, the root-batch stack and the root-refresh schedule (the
    ``RootReferenceCache`` keys, so ``root_refresh_every`` amortisation
    survives compilation).
  * AT the host boundary (once per chunk, never per event): eval, the
    telemetry-ring drain into the session, monitor verdict decode,
    the ``megastep`` trace span and its children
    (``CompiledStream._run_chunk``), and the next chunk's root batches.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import br_drag
from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.core.attacks import flip_labels
from repro.fl.client import local_update, with_counters
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.stream import buffer as buf_mod
from repro.stream import events
from repro.stream import server as server_mod
from repro.stream import sharded as sharded_mod

#: host-boundary span wrapping one compiled chunk (repro.obs.trace)
MEGASTEP_SPAN = "megastep"


# ------------------------------------------------------------ device data
class DeviceData(NamedTuple):
    """Device-resident federated dataset for hash-derived batch gathers.

    ``parts`` is the ragged per-worker index-set list padded to a
    ``[M, Lmax]`` matrix (``part_len`` holds the true lengths), so a
    worker's sample draw is two gathers — no host in the loop.
    """

    x: jax.Array  # [N, ...] f32 — train inputs
    y: jax.Array  # [N] i32 — train labels (unpoisoned; flips are applied
    #               at gather time from the malicious flag, like the
    #               host pipeline does)
    parts: jax.Array  # [M, Lmax] i32 — padded per-worker index sets
    part_len: jax.Array  # [M] i32 — true partition sizes
    malicious: jax.Array  # [M] bool — workers under adversarial control


def device_data(data) -> DeviceData:
    """Upload a ``repro.data.pipeline.FederatedData`` once."""
    lmax = max(len(p) for p in data.parts)
    m = len(data.parts)
    parts = np.zeros((m, lmax), np.int32)
    part_len = np.zeros((m,), np.int32)
    for i, p in enumerate(data.parts):
        parts[i, : len(p)] = p
        part_len[i] = len(p)
    return DeviceData(
        x=jnp.asarray(data.x, jnp.float32),
        y=jnp.asarray(data.y, jnp.int32),
        parts=jnp.asarray(parts),
        part_len=jnp.asarray(part_len),
        malicious=jnp.asarray(np.asarray(data.malicious, bool)),
    )


def event_batches(dd: DeviceData, seed, seqs, client_ids, malicious, *,
                  local_steps: int, batch_size: int, n_classes: int,
                  label_flip: bool, flip_fraction: float):
    """Hash-derived local-training batches for a block of events.

    ``seqs``/``client_ids``/``malicious`` are ``[E]``; returns
    ``(x [E, U, B, ...], y [E, U, B])``.  Draws are WITH replacement
    (uniform over the worker's partition, keyed on the dispatch seq) —
    the compiled regime's deterministic twin of the host pipeline's
    ``rng.choice``; label flipping mirrors
    ``FederatedData.sample_round`` through the same
    ``core.attacks.flip_labels`` transform.  Gathers and integer hashes
    only — no compilation-context-sensitive float ops — so the eager
    per-event evaluation in :func:`serve_unrolled` matches the scanned
    one bit for bit.
    """
    e = seqs.shape[0]
    u, b = local_steps, batch_size
    ub = u * b
    j = jnp.arange(ub, dtype=jnp.uint32)
    ctr = jnp.asarray(seqs, jnp.uint32)[:, None] * jnp.uint32(ub) + j[None, :]
    h = events.hash_u32(seed, events.SALT_BATCH, ctr)  # [E, UB]
    ln = dd.part_len[client_ids].astype(jnp.uint32)  # [E]
    pos = (h % ln[:, None]).astype(jnp.int32)
    take = dd.parts[jnp.asarray(client_ids, jnp.int32)[:, None], pos]  # [E, UB]
    x = dd.x[take]
    y = dd.y[take]
    if label_flip:
        uf = events.hash_unit(seed, events.SALT_FLIP, ctr)
        flip = (uf < jnp.float32(flip_fraction)) & jnp.asarray(malicious, bool)[:, None]
        y = flip_labels(y, n_classes, flip)
    x = x.reshape(e, u, b, *dd.x.shape[1:])
    y = y.reshape(e, u, b).astype(jnp.int32)
    return x, y


# ------------------------------------------------------------- the scan
class MegaCarry(NamedTuple):
    """Everything that rides the megastep scan (see module docstring)."""

    params: pt.Pytree
    drag: pt.Pytree
    rnd: jax.Array  # [] i32 — model version t
    buffer: pt.Pytree  # BufferState | ShardedBufferState
    adversary: pt.Pytree
    trust: pt.Pytree
    monitor: pt.Pytree
    key: jax.Array  # serving-loop PRNG (split once per flush, as host)
    sim: events.DeviceEventState
    snapshots: jax.Array  # [W, d] f32 — dispatch-time param snapshots
    completed: jax.Array  # [] i32 — events completed (round tagging)
    reference: pt.Pytree  # cached root reference r (with_root) | ()
    ring: pt.Pytree  # MetricsRing (telemetry) | ()


def make_megastep(loss_fn, cfg, dd: DeviceData, *, seed, n_clients: int,
                  local_steps: int, batch_size: int, n_classes: int,
                  label_flip: bool, flip_fraction: float,
                  malicious_table, block: int, chunk: int):
    """Builds the jitted ``(carry, dt_slice, dt_offset, xs) -> (carry, ys)``
    megastep running ``chunk`` flushes (K events each).

    ``block`` events share one vmapped client-update + one batched
    ingest; ``block=1`` takes the unbatched path — structurally the
    per-event graph, which is what the bit-for-bit oracle pins.
    ``dt_slice`` covers the chunk's re-dispatch seqs
    ``[dt_offset, dt_offset + chunk*K)`` of the arrivals table.
    """
    k = cfg.buffer_capacity
    if block < 1 or k % block:
        raise ValueError(f"block {block} must divide buffer capacity {k}")
    with_root = cfg.algorithm in ("br_drag", "fltrust")
    sharded = cfg.shards > 0
    grad_fn = jax.grad(loss_fn)

    def root_ref(params, root_batches):
        return br_drag.root_reference(
            params, lambda p, b: grad_fn(p, b), root_batches, cfg.lr
        )

    def client_row(spec, row, bx, by):
        g, _ = local_update(
            with_counters(loss_fn), flat_mod.unflatten_tree(row, spec), {"x": bx, "y": by},
            cfg.lr, variant="sgd",
        )
        return flat_mod.flatten_tree(g)

    def flush_step(dt_slice, dt_offset, carry, x):
        spec = flat_mod.spec_of(carry.params)
        params_flat = flat_mod.flatten_tree(carry.params)
        sim, snaps, buf = carry.sim, carry.snapshots, carry.buffer

        # ---- K completions: pop, local-train, batched ingest, re-dispatch
        def pop_body(c, _):
            sim, snaps, completed = c
            sim, ev = events.device_step(
                sim, carry.rnd, seed, n_clients,
                dt_slice, dt_offset=dt_offset,
                malicious_table=malicious_table,
            )
            row = snaps[ev["slot"]]
            snaps = snaps.at[ev["slot"]].set(params_flat)
            return (sim, snaps, completed + 1), (
                row, ev["seq"], ev["client"], ev["dispatch_round"], ev["malicious"]
            )

        completed = carry.completed
        for _ in range(k // block):
            (sim, snaps, completed), (rows, seqs, cids, drs, mals) = jax.lax.scan(
                pop_body, (sim, snaps, completed), None, length=block
            )
            bx, by = event_batches(
                dd, seed, seqs, cids, mals, local_steps=local_steps,
                batch_size=batch_size, n_classes=n_classes,
                label_flip=label_flip, flip_fraction=flip_fraction,
            )
            if block == 1:
                g_rows = client_row(spec, rows[0], bx[0], by[0])[None]
            else:
                g_rows = jax.vmap(
                    lambda r, x_, y_: client_row(spec, r, x_, y_)
                )(rows, bx, by)
            if sharded:
                # pod routing has a sequential dependence (least-full
                # fallback), so sharded ingest stays per-event in-scan
                buf, _ = jax.lax.scan(
                    lambda b_, i: (
                        sharded_mod.ingest(b_, g_rows[i], drs[i], mals[i], cids[i]),
                        None,
                    ),
                    buf, jnp.arange(block),
                )
            else:
                buf = buf_mod.ingest_batch(buf, g_rows, drs, mals, cids)

        # ---- threshold flush: K ingests since reset, so always ready —
        # the same invariant the host loop's flush-after-Kth-event has
        key, k_flush = jax.random.split(carry.key)
        reference = carry.reference
        if with_root:
            # the precomputed RootReferenceCache schedule: recompute r
            # only where the version-bucket key advanced
            reference = jax.lax.cond(
                x["refresh"],
                lambda op: root_ref(op[0], op[1]),
                lambda op: op[2],
                (carry.params, x["root"], reference),
            )
        params, new_drag, rnd, buf, adv, trust, metrics = server_mod.flush(
            loss_fn, cfg, carry.params, carry.drag, carry.rnd, buf, k_flush,
            adv_state=carry.adversary, trust_state=carry.trust,
            reference=reference if with_root else None,
            monitor_state=carry.monitor,
        )
        monitor = carry.monitor
        ys = {"now": sim.now}
        obs_mon = metrics.pop("obs_monitor", None)
        if obs_mon is not None:
            monitor, verdict = obs_mon
            ys["mon_state"], ys["verdict"] = monitor, verdict
        ring = carry.ring
        bundle = metrics.pop("obs", None)
        if bundle is not None:
            ring = obs_metrics.ring_push(ring, bundle)
        ys["metrics"] = metrics
        carry = MegaCarry(
            params=params, drag=new_drag, rnd=rnd, buffer=buf, adversary=adv,
            trust=trust, monitor=monitor, key=key, sim=sim, snapshots=snaps,
            completed=completed, reference=reference, ring=ring,
        )
        return carry, ys

    def megastep(carry, dt_slice, dt_offset, refresh=None, root=None):
        # the arrivals slice is loop-invariant: the scan body closes over
        # it (one resident copy) rather than receiving per-step xs rows
        xs = {"refresh": refresh, "root": root} if with_root else None
        body = lambda c, x: flush_step(dt_slice, dt_offset, c, x)  # noqa: E731
        return jax.lax.scan(body, carry, xs, length=chunk)

    return jax.jit(megastep)


# ------------------------------------------------------------- the driver
class CompiledStream:
    """Host driver of the compiled serving loop for one
    :class:`~repro.stream.server.AsyncStreamServer`.

    Owns the megastep carry between chunks, mirrors the host bookkeeping
    the legacy loop keeps (``server.t``/``state``, root-cache hit
    counters), and drains the device telemetry ring into the server's
    session once per chunk.
    """

    def __init__(self, server, data, *, seed, key, concurrency: int,
                 local_steps: int, batch_size: int, latency, bias_table=None,
                 root_samples: int = 3000, rng=None, block: int = 0,
                 chunk: int = 64):
        cfg = server.cfg
        self.server = server
        self.data = data
        self.seed = seed
        self.k = cfg.buffer_capacity
        self.w = int(concurrency)
        self.u, self.b = int(local_steps), int(batch_size)
        self.block = int(block) or self.k
        self.chunk = max(int(chunk), 1)
        self.root_samples = int(root_samples)
        self.rng = rng if rng is not None else np.random.RandomState(seed)
        self.with_root = cfg.algorithm in ("br_drag", "fltrust")
        self.n_clients = int(np.asarray(data.malicious).shape[0])
        self.dd = device_data(data)
        self.arrivals = events.HashArrivals(
            seed, latency, self.n_clients, bias_table=bias_table
        )
        self._root_key = None  # RootReferenceCache key mirror
        self._events_done = 0
        self._fns: dict[int, object] = {}
        self._megastep_kw = dict(
            seed=seed, n_clients=self.n_clients, local_steps=self.u,
            batch_size=self.b, n_classes=int(data.n_classes),
            label_flip=(data.attack == "label_flipping"),
            flip_fraction=float(data.flip_fraction),
            malicious_table=self.dd.malicious, block=self.block,
        )
        self._carry = self._init_carry(key)

    # ---------------------------------------------------------- carry init
    def _init_carry(self, key) -> MegaCarry:
        st = self.server.state
        pflat = flat_mod.flatten_tree(st.params)
        table = jnp.asarray(self.arrivals.upto(self.w))
        sim = events.device_stream_init(
            self.seed, self.n_clients, self.w, table,
            malicious_table=self.dd.malicious,
        )
        reference = (
            jax.tree.map(jnp.zeros_like, st.params) if self.with_root else ()
        )
        ring = ()
        if self.server.cfg.telemetry:
            bundle = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), self._bundle_struct()
            )
            ring = obs_metrics.ring_init(bundle, self.chunk)
        return MegaCarry(
            params=st.params, drag=st.drag, rnd=st.round, buffer=st.buffer,
            adversary=st.adversary, trust=st.trust, monitor=st.monitor,
            key=key, sim=sim, snapshots=jnp.tile(pflat[None], (self.w, 1)),
            completed=jnp.zeros((), jnp.int32), reference=reference, ring=ring,
        )

    def _bundle_struct(self):
        """Shape of one flush's MetricsBundle, via eval_shape (no compute)."""
        cfg, st = self.server.cfg, self.server.state

        def probe(params, drg, rnd, buf, key, adv, trust, mon, ref):
            out = server_mod.flush(
                self.server.loss_fn, cfg, params, drg, rnd, buf, key,
                adv_state=adv, trust_state=trust,
                reference=ref if self.with_root else None, monitor_state=mon,
            )
            return out[6]["obs"]

        return jax.eval_shape(
            probe, st.params, st.drag, st.round, st.buffer,
            jax.random.PRNGKey(0), st.adversary, st.trust, st.monitor,
            st.params,
        )

    @property
    def events_done(self) -> int:
        """Completions served so far (K per flush)."""
        return self._events_done

    # ------------------------------------------------------------- serving
    def serve_events(self, n_events: int) -> dict:
        """Complete ``n_events`` (a multiple of K) through the megastep."""
        if n_events % self.k:
            raise ValueError(
                f"n_events {n_events} must be a multiple of the flush "
                f"threshold K={self.k}"
            )
        return self.serve_flushes(n_events // self.k)

    def serve_flushes(self, n_flushes: int) -> dict:
        """Run ``n_flushes`` flushes; returns stacked per-flush host metrics."""
        chunks = []
        remaining = n_flushes
        while remaining > 0:
            c = min(remaining, self.chunk)
            chunks.append(self._run_chunk(c))
            remaining -= c
        out: dict = {}
        for ch in chunks:
            for name, v in ch.items():
                out.setdefault(name, []).append(v)
        return {name: np.concatenate(v) for name, v in out.items()}

    def _run_chunk(self, c: int) -> dict:
        """One host call of ``c`` flushes.  Spans: ``megastep`` over the
        whole chunk, with children ``megastep.prepare`` (the arrivals
        slice, root batches and argument arrays), ``megastep.dispatch``
        (the compiled call, until it returns), ``megastep.wait`` (until
        the device has finished) and ``megastep.drain`` (host mirrors,
        ring drain and the per-flush metrics brought to the host)."""
        with obs_trace.span(MEGASTEP_SPAN, flushes=c, block=self.block):
            with obs_trace.span("megastep.prepare"):
                args, refresh = self._chunk_args(c)
            with obs_trace.span("megastep.dispatch"):
                carry, ys = self._fns[c](*args)
            # sync in a span of its own: dispatch is asynchronous, and the
            # host mirrors below would otherwise absorb the device time
            with obs_trace.span("megastep.wait"):
                jax.block_until_ready((carry.params, ys))
            with obs_trace.span("megastep.drain"):
                return self._drain(c, carry, ys, refresh)

    def _chunk_args(self, c: int):
        """The compiled call's arguments for a chunk of ``c`` flushes,
        and its root-refresh schedule (None without a root reference)."""
        server, cfg = self.server, self.server.cfg
        if c not in self._fns:
            self._fns[c] = make_megastep(
                server.loss_fn, cfg, self.dd, chunk=c, **self._megastep_kw
            )
        # arrivals slice covering this chunk's re-dispatch seqs
        lo = self.w + self._events_done
        hi = lo + c * self.k
        dt_slice = jnp.asarray(self.arrivals.upto(hi)[lo:hi])
        dt_offset = jnp.asarray(lo, jnp.int32)
        args = [self._carry, dt_slice, dt_offset]
        refresh = None
        if self.with_root:
            refresh = np.zeros((c,), bool)
            for i in range(c):
                rk = (server.t + i) // cfg.root_refresh_every
                if not server.root_cache.enabled:
                    refresh[i] = True
                elif rk != self._root_key:
                    refresh[i] = True
                    self._root_key = rk
            roots = [
                self.data.root_batches(self.rng, self.u, self.b, self.root_samples)
                for _ in range(c)
            ]
            root = {
                "x": jnp.asarray(np.stack([r["x"] for r in roots])),
                "y": jnp.asarray(np.stack([r["y"] for r in roots])),
            }
            args += [jnp.asarray(refresh), root]
        return args, refresh

    def _drain(self, c: int, carry, ys, refresh) -> dict:
        """Host side of a finished chunk: the bookkeeping the legacy loop
        keeps, the telemetry drained into the session, and the per-flush
        metrics as numpy arrays."""
        server, cfg = self.server, self.server.cfg
        self._carry = carry
        self._events_done += c * self.k
        server.state = server_mod.StreamState(
            params=carry.params, round=carry.rnd, drag=carry.drag,
            buffer=carry.buffer, adversary=carry.adversary, trust=carry.trust,
            monitor=carry.monitor,
        )
        server.t += c
        server.ingested = 0
        if self.with_root and server.root_cache is not None:
            misses = int(refresh.sum())
            server.root_cache.misses += misses
            server.root_cache.hits += c - misses

        # ---- host sinks, drained once per chunk
        if cfg.telemetry:
            for b in obs_metrics.ring_tail(carry.ring, c):
                server.session.record_flush(b)
            if "verdict" in ys:
                for i in range(c):
                    server.session.record_alerts(
                        jax.tree.map(lambda a, j=i: a[j], ys["verdict"]),
                        jax.tree.map(lambda a, j=i: a[j], ys["mon_state"]),
                    )
        host = {
            name: np.asarray(v) for name, v in ys["metrics"].items()
        }
        host["virtual_time"] = np.asarray(ys["now"])
        return host


def serve_unrolled(server, data, *, seed, key, n_flushes: int,
                   concurrency: int, local_steps: int, batch_size: int,
                   latency, root_samples: int = 3000, rng=None,
                   progress=None):
    """The megastep's correctness oracle: the SAME hash-derived regime
    (event stream, batch gathers, root draws, key splits) driven one
    event at a time through the host :class:`AsyncStreamServer` methods.
    ``latency`` may be adversary-wrapped (``BiasedLatency``) — the
    compiled twin passes the base model plus the bias table instead.
    Returns ``(per-flush metrics list, final key)``.
    """
    cfg = server.cfg
    dd = device_data(data)
    if rng is None:
        rng = np.random.RandomState(seed)
    n_clients = int(np.asarray(data.malicious).shape[0])
    stream = events.EventStream(
        n_clients, latency, seed=seed,
        malicious_lookup=lambda m: bool(np.asarray(data.malicious)[m]),
        sampler="hash",
    )
    label_flip = data.attack == "label_flipping"
    inflight = {}
    for _ in range(concurrency):
        ev = stream.dispatch(server.t)
        inflight[ev.seq] = server.params
    mets = []
    while server.t < n_flushes:
        ev = stream.next_completion()
        snapshot = inflight.pop(ev.seq)
        bx, by = event_batches(
            dd, seed, jnp.asarray([ev.seq], jnp.int32),
            jnp.asarray([ev.client_id], jnp.int32),
            jnp.asarray([ev.malicious], bool),
            local_steps=local_steps, batch_size=batch_size,
            n_classes=int(data.n_classes), label_flip=label_flip,
            flip_fraction=float(data.flip_fraction),
        )
        g = server.client_update(snapshot, {"x": bx[0], "y": by[0]})
        server.ingest(g, ev.dispatch_round, ev.malicious, ev.client_id)
        ev2 = stream.dispatch(server.t)
        inflight[ev2.seq] = server.params
        if server.buffer_ready():
            key, k_flush = jax.random.split(key)
            root = None
            if server.with_root:
                root_np = data.root_batches(rng, local_steps, batch_size, root_samples)
                root = {
                    "x": jnp.asarray(root_np["x"]),
                    "y": jnp.asarray(root_np["y"]),
                }
            m = server.flush_if_ready(k_flush, root)
            mets.append({**m, "virtual_time": stream.now})
            if progress:
                progress(m)
    return mets, key
