"""Sync bridge: the async stream engine subsumes the synchronous round.

``streamed_round`` executes ONE paper round entirely through the stream
machinery — per-client jitted updates, a capacity-S ingest buffer fed in
worker order by a zero-latency :class:`repro.stream.events.EventStream`,
one threshold flush — and reproduces ``repro.fl.round.federated_round``
bit-for-bit when staleness is zero and phi = none (buffer capacity S
means every update is ingested and flushed at the dispatch version, so
tau = 0 and the discounted DoD collapses to the paper's eq. (10); the
equivalence is asserted by tests/test_stream.py).

``to_stream_state`` / ``to_sync_state`` convert server state both ways so
a deployment can warm up synchronously and then go async (or drain the
buffer and fall back) without restarting training.

The equivalence extends to the SHARDED plane (``repro.stream.sharded``):
``streamed_round(..., shards=1)`` runs the same round through the
pod-sharded buffer and the hierarchical one-psum flush and still matches
``federated_round`` bit-for-bit (a single pod runs the identical fused
passes); ``shards=p`` reassociates the reduction across pods (~1e-5,
pinned by tests/test_sharded_buffer.py).
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from repro.core import aggregators
from repro.core import pytree as pt
from repro.fl.round import RoundConfig, ServerState
from repro.stream import buffer as buf_mod
from repro.stream import server as stream_server
from repro.stream import sharded as sharded_mod
from repro.stream.events import Constant, EventStream

#: algorithms whose clients are plain local SGD — exactly the server-side
#: registry rules (client-variant algorithms like fedprox/scaffold/fedacg
#: are NOT registry rules: they carry per-client server state and stay in
#: the synchronous regime).  Derived, so new registry rules stream for free.
STREAMABLE = frozenset(aggregators.AGGREGATORS)


def stream_config_from_round(
    cfg: RoundConfig, capacity: int, shards: int = 0
) -> stream_server.StreamConfig:
    """RoundConfig -> StreamConfig with zero-staleness semantics (phi=none).

    The field copying itself is the declarative plane's lowering
    (``repro.api.lowering.stream_config_from_round`` — RoundConfig ->
    spec fragments -> StreamConfig), so the bit-for-bit sync<->async
    proof below pins the SAME code path every entry point lowers
    through."""
    if cfg.algorithm not in STREAMABLE:
        raise ValueError(
            f"algorithm {cfg.algorithm!r} needs per-client server state and "
            f"cannot run through the stream engine; streamable: {sorted(STREAMABLE)}"
        )
    from repro.api import lowering

    return lowering.stream_config_from_round(cfg, capacity, shards)


def to_stream_state(
    state: ServerState, capacity: int, shards: int = 0, mesh=None
) -> stream_server.StreamState:
    """Adopt a synchronous server's model + reference EMA into the async
    engine (buffer starts empty; ``shards > 0`` allocates the pod-sharded
    sub-buffers instead of the flat [K, d] plane)."""
    if shards > 0:
        buffer = sharded_mod.init_sharded_buffer(
            state.params, capacity, shards, mesh
        )
    else:
        buffer = buf_mod.init_buffer(state.params, capacity)
    return stream_server.StreamState(
        params=state.params,
        round=state.round,
        drag=state.drag,
        buffer=buffer,
        adversary=state.adversary,
        trust=state.trust,
    )


def to_sync_state(stream_state: stream_server.StreamState, n_workers: int) -> ServerState:
    """Drain back to the synchronous regime (momentum/control variates
    restart at zero — they never existed asynchronously)."""
    import jax

    params = stream_state.params
    return ServerState(
        params=params,
        round=stream_state.round,
        drag=stream_state.drag,
        momentum=pt.tree_zeros_like(params),
        control_global=pt.tree_zeros_like(params),
        control_workers=jax.tree.map(
            lambda x: jnp.zeros((n_workers,) + x.shape, x.dtype), params
        ),
        adversary=stream_state.adversary,
        trust=stream_state.trust,
    )


def streamed_round(
    loss_fn: Callable,
    state: ServerState,
    cfg: RoundConfig,
    batches,  # [S, U, B, ...]
    selected_idx,  # [S] int32
    malicious_mask,  # [S] bool
    key,
    root_batches=None,
    jit_client: bool = True,
    shards: int = 0,
    mesh=None,
) -> tuple[ServerState, dict]:
    """One ``federated_round`` driven through the stream engine.

    S dispatches at the current version, zero latency, capacity-S buffer,
    one flush.  Signature-compatible with ``federated_round``.

    ``jit_client=False`` runs the client update eagerly — op-for-op the
    same primitive sequence as an eager ``federated_round``, which makes
    the two trajectories comparable bit-for-bit (a jitted program may
    fuse/contract differently and drift by ~1 ulp while staying
    mathematically identical).

    ``shards > 0`` routes the round through the SHARDED ingest buffer
    and the hierarchical one-psum flush (``repro.stream.sharded``) —
    S must divide into the pods.  ``shards=1`` extends the bit-for-bit
    equivalence proof to the sharded plane (the single-pod flush is the
    single-buffer flush operation-for-operation); ``shards > 1`` is the
    same math reassociated across pods (~1e-5).
    """
    s = int(malicious_mask.shape[0])
    scfg = stream_config_from_round(cfg, capacity=s, shards=shards)
    if jit_client:
        client_fn = stream_server.make_client_fn(loss_fn, scfg)
    else:
        from repro.fl.client import local_update, with_counters

        client_fn = lambda p, b: local_update(
            with_counters(loss_fn), p, b, scfg.lr, variant="sgd")[0]

    es = EventStream(n_clients=max(s, 1), latency=Constant(0.0), seed=0)
    rnd_host = int(state.round)
    for i in range(s):
        es.dispatch(rnd_host, client_id=int(selected_idx[i]))

    if shards > 0:
        ingest_fn = sharded_mod.make_ingest_fn()
        buf = sharded_mod.init_sharded_buffer(state.params, s, shards, mesh)
    else:
        ingest_fn = buf_mod.make_ingest_fn()
        buf = buf_mod.init_buffer(state.params, s)
    for i in range(s):
        ev = es.next_completion()  # FIFO at zero latency -> worker order
        g = client_fn(state.params, pt.tree_index(batches, ev.seq))
        buf = ingest_fn(
            buf, g, ev.dispatch_round, malicious_mask[ev.seq], ev.client_id
        )

    flush_args = [loss_fn, scfg, state.params, state.drag, state.round, buf, key]
    params, new_drag, rnd, _, new_adv, new_trust, metrics = stream_server.flush(
        *flush_args, root_batches=root_batches,
        adv_state=state.adversary, trust_state=state.trust, mesh=mesh,
    )
    new_state = ServerState(
        params=params,
        round=rnd,
        drag=new_drag,
        momentum=state.momentum,
        control_global=state.control_global,
        control_workers=state.control_workers,
        adversary=new_adv,
        trust=new_trust,
    )
    return new_state, metrics
