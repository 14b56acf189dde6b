"""Fixed-capacity jitted ingest buffer for the async server.

The buffer IS the flat update plane (``repro.core.flat``): a single
pre-allocated ``[K, d]`` f32 slot matrix plus per-slot metadata
(dispatch-round tag, Byzantine flag, client id).  Uploads are flattened
ONCE at ingest — the flatten boundary of the async regime — and the
flush hands ``slots`` straight to the fused aggregation kernels and the
flat aggregator tier (``aggregators.FLAT_AGGREGATORS``) without ever
rebuilding a pytree; only the aggregated ``[d]`` delta is unflattened.

``ingest`` is a donated jitted write — ``.at[slot].set`` on the donated
arrays lowers to an in-place dynamic-update-slice, so accepting an
upload costs one row write, never a buffer copy.  ``reset`` only zeroes
the fill count; slot contents are overwritten by subsequent ingests.

A flat row buffer is also what the ROADMAP's sharded-ingest direction
needs: ``[K, d]`` rows shard over a mesh axis trivially, per-leaf
pytree buffers do not.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.obs.metrics import DROP_BUCKETS


def mix32(x) -> jax.Array:
    """Jittable 32-bit integer finaliser (splitmix-style avalanche).

    THE client-id hash of the stream plane: pod routing
    (``stream.sharded.route_pod``) and drop-bucket accounting both go
    through it, so "which pod" and "whose uploads got dropped" are keyed
    consistently.
    """
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def drop_bucket(client_id) -> jax.Array:
    """Which of the ``DROP_BUCKETS`` drop counters a client hashes into."""
    return (mix32(client_id) % jnp.uint32(DROP_BUCKETS)).astype(jnp.int32)


class BufferState(NamedTuple):
    """Device-side ingest buffer (capacity K = leading axis of slots)."""

    slots: jax.Array  # [K, d] f32 — flat update rows (repro.core.flat)
    dispatch_rounds: jax.Array  # [K] int32 — server version tags
    malicious: jax.Array  # [K] bool — for Byzantine injection at flush
    count: jax.Array  # [] int32 — filled slots
    client_ids: jax.Array  # [K] int32 — uploader ids (trust indexing)
    drops: jax.Array  # [DROP_BUCKETS] int32 — CUMULATIVE overflow drops
    #                    per client-hash bucket; never reset by ``reset``


def capacity_of(buf: BufferState) -> int:
    return buf.slots.shape[0]


def init_buffer(params_like: pt.Pytree, capacity: int) -> BufferState:
    """Allocates an empty K-slot flat buffer sized from the param pytree."""
    d = pt.tree_size(params_like)
    return BufferState(
        slots=jnp.zeros((capacity, d), jnp.float32),
        dispatch_rounds=jnp.zeros((capacity,), jnp.int32),
        malicious=jnp.zeros((capacity,), bool),
        count=jnp.zeros((), jnp.int32),
        client_ids=jnp.zeros((capacity,), jnp.int32),
        drops=jnp.zeros((DROP_BUCKETS,), jnp.int32),
    )


def ingest(
    buf: BufferState, g: pt.Pytree, dispatch_round, is_malicious, client_id=0
) -> BufferState:
    """Write one update into the next free slot (drops if already full).

    ``g`` may be an update pytree (flattened here — THE boundary) or an
    already-flat ``[d]`` row.  ``client_id`` tags the slot with the
    uploader's identity so the flush can index the trust layer's
    reputation table; 0 when no trust is configured.
    """
    row = g if isinstance(g, jax.Array) and g.ndim == 1 else flat_mod.flatten_tree(g)
    k = capacity_of(buf)
    slot = jnp.minimum(buf.count, k - 1)
    keep = buf.count < k  # full buffer: refuse the write, don't clobber

    # select at SLOT granularity so the slot write stays a single in-place
    # dynamic-update-slice on the donated arrays (a whole-buffer where
    # would materialise a copy and break the donation fast path)
    return BufferState(
        slots=buf.slots.at[slot].set(
            jnp.where(keep, row.astype(jnp.float32), buf.slots[slot])
        ),
        dispatch_rounds=buf.dispatch_rounds.at[slot].set(
            jnp.where(keep, jnp.asarray(dispatch_round, jnp.int32), buf.dispatch_rounds[slot])
        ),
        malicious=buf.malicious.at[slot].set(
            jnp.where(keep, is_malicious, buf.malicious[slot])
        ),
        count=buf.count + keep.astype(jnp.int32),
        client_ids=buf.client_ids.at[slot].set(
            jnp.where(keep, jnp.asarray(client_id, jnp.int32), buf.client_ids[slot])
        ),
        # a refused write is ACCOUNTED, not silent: the dropping client's
        # hash bucket increments (one scatter-add, same donation fast path)
        drops=buf.drops.at[drop_bucket(client_id)].add(
            1 - keep.astype(jnp.int32)
        ),
    )


def ingest_packed(buf: BufferState, row) -> BufferState:
    """:func:`ingest` of one packed upload (``core.flat.pack_upload``): a
    ``[d + 3]`` f32 row whose last three words are the int32 bit patterns
    of ``(dispatch_round, malicious, client_id)``.  They are bitcast back,
    with no float arithmetic on them, so the write and its tags are
    :func:`ingest`'s bit for bit and the buffer and the row are the only
    arguments: no host scalar crosses per upload.
    """
    d = buf.slots.shape[1]
    # slice first: bitcasting the whole row would copy all of it
    meta = jax.lax.bitcast_convert_type(row[d:], jnp.int32)
    return ingest(buf, row[:d], meta[0], meta[1] != 0, meta[2])


def ingest_batch(buf: BufferState, rows, dispatch_rounds, malicious,
                 client_ids) -> BufferState:
    """Write B already-flat upload rows in one segment-scatter.

    Bit-equivalent to B sequential :func:`ingest` calls: the fill count
    is monotone, so row i lands in slot ``count + i`` iff that is still
    inside the buffer; later rows are DROPPED (scatter ``mode="drop"``
    discards their out-of-bounds writes) and accounted in the same
    cumulative per-client-hash ``drops`` buckets, one scatter-add.  This
    is the megastep's ingest: one write per [B, d] block instead of B
    jit round-trips.
    """
    b, k = rows.shape[0], capacity_of(buf)
    pos = buf.count + jnp.arange(b, dtype=jnp.int32)
    keep = pos < k
    slot = jnp.where(keep, pos, k)  # k = one past the end -> dropped
    return BufferState(
        slots=buf.slots.at[slot].set(rows.astype(jnp.float32), mode="drop"),
        dispatch_rounds=buf.dispatch_rounds.at[slot].set(
            jnp.asarray(dispatch_rounds, jnp.int32), mode="drop"
        ),
        malicious=buf.malicious.at[slot].set(
            jnp.asarray(malicious, bool), mode="drop"
        ),
        count=buf.count + keep.astype(jnp.int32).sum(),
        client_ids=buf.client_ids.at[slot].set(
            jnp.asarray(client_ids, jnp.int32), mode="drop"
        ),
        drops=buf.drops.at[drop_bucket(client_ids)].add(
            (~keep).astype(jnp.int32)
        ),
    )


def reset(buf: BufferState) -> BufferState:
    """Empty the buffer without touching slot storage."""
    return buf._replace(count=jnp.zeros((), jnp.int32))


def staleness(buf: BufferState, server_round) -> jax.Array:
    """tau_m = current version - dispatch version, per slot, [K] int32."""
    return jnp.maximum(jnp.asarray(server_round, jnp.int32) - buf.dispatch_rounds, 0)


def as_stack(buf: BufferState, spec: flat_mod.StackSpec, server_round) -> flat_mod.UpdateStack:
    """View the full buffer as an :class:`~repro.core.flat.UpdateStack`."""
    return flat_mod.UpdateStack(
        data=buf.slots,
        client_ids=buf.client_ids,
        staleness=staleness(buf, server_round),
        spec=spec,
    )


def make_ingest_fn():
    """Jitted donated ingest: the buffer argument is consumed in place."""
    return jax.jit(ingest, donate_argnums=(0,))


def make_ingest_packed_fn():
    """Jitted donated packed ingest: (buffer, packed row) -> buffer."""
    return jax.jit(ingest_packed, donate_argnums=(0,))


def make_ingest_batch_fn():
    """Jitted donated batch ingest (one segment-scatter per [B, d] block)."""
    return jax.jit(ingest_batch, donate_argnums=(0,))
