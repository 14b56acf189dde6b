"""Mesh-sharded ingest buffer with a hierarchical one-psum flush.

The single-device buffer (``repro.stream.buffer``) is one ``[K, d]``
slot matrix; this module splits it into per-pod ``[K/p, d]`` sub-buffers
laid out with a plain ``NamedSharding`` over a mesh axis (rows = clients
shard over the pod axis; metadata stays replicated — it is O(K), not
O(K·d)).  This is what lets the async stream engine ride
``launch.train``'s SPMD round: each pod ingests its own clients and runs
the fused flush (``kernels.ops.calibrated_reduce`` — one ``fused_flush``
pass for VMEM-resident sub-stacks, else ``dot_norms`` +
``blend_reduce``) over ITS rows only.

Routing: ``client_id`` hash-routes to a home pod (:func:`route_pod`),
falling back to the least-full pod when the home sub-buffer is full —
so an upload is dropped only when the WHOLE buffer is full, exactly the
single-buffer acceptance behaviour.

The hierarchical flush keeps DRAG/BR-DRAG's O(d) communication story at
pod scale.  Everything cross-pod is ONE ``psum``:

  * per-row blend coefficients need only that row's ``<g, r>`` /
    ``||g||²`` plus ``||r||²`` — and r is replicated, so every
    coefficient is pod-local;
  * the aggregation weights (staleness discounts × trust reputations)
    are computed REPLICATED from the replicated metadata and normalised
    globally before the blend — no collective;
  * each pod's flush emits a partial ``[d]`` weighted sum;
    the partials — together with the per-row DoD/trust scalars,
    scattered into their ``[p, K/p]`` slots — meet in exactly one
    ``psum`` (:func:`psum_bundle`, the probe point counted by
    ``kernels.instrument``) before the egress unflatten.

With ``mesh=None`` the same per-pod program runs as an unrolled loop on
one device (the emulation path — benchmarks and single-process tests);
the cross-pod reduction still goes through the one :func:`psum_bundle`
call, so the program structure is identical.  At ``p = 1`` the flush is
bit-for-bit the single-buffer flush (same kernels, same block sizes,
same operation order) — pinned by ``tests/test_sharded_buffer.py``.

The single-buffer path stays the numerical oracle, the same way
``tests/test_flat.py`` pins flat vs pytree.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import drag
from repro.core import flat as flat_mod
from repro.core import pytree as pt
from repro.kernels import ops as kops
from repro.stream import buffer as buffer_mod

#: the mesh axis the sub-buffers shard over (``launch.mesh.make_pod_mesh``)
POD_AXIS = "pod"

#: span name the host loop wraps the jitted hierarchical flush in
#: (obs plane — span parity with the single-buffer flush/round spans;
#: the span sits at the HOST boundary, never inside jit)
FLUSH_SPAN = "sharded_flush"


def span_attrs(cfg) -> dict:
    """Span attributes identifying a sharded flush's pod geometry.

    Takes the ``StreamConfig`` (duck-typed: ``shards`` /
    ``buffer_capacity``) so the host loop can attribute wall-clock to a
    pod layout without touching device state.
    """
    shards = int(getattr(cfg, "shards", 0))
    capacity = int(getattr(cfg, "buffer_capacity", 0))
    return {
        "shards": shards,
        "pod_capacity": capacity // shards if shards else capacity,
    }


class ShardedBufferState(NamedTuple):
    """Per-pod sub-buffers: ``slots[i]`` is pod i's ``[K/p, d]`` plane.

    ``slots`` shards over the pod axis; the per-slot metadata and the
    ``[p]`` fill counts are replicated (every pod needs the global
    counts for the least-full fallback, and the flush derives the
    discount/reputation weights from the metadata replicated).
    """

    slots: jax.Array  # [p, K/p, d] f32 — pod-sharded flat update rows
    dispatch_rounds: jax.Array  # [p, K/p] int32 — server version tags
    malicious: jax.Array  # [p, K/p] bool
    counts: jax.Array  # [p] int32 — per-pod fill counts
    client_ids: jax.Array  # [p, K/p] int32
    drops: jax.Array  # [DROP_BUCKETS] int32 — cumulative overflow drops
    #                    per client-hash bucket (replicated; never reset)


def n_pods(buf: ShardedBufferState) -> int:
    return buf.slots.shape[0]


def pod_capacity(buf: ShardedBufferState) -> int:
    return buf.slots.shape[1]


def capacity_of(buf: ShardedBufferState) -> int:
    return buf.slots.shape[0] * buf.slots.shape[1]


def total_count(buf: ShardedBufferState) -> jax.Array:
    return jnp.sum(buf.counts)


def buffer_layout(mesh, pod_axis: str = POD_AXIS, model_axis: str | None = None):
    """(slots sharding, metadata sharding) for a sharded buffer on ``mesh``.

    Rows (clients) shard over ``pod_axis``; columns optionally shard with
    the model over ``model_axis`` (storage layout only — the hierarchical
    flush is manual over the pod axis and keeps d replicated inside the
    manual region).
    """
    slots = NamedSharding(mesh, P(pod_axis, None, model_axis))
    meta = NamedSharding(mesh, P())
    return slots, meta


def init_sharded_buffer(
    params_like: pt.Pytree,
    capacity: int,
    shards: int,
    mesh=None,
    pod_axis: str = POD_AXIS,
) -> ShardedBufferState:
    """Allocates p = ``shards`` empty ``[K/p, d]`` sub-buffers.

    With ``mesh`` the slots land pod-sharded (``buffer_layout``); without
    one the same ``[p, K/p, d]`` array lives on the default device and
    the flush runs the emulation path.
    """
    if capacity % shards != 0:
        raise ValueError(
            f"buffer capacity {capacity} must divide evenly into {shards} pods"
        )
    d = pt.tree_size(params_like)
    kp = capacity // shards
    buf = ShardedBufferState(
        slots=jnp.zeros((shards, kp, d), jnp.float32),
        dispatch_rounds=jnp.zeros((shards, kp), jnp.int32),
        malicious=jnp.zeros((shards, kp), bool),
        counts=jnp.zeros((shards,), jnp.int32),
        client_ids=jnp.zeros((shards, kp), jnp.int32),
        drops=jnp.zeros((buffer_mod.DROP_BUCKETS,), jnp.int32),
    )
    if mesh is not None:
        if mesh.shape[pod_axis] != shards:
            raise ValueError(
                f"mesh axis {pod_axis!r} has size {mesh.shape[pod_axis]}, "
                f"need {shards}"
            )
        slots_sh, meta_sh = buffer_layout(mesh, pod_axis)
        buf = ShardedBufferState(
            slots=jax.device_put(buf.slots, slots_sh),
            dispatch_rounds=jax.device_put(buf.dispatch_rounds, meta_sh),
            malicious=jax.device_put(buf.malicious, meta_sh),
            counts=jax.device_put(buf.counts, meta_sh),
            client_ids=jax.device_put(buf.client_ids, meta_sh),
            drops=jax.device_put(buf.drops, meta_sh),
        )
    return buf


# ---------------------------------------------------------------- routing

#: shared with the flat buffer's drop-bucket accounting — ONE client hash
_mix32 = buffer_mod.mix32


def route_pod(client_id, pods: int) -> jax.Array:
    """Home pod of a client: deterministic hash of the id, mod p.

    A HASH, not ``id % p``: real client-id spaces are structured (shard
    ranges, tenant prefixes), and a modulo would map a contiguous tenant
    onto one pod by construction.
    """
    return (_mix32(client_id) % jnp.uint32(pods)).astype(jnp.int32)


def ingest(
    buf: ShardedBufferState, g: pt.Pytree, dispatch_round, is_malicious, client_id=0
) -> ShardedBufferState:
    """Route one upload to its pod's next free slot.

    ``client_id`` hash-routes to its home pod; a full home sub-buffer
    falls back to the least-full pod, so the write is refused only when
    every sub-buffer is full — the same drop semantics as the flat
    buffer.  The slot write stays a single dynamic-update-slice on the
    donated slot array (see ``stream.buffer.ingest``).
    """
    row = g if isinstance(g, jax.Array) and g.ndim == 1 else flat_mod.flatten_tree(g)
    p, kp = buf.slots.shape[0], buf.slots.shape[1]
    home = route_pod(client_id, p)
    fallback = jnp.argmin(buf.counts).astype(jnp.int32)
    pod = jnp.where(buf.counts[home] < kp, home, fallback)
    keep = buf.counts[pod] < kp
    slot = jnp.minimum(buf.counts[pod], kp - 1)
    return ShardedBufferState(
        slots=buf.slots.at[pod, slot].set(
            jnp.where(keep, row.astype(jnp.float32), buf.slots[pod, slot])
        ),
        dispatch_rounds=buf.dispatch_rounds.at[pod, slot].set(
            jnp.where(keep, jnp.asarray(dispatch_round, jnp.int32),
                      buf.dispatch_rounds[pod, slot])
        ),
        malicious=buf.malicious.at[pod, slot].set(
            jnp.where(keep, is_malicious, buf.malicious[pod, slot])
        ),
        counts=buf.counts.at[pod].add(keep.astype(jnp.int32)),
        client_ids=buf.client_ids.at[pod, slot].set(
            jnp.where(keep, jnp.asarray(client_id, jnp.int32),
                      buf.client_ids[pod, slot])
        ),
        # same accounting as the flat buffer: a whole-buffer-full refusal
        # increments the dropping client's hash bucket
        drops=buf.drops.at[buffer_mod.drop_bucket(client_id)].add(
            1 - keep.astype(jnp.int32)
        ),
    )


def reset(buf: ShardedBufferState) -> ShardedBufferState:
    """Empty every pod without touching slot storage."""
    return buf._replace(counts=jnp.zeros_like(buf.counts))


def staleness(buf: ShardedBufferState, server_round) -> jax.Array:
    """tau per slot, ``[p, K/p]`` int32 (replicated metadata)."""
    return jnp.maximum(
        jnp.asarray(server_round, jnp.int32) - buf.dispatch_rounds, 0
    )


def make_ingest_fn():
    """Jitted donated ingest: the buffer argument is consumed in place."""
    return jax.jit(ingest, donate_argnums=(0,))


# ------------------------------------------------------ hierarchical flush

def psum_bundle(bundle: pt.Pytree, axis_name: str | None):
    """THE one cross-pod reduction of a hierarchical flush.

    Every partial a flush exchanges — the ``[d]`` weighted sum, the
    scattered per-row DoD/trust scalars — rides this single call: one
    ``psum`` primitive over the pod mesh axis, or (emulation,
    ``axis_name=None``) one tree-sum over the stacked leading pod axis.
    A psum of a pytree is one collective per leaf, so the leaves are
    packed into one flat buffer first and unpacked after.
    ``kernels.instrument.count_collective_calls`` counts invocations,
    which is how the one-psum invariant is asserted.
    """
    if axis_name is not None:
        flat, unpack = ravel_pytree(bundle)
        return unpack(jax.lax.psum(flat, axis_name))
    # emulation: leaves are [p, ...] stacked partials.  p == 1 is a pure
    # slice — no arithmetic — which keeps the p=1 path bit-for-bit.
    return jax.tree.map(
        lambda x: x[0] if x.shape[0] == 1 else jnp.sum(x, axis=0), bundle
    )


def _pod_passes(g_local, r_flat, w_local, disc_local, *, mode, c, init,
                k_total, interpret):
    """One pod's share of the flush: the SAME fused flush the
    single-buffer plane runs (``kops.calibrated_reduce`` — single-pass
    when the local stack is VMEM-resident, two streaming passes
    otherwise), over the local ``[K/p, d]`` rows only.

    Returns (partial delta [d], dots [K/p], g_sq [K/p], lam [K/p],
    r_sq []).  The partial delta carries the globally-normalised weights
    already multiplied in, so partials sum directly.  The bootstrap
    fallback (eq. 5a) is uniform 1/K over the GLOBAL worker count.
    """
    kp = g_local.shape[0]
    partial, lam, (dots, gsq, rsq) = kops.calibrated_reduce(
        g_local, r_flat, c, mode, w=w_local, discounts=disc_local,
        init=init, boot_aw=jnp.full((kp,), 1.0 / k_total, jnp.float32),
        interpret=interpret,
    )
    return partial, dots, gsq, lam, rsq


def hierarchical_flush(
    slots3: jax.Array,  # [p, K/p, d] — (possibly attacked) sub-buffers
    r_flat: jax.Array,  # [d] — replicated reference (zeros for mode=mean)
    *,
    mode: str,  # drag | br_drag | mean
    c: float = 0.0,
    discounts2=None,  # [p, K/p] phi(tau) | None
    weights=None,  # [K] raw aggregation weights (pod-major) | None
    init=None,  # scalar bool — DRAG bootstrap switch | None
    mesh=None,
    pod_axis: str = POD_AXIS,
    interpret: bool | None = None,
):
    """The sharded DRAG/BR-DRAG reduction: per-pod fused passes, one psum.

    Returns (delta [d], lam [K], (dots [K], g_sq [K], r_sq [])) with the
    per-row vectors in pod-major order — the row order of the sharded
    plane.  The stats feed ``trust.signals_from_stats`` exactly as on the
    single-buffer path.
    """
    p, kp, _ = slots3.shape
    k = p * kp
    disc2 = (
        jnp.ones((p, kp), jnp.float32) if discounts2 is None
        else jnp.asarray(discounts2, jnp.float32)
    )
    # weight normalisation is GLOBAL but collective-free: weights derive
    # from replicated metadata (staleness tags, trust table), so every
    # pod computes the identical normalised [p, K/p] table
    w2 = kops.normalize_weights(weights, k).reshape(p, kp)

    if mesh is None:
        parts = [
            _pod_passes(
                slots3[i], r_flat, w2[i], disc2[i],
                mode=mode, c=c, init=init, k_total=k, interpret=interpret,
            )
            for i in range(p)
        ]
        bundle = {"delta": jnp.stack([pr[0] for pr in parts])}
        delta = psum_bundle(bundle, None)["delta"]
        dots = jnp.stack([pr[1] for pr in parts])
        gsq = jnp.stack([pr[2] for pr in parts])
        lam = jnp.stack([pr[3] for pr in parts])
        rsq = parts[0][4]
    else:
        if mesh.shape[pod_axis] != p:
            raise ValueError(
                f"mesh axis {pod_axis!r} size {mesh.shape[pod_axis]} != {p} pods"
            )

        def body(g_block, r_rep, w_block, disc_block, init_rep):
            i = jax.lax.axis_index(pod_axis)
            partial, dots_l, gsq_l, lam_l, rsq_l = _pod_passes(
                g_block[0], r_rep, w_block[0], disc_block[0],
                mode=mode, c=c,
                init=None if init is None else init_rep,
                k_total=k, interpret=interpret,
            )
            # scatter this pod's per-row scalars into their [p, K/p]
            # slots so they ride the ONE psum alongside the [d] partial
            scat = lambda x: jnp.zeros((p,) + x.shape, x.dtype).at[i].set(x)  # noqa: E731
            red = psum_bundle(
                {"delta": partial, "dots": scat(dots_l),
                 "gsq": scat(gsq_l), "lam": scat(lam_l)},
                pod_axis,
            )
            # r is replicated, so r_sq is already identical on every pod
            return red["delta"], red["dots"], red["gsq"], red["lam"], rsq_l

        fn = jax.shard_map(
            body,
            mesh=mesh,
            axis_names={pod_axis},
            in_specs=(P(pod_axis, None, None), P(), P(pod_axis, None),
                      P(pod_axis, None), P()),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )
        init_arg = jnp.asarray(False) if init is None else jnp.asarray(init)
        delta, dots, gsq, lam, rsq = fn(slots3, r_flat, w2, disc2, init_arg)

    return delta, lam.reshape(k), (dots.reshape(k), gsq.reshape(k), rsq)


# --------------------------------------------------- algorithm entry points

def drag_round_step(
    params: pt.Pytree,
    state: drag.DragState,
    slots3: jax.Array,
    *,
    alpha: float,
    c: float,
    discounts2=None,
    weights=None,
    mesh=None,
    pod_axis: str = POD_AXIS,
    interpret: bool | None = None,
):
    """``drag.round_step_flat`` on the sharded plane.

    Identical semantics and — at p = 1 — identical operations: the same
    ``kops.calibrated_reduce`` flush (same ``flush_path`` selection,
    same kernels, same operation order) over the same ``[K, d]`` rows,
    so the single-pod flush is bit-for-bit the single-buffer flush.

    Returns (params', state', metrics, (dots, g_sq, r_sq)).
    """
    spec = flat_mod.spec_of(params)
    r_flat = flat_mod.flatten_tree(state.reference)
    delta_flat, lam, stats = hierarchical_flush(
        slots3, r_flat, mode="drag", c=c, discounts2=discounts2,
        weights=weights, init=state.initialized, mesh=mesh,
        pod_axis=pod_axis, interpret=interpret,
    )
    ema = (1.0 - alpha) * r_flat + alpha * delta_flat
    new_ref_flat = jnp.where(state.initialized, ema, delta_flat)
    new_params = pt.tree_add(params, flat_mod.unflatten_tree(delta_flat, spec))
    new_state = drag.DragState(
        reference=flat_mod.unflatten_tree(new_ref_flat, spec),
        initialized=jnp.asarray(True),
    )
    metrics = {
        "dod_mean": jnp.mean(lam),
        "dod_max": jnp.max(lam),
        "delta_norm": jnp.linalg.norm(delta_flat),
        "ref_norm": jnp.linalg.norm(new_ref_flat),
    }
    return new_params, new_state, metrics, stats


def br_drag_round_step(
    params: pt.Pytree,
    slots3: jax.Array,
    reference_flat: jax.Array,
    *,
    c: float,
    discounts2=None,
    weights=None,
    mesh=None,
    pod_axis: str = POD_AXIS,
    interpret: bool | None = None,
):
    """``br_drag.round_step_flat`` on the sharded plane.

    Returns (params', metrics, (dots, g_sq, r_sq))."""
    spec = flat_mod.spec_of(params)
    delta_flat, lam, stats = hierarchical_flush(
        slots3, reference_flat, mode="br_drag", c=c, discounts2=discounts2,
        weights=weights, mesh=mesh, pod_axis=pod_axis, interpret=interpret,
    )
    new_params = pt.tree_add(params, flat_mod.unflatten_tree(delta_flat, spec))
    metrics = {
        "dod_mean": jnp.mean(lam),
        "dod_max": jnp.max(lam),
        "delta_norm": jnp.linalg.norm(delta_flat),
        "ref_norm": jnp.linalg.norm(reference_flat),
    }
    return new_params, metrics, stats


def mean_flush(
    slots3: jax.Array,
    *,
    weights=None,
    mesh=None,
    pod_axis: str = POD_AXIS,
    interpret: bool | None = None,
):
    """Hierarchical (weighted) mean — the FedAvg flush on the sharded
    plane.  Returns (delta [d], (dots, g_sq, r_sq)); g_sq gives the
    per-row update norms for free."""
    r0 = jnp.zeros((slots3.shape[2],), jnp.float32)
    delta, _, stats = hierarchical_flush(
        slots3, r0, mode="mean", weights=weights, mesh=mesh,
        pod_axis=pod_axis, interpret=interpret,
    )
    return delta, stats
