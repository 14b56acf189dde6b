"""The flat update plane: one canonical ``[S, d]`` representation for the
whole aggregation data path.

Every aggregation rule in the robust-FL literature — DRAG/BR-DRAG's
divergence calibration, FLTrust's cosine scores, Krum's pairwise
distances, trimmed mean, geometric median — is row algebra over a stack
of per-client update *vectors*.  The pytree representation the clients
naturally produce is a serialization detail; keeping it alive through
the server hot path forces every consumer (calibration, trust signals,
adversary crafting, reducers) to re-walk the leaves separately.

This module fixes the boundary rules:

  * updates are flattened into an :class:`UpdateStack` ONCE where they
    enter the server (client upload in ``repro.fl.round``, buffer ingest
    in ``repro.stream.buffer``);
  * everything in between — adversary crafting, DoD calibration, trust
    signals, reduction — stays flat and is served by the fused Pallas
    kernels in ``repro.kernels`` (two HBM passes over G per flush);
  * exactly ONE unflatten happens at the exit, when the aggregated
    Delta (a single ``[d]`` vector) is applied to the model pytree.

The stacked-pytree code paths in ``core.drag`` / ``core.br_drag`` /
``core.aggregators`` are retained as the numerical oracle (the
``ref.py`` of the update plane); ``tests/test_flat.py`` pins the flat
path against them.

A flat ``[K, d]`` ingest buffer is also the prerequisite for sharding
the buffer over a mesh axis (ROADMAP): rows of a matrix shard trivially,
per-leaf pytree buffers do not.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pytree as pt


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class StackSpec:
    """Static (hashable) description of the pytree a row flattens from.

    ``treedef``/``shapes``/``dtypes`` describe the leaves in traversal
    order; ``d`` is the total flat length.  Hashable, and registered as
    a STATIC pytree node (zero leaves, itself the aux data) so it can
    ride as aux_data, a jit argument, or inside traced containers —
    e.g. the ``AttackContext`` that ``Schedule.craft`` threads through
    ``lax.switch``."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[str, ...]

    @property
    def sizes(self) -> tuple[int, ...]:
        out = []
        for shp in self.shapes:
            n = 1
            for s in shp:
                n *= s
            out.append(n)
        return tuple(out)

    @property
    def d(self) -> int:
        return sum(self.sizes)


def spec_of(tree: pt.Pytree) -> StackSpec:
    """Spec of a single (non-stacked) pytree, e.g. the model params."""
    leaves, treedef = jax.tree.flatten(tree)
    return StackSpec(
        treedef=treedef,
        shapes=tuple(tuple(x.shape) for x in leaves),
        dtypes=tuple(str(jnp.asarray(x).dtype) for x in leaves),
    )


def stacked_spec_of(stacked: pt.Pytree) -> StackSpec:
    """Spec of one ROW of a stacked (leading worker axis) pytree."""
    leaves, treedef = jax.tree.flatten(stacked)
    return StackSpec(
        treedef=treedef,
        shapes=tuple(tuple(x.shape[1:]) for x in leaves),
        dtypes=tuple(str(jnp.asarray(x).dtype) for x in leaves),
    )


def flatten_tree(tree: pt.Pytree) -> jax.Array:
    """One pytree -> flat f32 ``[d]`` vector (leaf traversal order)."""
    return pt.tree_flatten_vector(tree)


def flatten_host(tree: pt.Pytree):
    """A pytree of host (numpy) leaves -> the :func:`flatten_tree` row,
    built on the host as one contiguous f32 ``[d]`` array, so an upload
    off the wire crosses to the device as one transfer instead of one per
    leaf.  Any other tree (device arrays) is returned unchanged."""
    leaves = jax.tree.leaves(tree)
    if not leaves or not all(isinstance(x, np.ndarray) for x in leaves):
        return tree
    return np.concatenate([np.asarray(x, np.float32).ravel() for x in leaves])


def unflatten_tree(vec: jax.Array, spec: StackSpec) -> pt.Pytree:
    """Flat ``[d]`` vector -> pytree per ``spec`` (the ONE exit point)."""
    out, off = [], 0
    for shp, dt, n in zip(spec.shapes, spec.dtypes, spec.sizes):
        out.append(vec[off : off + n].reshape(shp).astype(dt))
        off += n
    return jax.tree.unflatten(spec.treedef, out)


def flatten_stacked(stacked: pt.Pytree) -> jax.Array:
    """Stacked ``[S, ...]`` pytree -> ``[S, d]`` f32 matrix.

    Row ``s`` equals ``flatten_tree`` of worker ``s``'s pytree bit-for-bit
    (reshape + concatenate only — no arithmetic), which is what makes the
    sync round and the async ingest agree exactly.
    """
    leaves = jax.tree.leaves(stacked)
    s = leaves[0].shape[0]
    return jnp.concatenate(
        [x.reshape(s, -1).astype(jnp.float32) for x in leaves], axis=1
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class UpdateStack:
    """The canonical aggregation operand: flat updates + row metadata.

    ``data`` is the ``[S, d]`` f32 stack; ``client_ids``/``staleness``
    are per-row tags consumed by the trust layer and the staleness
    discounts; ``spec`` (static aux_data) remembers how to unflatten.
    """

    data: jax.Array  # [S, d] f32
    client_ids: jax.Array  # [S] int32
    staleness: jax.Array  # [S] int32
    spec: StackSpec

    def tree_flatten(self):
        return (self.data, self.client_ids, self.staleness), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        data, client_ids, staleness = children
        return cls(data=data, client_ids=client_ids, staleness=staleness, spec=spec)

    @property
    def s(self) -> int:
        return self.data.shape[0]

    @property
    def d(self) -> int:
        return self.data.shape[1]

    def row_tree(self, i) -> pt.Pytree:
        return unflatten_tree(self.data[i], self.spec)

    def to_stacked_pytree(self) -> pt.Pytree:
        """Inverse of :func:`stack_updates` — the oracle-parity bridge."""
        out, off = [], 0
        for shp, dt, n in zip(self.spec.shapes, self.spec.dtypes, self.spec.sizes):
            out.append(
                self.data[:, off : off + n].reshape((self.s,) + shp).astype(dt)
            )
            off += n
        return jax.tree.unflatten(self.spec.treedef, out)


def stack_updates(
    stacked: pt.Pytree, client_ids=None, staleness=None
) -> UpdateStack:
    """THE flatten boundary: stacked update pytree -> :class:`UpdateStack`."""
    data = flatten_stacked(stacked)
    s = data.shape[0]
    if client_ids is None:
        client_ids = jnp.arange(s, dtype=jnp.int32)
    if staleness is None:
        staleness = jnp.zeros((s,), jnp.int32)
    return UpdateStack(
        data=data,
        client_ids=jnp.asarray(client_ids, jnp.int32),
        staleness=jnp.asarray(staleness, jnp.int32),
        spec=stacked_spec_of(stacked),
    )
