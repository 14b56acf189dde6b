"""Low-rank adapters (LoRA, Hu et al., arXiv 2106.09685) over a frozen
base: the adapted layer computes ``h = W x + (alpha / r) B (A x)``, with
A seeded Gaussian (scaled by 1/sqrt(fan-in)) and B zero, so a fresh
adapter leaves the base's output as it is.

The adapters are a pytree of their own, ``{"a", "b"}`` pairs at the
paths of the weights they adapt (with the same leading stack axes), so
they are what an FL client trains and uploads; :func:`merge` lays them
over the base inside the loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import Adapted

#: what a target name adapts in a ``models/transformer`` parameter tree
TARGETS = ("attn", "mlp", "shared")


def _target(path: tuple, node: dict) -> str | None:
    """The target a weight dict belongs to: ``attn`` for attention
    projections, ``shared`` for shared experts, ``mlp`` for a dense MLP
    (a routed-expert MLP, which holds a router, is never adapted)."""
    if path and path[-1] == "attn":
        return "attn"
    if path and path[-1] == "shared":
        return "shared"
    if path and path[-1] == "mlp" and "router" not in node:
        return "mlp"
    return None


def init_adapters(key, base: dict, targets, rank: int) -> dict:
    """Adapters for every matrix ``w*`` of the targeted weight dicts."""
    unknown = set(targets) - set(TARGETS)
    if unknown:
        raise ValueError(f"unknown LoRA targets {sorted(unknown)}; have {list(TARGETS)}")
    out: dict = {}
    counter = [0]

    def walk(node: dict, path: tuple, dst: dict):
        kind = _target(path, node)
        for name in sorted(node):
            leaf = node[name]
            if isinstance(leaf, dict):
                sub: dict = {}
                walk(leaf, path + (name,), sub)
                if sub:
                    dst[name] = sub
            elif kind in targets and name.startswith("w") and leaf.ndim >= 2:
                *lead, d_in, d_out = leaf.shape
                k = jax.random.fold_in(key, counter[0])
                counter[0] += 1
                a = jax.random.normal(k, (*lead, d_in, rank), jnp.float32) / math.sqrt(d_in)
                dst[name] = {"a": a.astype(leaf.dtype),
                             "b": jnp.zeros((*lead, rank, d_out), leaf.dtype)}

    walk(base, (), out)
    return out


def merge(base: dict, adapters: dict, scale: float) -> dict:
    """The base with each adapted weight replaced by ``Adapted(W, A,
    scale * B)``; untouched subtrees are shared, not copied."""
    if set(adapters) == {"a", "b"} and not isinstance(adapters["a"], dict):
        return Adapted(base, adapters["a"], adapters["b"] * scale)
    out = dict(base)
    for name, sub in adapters.items():
        out[name] = merge(base[name], sub, scale)
    return out
