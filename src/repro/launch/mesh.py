"""Production mesh construction (assignment §MULTI-POD DRY-RUN).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharded ingest's scatter and
    gather rely on GSPMD propagation, which Explicit axes (the default
    since jax 0.7) reject with a ``ShardingTypeError``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(devices=None):
    """Small mesh over whatever local devices exist (CPU tests)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    d = 2 if n % 2 == 0 and n > 1 else 1
    return _auto_mesh((d, n // d), ("data", "model"), devices[: d * (n // d)])


def make_pod_mesh(n_pods: int, devices=None):
    """1-D ``("pod",)`` mesh for the sharded ingest buffer
    (``repro.stream.sharded``): one pod per device, rows = clients shard
    over it.  Uses the first ``n_pods`` local devices."""
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_pods:
        raise ValueError(
            f"need {n_pods} devices for {n_pods} pods, have {len(devices)}"
        )
    return _auto_mesh((n_pods,), ("pod",), devices[:n_pods])


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh) -> int:
    n = 1
    for a in batch_axes_of(mesh):
        n *= mesh.shape[a]
    return n
