"""Inputs of the benchmark, all made from seeds: weights, the synthetic
image set and its Dirichlet partition, arrival latencies.

Nothing here imports the system under test: the reference
(``bench/reference.py``) and the program receive the same arrays from
these functions.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def subseed(seed: int, salt: int) -> int:
    """A 31-bit seed for one purpose, derived from the run's ``--seed``
    (any whole number, large ones included) and a salt."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), int(salt)])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


# ------------------------------------------------------------- weights
def param_shapes(config: dict) -> dict:
    """{leaf name: shape} of the model the configuration describes."""
    shapes = {}
    for layer in config["layers"]:
        if "conv" in layer:
            shapes[layer["w"]] = tuple(layer["conv"])
            shapes[layer["b"]] = (layer["conv"][-1],)
        elif "dense" in layer:
            shapes[layer["w"]] = tuple(layer["dense"])
            shapes[layer["b"]] = (layer["dense"][-1],)
    return shapes


def init_params(config: dict, seed: int) -> dict:
    """Weights from the seed in one jitted call on the device: normal
    weights scaled by 1/sqrt(fan-in), zero biases (the paper's CNN
    recipe, ``models/cnn.py`` uses the same scales)."""
    shapes = param_shapes(config)
    names = sorted(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape = shapes[name]
            if len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = math.prod(shape[:-1])
                out[name] = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        return out

    return make(jax.random.PRNGKey(subseed(seed, 1)))


# ------------------------------------------------------------- images
def image_set(config: dict, data_seed: int):
    """The synthetic stand-in for the configuration's image set: class
    prototypes (a 7x7 grid per class, upsampled) plus Gaussian noise,
    made on the device in one jitted call.  Returns (x [N, H, W, C] f32
    on the device, y [N] int32 on the host)."""
    n, (h, w, ch), n_classes = config["train_images"], config["input_shape"], config["n_classes"]
    y = np.random.RandomState(data_seed).randint(0, n_classes, size=n).astype(np.int32)

    @jax.jit
    def make(key, y):
        kp, kn = jax.random.split(key)
        coarse = jax.random.normal(kp, (n_classes, 7, 7, ch), jnp.float32)
        reps = (h + 6) // 7
        protos = jnp.repeat(jnp.repeat(coarse, reps, axis=1), reps, axis=2)[:, :h, :w, :]
        noise = jax.random.normal(kn, (n, h, w, ch), jnp.float32)
        return protos[y] + jnp.float32(config["image_sigma"]) * noise

    return make(jax.random.PRNGKey(data_seed), jnp.asarray(y)), y


def dirichlet_partition(labels: np.ndarray, n_workers: int, beta: float, seed: int,
                        min_per_worker: int = 2) -> tuple[list[np.ndarray], int]:
    """Label-skewed split (paper §VI): each class is divided over the
    workers in proportions drawn from Dir(beta).  A worker left with
    fewer than ``min_per_worker`` samples is topped up at random.
    Returns (index sets, number of workers topped up)."""
    rng = np.random.RandomState(seed)
    n_classes = int(labels.max()) + 1
    by_class = [np.where(labels == k)[0] for k in range(n_classes)]
    for idx in by_class:
        rng.shuffle(idx)
    owned: list[list[int]] = [[] for _ in range(n_workers)]
    for k in range(n_classes):
        p = rng.dirichlet([beta] * n_workers)
        counts = (p * len(by_class[k])).astype(int)
        for _ in range(len(by_class[k]) - counts.sum()):
            counts[rng.randint(n_workers)] += 1
        off = 0
        for j in range(n_workers):
            owned[j].extend(by_class[k][off: off + counts[j]])
            off += counts[j]
    parts, topped = [], 0
    everything = np.arange(len(labels))
    for j in range(n_workers):
        idx = np.array(sorted(owned[j]), dtype=np.int64)
        if len(idx) < min_per_worker:
            topped += 1
            extra = rng.choice(everything, size=min_per_worker - len(idx), replace=False)
            idx = np.concatenate([idx, extra])
        rng.shuffle(idx)
        parts.append(idx)
    return parts, topped


# ------------------------------------------------------------- arrivals
#: quantile grid of the arrival latencies: a latency is one of this many
#: equally likely values, so the program and the reference read the
#: same f32 number for a dispatch, bit for bit
LATENCY_GRID = 4096


def latency_table(mu: float, sigma: float, seed: int) -> np.ndarray:
    """[LATENCY_GRID] f32 lognormal(mu, sigma) quantiles at the grid's
    mid-points, permuted by the seed: every seed has the same set of
    latencies, in another order."""
    from statistics import NormalDist

    q = (np.arange(LATENCY_GRID) + 0.5) / LATENCY_GRID
    z = np.array([NormalDist().inv_cdf(v) for v in q])
    table = np.exp(mu + sigma * z).astype(np.float32)
    return table[np.random.RandomState(seed).permutation(LATENCY_GRID)]


class TableLatency:
    """Latency model for the program's hash-mode arrival plane: the
    dispatch's uniform draw ``u`` (24 bits) picks an entry of the table.
    Only exact operations, so the value is the same on any device."""

    def __init__(self, table: np.ndarray):
        self.table = jnp.asarray(table, jnp.float32)

    def icdf(self, u, client_id):
        del client_id
        idx = (jnp.asarray(u, jnp.float32) * jnp.float32(LATENCY_GRID)).astype(jnp.int32)
        return self.table[idx]
