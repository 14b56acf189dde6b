"""Deterministic synthetic stand-ins for the paper's datasets.

The container is offline, so EMNIST/CIFAR-10/CIFAR-100 are replaced by
class-conditional Gaussian image generators with matching shapes and
class counts.  Each class k has a fixed random prototype mu_k; samples
are mu_k + sigma * noise, so (a) the Bayes classifier is learnable by the
paper's CNNs, (b) heterogeneity via Dirichlet label skew behaves exactly
as with real data, and (c) label flipping is semantically meaningful.

Token datasets for the LM architectures are Zipf-sampled integer
sequences with a deterministic next-token structure (a noisy affine map
over token ids) so LM training loss decreases.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ImageDatasetSpec:
    name: str
    shape: tuple  # (H, W, C)
    n_classes: int
    n_train: int
    n_test: int
    sigma: float = 0.35  # within-class noise (controls task difficulty)


EMNIST_SPEC = ImageDatasetSpec("emnist", (28, 28, 1), 47, 20000, 4000)
CIFAR10_SPEC = ImageDatasetSpec("cifar10", (32, 32, 3), 10, 20000, 4000)
CIFAR100_SPEC = ImageDatasetSpec("cifar100", (32, 32, 3), 100, 20000, 4000)
#: 10x-reduced emnist for sweep grids / CI smoke cells, where the host
#: data build must stay small next to a cell's compile cost
EMNIST_SMALL_SPEC = ImageDatasetSpec("emnist_small", (28, 28, 1), 47, 2000, 400)

SPECS = {
    s.name: s
    for s in (EMNIST_SPEC, CIFAR10_SPEC, CIFAR100_SPEC, EMNIST_SMALL_SPEC)
}


def class_prototypes(spec: ImageDatasetSpec, seed: int = 0) -> np.ndarray:
    rng = np.random.RandomState(seed)
    # low-frequency prototypes: upsampled coarse grids, more image-like
    coarse = rng.randn(spec.n_classes, 7, 7, spec.shape[2]).astype(np.float32)
    reps = (spec.shape[0] + 6) // 7
    protos = np.repeat(np.repeat(coarse, reps, axis=1), reps, axis=2)
    return protos[:, : spec.shape[0], : spec.shape[1], :]


def make_image_dataset(spec: ImageDatasetSpec, seed: int = 0):
    """Returns dict(train=(x, y), test=(x, y)) as numpy arrays."""
    rng = np.random.RandomState(seed + 1)
    protos = class_prototypes(spec, seed)

    def sample(n, rng):
        y = rng.randint(0, spec.n_classes, size=n).astype(np.int32)
        x = protos[y] + spec.sigma * rng.randn(n, *spec.shape).astype(np.float32)
        return x.astype(np.float32), y

    return {
        "train": sample(spec.n_train, rng),
        "test": sample(spec.n_test, np.random.RandomState(seed + 2)),
    }


# ------------------------------------------------------------ token data

def synth_token_batch(key, batch: int, seq: int, vocab: int):
    """Synthetic LM batch with learnable structure: t_{i+1} depends on t_i.
    One ``lax.scan`` over the sequence, step i drawing from the i-th of
    ``seq`` split keys."""
    k1, k2 = jax.random.split(key)
    first = jax.random.randint(k1, (batch, 1), 0, vocab)

    def step(tok, k):
        nxt = (tok * 31 + 17) % vocab
        noise = jax.random.bernoulli(k, 0.1, tok.shape)
        rand = jax.random.randint(k, tok.shape, 0, vocab)
        tok = jnp.where(noise, rand, nxt)
        return tok, tok

    keys = jax.random.split(k2, seq)
    _, rest = jax.lax.scan(step, first, keys[: seq - 1])  # [seq-1, batch, 1]
    tokens = jnp.concatenate([first, jnp.moveaxis(rest[..., 0], 0, 1)], axis=1)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    return {"tokens": tokens, "targets": targets}


@dataclasses.dataclass(frozen=True)
class TokenDatasetSpec:
    """Sequences drawn from seeded topics: topic k continues token t with
    (a_k t + c_k) mod V, and with probability ``noise`` with a uniform
    id.  A client's topics follow the Dirichlet split, as image classes
    do; the root set holds every topic equally."""

    name: str
    n_topics: int
    n_train: int  # sequences
    n_test: int
    noise: float = 0.1


TOPICS_SPEC = TokenDatasetSpec("topics", n_topics=8, n_train=1024, n_test=4)
TOKEN_SPECS = {TOPICS_SPEC.name: TOPICS_SPEC}


def topic_sequences(rng: np.random.RandomState, topics: np.ndarray, seq: int, vocab: int,
                    spec: TokenDatasetSpec, seed: int = 0) -> np.ndarray:
    """[N, seq + 1] int32 sequences, the n-th of topic ``topics[n]``
    (topic maps from ``seed``, the draws from ``rng``)."""
    maps = np.random.RandomState(seed + 3)
    mult = 2 * maps.randint(1, vocab // 2, size=spec.n_topics) + 1
    add = maps.randint(0, vocab, size=spec.n_topics)
    a, c = mult[topics].astype(np.int64), add[topics].astype(np.int64)
    out = np.empty((len(topics), seq + 1), np.int64)
    out[:, 0] = rng.randint(0, vocab, size=len(topics))
    noise = rng.rand(len(topics), seq) < spec.noise
    rand = rng.randint(0, vocab, size=(len(topics), seq))
    for i in range(seq):
        out[:, i + 1] = np.where(noise[:, i], rand[:, i], (a * out[:, i] + c) % vocab)
    return out.astype(np.int32)
