"""Federated data pipeline: per-worker datasets with deterministic batch
sampling, label-flipping poisoning for malicious workers, and the vetted
root dataset for BR-DRAG (paper §IV-B).

The pipeline produces, for a round, the stacked tensor
``[S, U, B, ...]`` consumed by the jitted federated round step —
S selected workers x U local steps x local batch B.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.attacks import flip_labels
from repro.data.dirichlet import dirichlet_partition
from repro.data.synthetic import SPECS, TOKEN_SPECS, make_image_dataset, topic_sequences


@dataclasses.dataclass
class FederatedData:
    x: np.ndarray  # full train images
    y: np.ndarray  # full train labels (possibly poisoned per worker at sample time)
    parts: list[np.ndarray]  # per-worker index sets
    test: tuple  # (x_test, y_test)
    n_classes: int
    malicious: np.ndarray  # bool [M] — workers under adversarial control
    attack: str = "none"  # none | noise_injection | sign_flipping | label_flipping
    flip_fraction: float = 0.5
    root_pool: np.ndarray | None = None  # indices of a server-held root set

    def sample_round(self, rng: np.random.RandomState, selected, u: int, b: int):
        """Returns dict(x=[S,U,B,...], y=[S,U,B]) for the selected workers."""
        xs, ys = [], []
        for m in selected:
            idx = self.parts[m]
            take = rng.choice(idx, size=u * b, replace=len(idx) < u * b)
            x = self.x[take].reshape(u, b, *self.x.shape[1:])
            y = self.y[take].reshape(u, b, *self.y.shape[1:]).copy()
            if self.malicious[m] and self.attack == "label_flipping":
                # label flipping on half the local samples (paper §VI-B),
                # through the canonical transform in ``core.attacks`` so
                # the data- and update-space attack semantics share one
                # definition (l -> L - l - 1)
                flip = rng.rand(u, b) < self.flip_fraction
                y = np.asarray(flip_labels(y, self.n_classes, flip), dtype=y.dtype)
            xs.append(x)
            ys.append(y)
        return {"x": np.stack(xs), "y": np.stack(ys).astype(np.int32)}

    def root_batches(self, rng: np.random.RandomState, u: int, b: int, n_root: int):
        """Vetted root batches [U, B, ...] drawn from trusted (benign) data."""
        if self.root_pool is not None:
            pool = self.root_pool
        else:
            benign = np.where(~self.malicious)[0]
            pool = np.concatenate([self.parts[m] for m in benign])
            pool = pool[: n_root] if len(pool) > n_root else pool
        take = rng.choice(pool, size=u * b, replace=len(pool) < u * b)
        return {
            "x": self.x[take].reshape(u, b, *self.x.shape[1:]),
            "y": self.y[take].reshape(u, b, *self.y.shape[1:]).astype(np.int32),
        }

    def test_batch(self, n: int = 1024):
        x, y = self.test
        return {"x": x[:n], "y": y[:n].astype(np.int32)}


def drift_labels(y: np.ndarray, n_classes: int, t: int, mode: str, rate: float):
    """Non-stationary label drift: the class identified by label ``l`` at
    time 0 is labelled ``(l + floor(rate * t)) mod C`` at time ``t`` — a
    slow rotation of the label space (concept drift), applied identically
    to train, root, and eval batches so the task stays self-consistent at
    every instant while the decision boundary a fixed model learned goes
    stale.  ``mode="none"`` or ``rate<=0`` is the identity."""
    if mode == "none" or rate <= 0.0:
        return y
    shift = int(rate * t) % n_classes
    if shift == 0:
        return y
    return ((y.astype(np.int64) + shift) % n_classes).astype(y.dtype)


def build_federated_data(
    dataset: str,
    n_workers: int,
    beta: float,
    malicious_fraction: float = 0.0,
    attack: str = "none",
    seed: int = 0,
    seq_len: int = 0,
    vocab: int = 0,
    root_samples: int = 0,
) -> FederatedData:
    """A dataset split over ``n_workers`` by Dirichlet(beta) label skew.
    A token dataset (``seq_len`` tokens of ``vocab`` ids per sequence)
    splits by topic and holds a root set of ``root_samples`` sequences,
    every topic equally."""
    if dataset in TOKEN_SPECS:
        return _token_data(TOKEN_SPECS[dataset], n_workers, beta, malicious_fraction,
                           seed, seq_len, vocab, root_samples)
    spec = SPECS[dataset]
    data = make_image_dataset(spec, seed)
    x, y = data["train"]
    parts = dirichlet_partition(y, n_workers, beta, seed)
    malicious = _malicious(n_workers, malicious_fraction, seed)
    return FederatedData(
        x=x,
        y=y,
        parts=parts,
        test=data["test"],
        n_classes=spec.n_classes,
        malicious=malicious,
        attack=attack,
        flip_fraction=0.5,
    )


def _malicious(n_workers: int, fraction: float, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed + 7)
    malicious = np.zeros(n_workers, dtype=bool)
    n_mal = int(round(fraction * n_workers))
    if n_mal:
        malicious[rng.choice(n_workers, size=n_mal, replace=False)] = True
    return malicious


def _token_data(spec, n_workers, beta, malicious_fraction, seed, seq_len, vocab,
                root_samples) -> FederatedData:
    rng = np.random.RandomState(seed + 1)
    train_topics = rng.randint(0, spec.n_topics, size=spec.n_train)
    root_topics = np.arange(root_samples) % spec.n_topics
    test_topics = np.arange(spec.n_test) % spec.n_topics
    seqs = topic_sequences(rng, np.concatenate([train_topics, root_topics, test_topics]),
                           seq_len, vocab, spec, seed)
    n_fit = spec.n_train + root_samples
    return FederatedData(
        x=seqs[:n_fit, :-1],
        y=seqs[:n_fit, 1:],
        parts=dirichlet_partition(train_topics, n_workers, beta, seed),
        test=(seqs[n_fit:, :-1], seqs[n_fit:, 1:]),
        n_classes=vocab,
        malicious=_malicious(n_workers, malicious_fraction, seed),
        root_pool=np.arange(spec.n_train, n_fit),
    )
