"""Data substrate tests: synthetic datasets, Dirichlet skew, pipeline."""
import numpy as np
import pytest

from repro.data.dirichlet import dirichlet_partition, heterogeneity_stats
from repro.data.pipeline import build_federated_data
from repro.data.synthetic import SPECS, make_image_dataset, synth_token_batch


class TestSynthetic:
    @pytest.mark.parametrize("name", ["emnist", "cifar10", "cifar100"])
    def test_shapes_and_classes(self, name):
        spec = SPECS[name]
        d = make_image_dataset(spec, seed=0)
        x, y = d["train"]
        assert x.shape == (spec.n_train,) + spec.shape
        assert y.min() >= 0 and y.max() == spec.n_classes - 1

    def test_deterministic(self):
        a = make_image_dataset(SPECS["cifar10"], seed=5)
        b = make_image_dataset(SPECS["cifar10"], seed=5)
        np.testing.assert_array_equal(a["train"][0], b["train"][0])

    def test_learnable_structure(self):
        """A nearest-prototype classifier must beat chance by a wide margin
        (otherwise FL accuracy curves would be meaningless)."""
        from repro.data.synthetic import class_prototypes

        spec = SPECS["cifar10"]
        d = make_image_dataset(spec, seed=0)
        x, y = d["test"]
        protos = class_prototypes(spec, seed=0).reshape(spec.n_classes, -1)
        xf = x[:500].reshape(500, -1)
        pred = np.argmin(
            ((xf[:, None, :] - protos[None]) ** 2).sum(-1), axis=1
        )
        acc = (pred == y[:500]).mean()
        assert acc > 0.8

    def test_token_batch(self):
        import jax

        b = synth_token_batch(jax.random.PRNGKey(0), 4, 32, 101)
        assert b["tokens"].shape == (4, 32)
        assert b["targets"].shape == (4, 32)
        assert int(b["tokens"].max()) < 101

    @pytest.mark.parametrize("seq", [1, 2, 17])
    def test_token_batch_scan_equals_the_step_loop(self, seq):
        """The scanned sequence is the one the per-position loop draws
        from the same keys, token for token."""
        import jax
        import jax.numpy as jnp

        key, batch, vocab = jax.random.PRNGKey(3), 3, 101
        k1, k2 = jax.random.split(key)
        toks = [jax.random.randint(k1, (batch, 1), 0, vocab)]
        keys = jax.random.split(k2, seq)
        for i in range(seq - 1):
            tok = toks[-1]
            noise = jax.random.bernoulli(keys[i], 0.1, tok.shape)
            rand = jax.random.randint(keys[i], tok.shape, 0, vocab)
            toks.append(jnp.where(noise, rand, (tok * 31 + 17) % vocab))
        want = jnp.concatenate(toks, axis=1)
        got = synth_token_batch(key, batch, seq, vocab)
        np.testing.assert_array_equal(got["tokens"], want)
        np.testing.assert_array_equal(got["targets"],
                                      jnp.concatenate([want[:, 1:], want[:, :1]], axis=1))

    def test_topic_tokens_split_by_topic_with_a_balanced_root(self):
        from repro.data.pipeline import build_federated_data

        data = build_federated_data("topics", 6, 0.1, malicious_fraction=0.5, seed=2,
                                    seq_len=12, vocab=97, root_samples=16)
        assert data.x.shape[1:] == data.y.shape[1:] == (12,)
        np.testing.assert_array_equal(data.x[:, 1:], data.y[:, :-1])
        assert int(data.x.max()) < 97 and data.malicious.sum() == 3
        rng = np.random.RandomState(0)
        batch = data.sample_round(rng, np.array([0, 3]), 2, 1)
        assert batch["x"].shape == batch["y"].shape == (2, 2, 1, 12)
        root = data.root_batches(rng, 2, 4, 16)
        assert root["x"].shape == (2, 4, 12)
        assert set(np.concatenate(data.parts)).isdisjoint(data.root_pool)


class TestDirichlet:
    def test_smaller_beta_more_skew(self):
        rng = np.random.RandomState(0)
        labels = rng.randint(0, 10, size=5000)
        tv_01 = heterogeneity_stats(labels, dirichlet_partition(labels, 20, 0.1, 0))[
            "mean_tv_distance"
        ]
        tv_50 = heterogeneity_stats(labels, dirichlet_partition(labels, 20, 5.0, 0))[
            "mean_tv_distance"
        ]
        assert tv_01 > tv_50 + 0.1

    def test_partition_covers_all(self):
        labels = np.random.RandomState(1).randint(0, 5, size=1000)
        parts = dirichlet_partition(labels, 8, 0.5, seed=2)
        covered = np.sort(np.concatenate(parts))
        assert len(np.unique(covered)) >= 995  # min_per_worker may duplicate a few


class TestPipeline:
    def test_malicious_marking(self):
        data = build_federated_data(
            "cifar10", 40, 0.1, malicious_fraction=0.3, attack="sign_flipping", seed=0
        )
        assert data.malicious.sum() == 12
        assert data.attack == "sign_flipping"

    def test_round_sampling_deterministic_given_rng(self):
        data = build_federated_data("cifar10", 10, 0.5, seed=0)
        b1 = data.sample_round(np.random.RandomState(3), [0, 1], 2, 4)
        b2 = data.sample_round(np.random.RandomState(3), [0, 1], 2, 4)
        np.testing.assert_array_equal(b1["x"], b2["x"])


class TestLabelFlipping:
    """ISSUE satellite: the data-space attack flows end to end from
    ``core.attacks.flip_labels`` through the pipeline into the batches a
    malicious client trains on."""

    def _paired(self, flip_fraction):
        import dataclasses

        clean = build_federated_data("cifar10", 6, 0.5, seed=0)
        poisoned = dataclasses.replace(
            build_federated_data(
                "cifar10", 6, 0.5, malicious_fraction=0.5,
                attack="label_flipping", seed=0,
            ),
            flip_fraction=flip_fraction,
        )
        # same seed -> identical underlying data and partitions
        np.testing.assert_array_equal(clean.y, poisoned.y)
        return clean, poisoned

    def test_malicious_clients_train_on_flipped_labels(self):
        """With flip_fraction=1 a malicious client's sampled labels are
        EXACTLY L - l - 1 of the clean pipeline's labels; x untouched."""
        clean, poisoned = self._paired(flip_fraction=1.0)
        mal = int(np.where(poisoned.malicious)[0][0])
        b_clean = clean.sample_round(np.random.RandomState(7), [mal], 3, 5)
        b_mal = poisoned.sample_round(np.random.RandomState(7), [mal], 3, 5)
        np.testing.assert_array_equal(b_clean["x"], b_mal["x"])
        np.testing.assert_array_equal(
            b_mal["y"], poisoned.n_classes - b_clean["y"] - 1
        )

    @pytest.mark.slow
    def test_benign_clients_and_root_data_unaffected(self):
        clean, poisoned = self._paired(flip_fraction=1.0)
        ben = int(np.where(~poisoned.malicious)[0][0])
        b_clean = clean.sample_round(np.random.RandomState(9), [ben], 2, 4)
        b_ben = poisoned.sample_round(np.random.RandomState(9), [ben], 2, 4)
        np.testing.assert_array_equal(b_clean["y"], b_ben["y"])
        root = poisoned.root_batches(np.random.RandomState(11), 2, 4, 500)
        assert root["y"].min() >= 0 and root["y"].max() < poisoned.n_classes

    @pytest.mark.slow
    def test_partial_flip_fraction(self):
        """The paper's 50% flip: about half the malicious samples move,
        and every moved label is the involutive L - l - 1 image."""
        clean, poisoned = self._paired(flip_fraction=0.5)
        mal = int(np.where(poisoned.malicious)[0][0])
        b_clean = clean.sample_round(np.random.RandomState(13), [mal], 5, 20)
        b_mal = poisoned.sample_round(np.random.RandomState(13), [mal], 5, 20)
        flipped = b_mal["y"] != b_clean["y"]
        # ~Binomial(100, .5) minus self-flips (l == L - l - 1 is impossible
        # for even n_classes); allow a wide seeded band
        assert 0.3 < flipped.mean() < 0.7
        np.testing.assert_array_equal(
            b_mal["y"][flipped], poisoned.n_classes - b_clean["y"][flipped] - 1
        )
