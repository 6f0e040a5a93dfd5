"""Data pipeline: determinism, alignment, learnable structure."""
import numpy as np
import pytest
from repro.testing.hypo import given, settings, strategies as st

from repro.data import (batches, make_mnist_like, make_token_dataset,
                        make_vertical_mnist_parties)


def test_mnist_like_shapes_and_range():
    X, y = make_mnist_like(100, seed=0)
    assert X.shape == (100, 784) and y.shape == (100,)
    assert X.min() >= 0.0 and X.max() <= 1.0
    assert set(np.unique(y)) <= set(range(10))


def test_mnist_like_deterministic():
    X1, y1 = make_mnist_like(50, seed=3)
    X2, y2 = make_mnist_like(50, seed=3)
    np.testing.assert_array_equal(X1, X2)
    np.testing.assert_array_equal(y1, y2)


def test_mnist_like_classes_separable_by_mean():
    """Class structure exists: per-class mean images differ measurably."""
    X, y = make_mnist_like(2000, seed=1)
    means = np.stack([X[y == c].mean(0) for c in range(10)])
    dists = np.linalg.norm(means[:, None] - means[None], axis=-1)
    np.fill_diagonal(dists, np.inf)
    assert dists.min() > 0.5


def test_vertical_parties_have_partial_overlap():
    sci, owners = make_vertical_mnist_parties(200, seed=0, keep_frac=0.7)
    assert len(sci.ids) == 200
    for ds in owners.values():
        assert 80 < len(ds.ids) < 200         # true subsets
        assert ds.data.shape[1] == 392        # half images


def test_token_dataset_has_learnable_structure():
    """Order-2 Markov structure: the same (t-1, t-2) context predicts the
    same next token most of the time."""
    toks = make_token_dataset(64, 128, vocab=97, seed=0)
    assert toks.shape == (64, 129)
    hits = total = 0
    from collections import Counter, defaultdict
    ctx = defaultdict(Counter)
    for row in toks[:32]:
        for j in range(2, len(row)):
            ctx[(row[j - 1], row[j - 2])][row[j]] += 1
    for c, counter in ctx.items():
        n = sum(counter.values())
        if n >= 3:
            hits += counter.most_common(1)[0][1]
            total += n
    assert total > 0 and hits / total > 0.6


@given(st.integers(10, 100), st.integers(1, 16))
@settings(max_examples=20)
def test_batches_partition_without_duplication(n, bs):
    data = {"x": np.arange(n)}
    seen = []
    for b in batches(data, bs, seed=0, epochs=1):
        seen.extend(b["x"].tolist())
    assert len(seen) == len(set(seen)) == n - (n % bs)


def test_batches_seeded_shuffle_deterministic():
    data = {"x": np.arange(64)}
    a = [b["x"].tolist() for b in batches(data, 8, seed=5)]
    b = [b["x"].tolist() for b in batches(data, 8, seed=5)]
    assert a == b
