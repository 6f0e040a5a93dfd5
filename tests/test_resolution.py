"""The §3.1 data-resolution protocol: alignment invariants (claim C1)."""
import numpy as np
import pytest
from repro.testing.hypo import given, settings, strategies as st

from repro.core.resolution import VerticalDataset, resolve
from repro.core.vertical import (make_ids, partition_features,
                                 partition_sequence, scatter_to_owners,
                                 unpartition)

GROUP = "modp512"


def _setup(n, keep, seed, n_owners=2):
    rng = np.random.default_rng(seed)
    ids = make_ids(n)
    X = rng.normal(size=(n, 4 * n_owners)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    slices = partition_features(X, n_owners)
    raw = scatter_to_owners(ids, slices, rng, keep)
    sci = VerticalDataset(ids, y)
    owners = {f"o{i}": VerticalDataset(i_, d_) for i, (i_, d_) in
              enumerate(raw)}
    return ids, X, y, slices, sci, owners


@given(st.integers(20, 120), st.floats(0.5, 1.0), st.integers(0, 10_000))
@settings(max_examples=15)
def test_resolution_aligns_all_parties(n, keep, seed):
    ids, X, y, slices, sci, owners = _setup(n, keep, seed)
    s_al, o_al, stats = resolve(sci, owners, group=GROUP)
    # identical ID order everywhere
    for ds in o_al.values():
        assert ds.ids == s_al.ids
    # aligned rows reconstruct the original subjects exactly
    idx = [ids.index(i) for i in s_al.ids]
    np.testing.assert_array_equal(s_al.data, y[idx])
    for k, ds in o_al.items():
        p = int(k[1:])
        np.testing.assert_array_equal(ds.data, slices[p][idx])
    # global intersection is exactly the set intersection
    expect = set(ids)
    for ds in owners.values():
        expect &= set(ds.ids)
    assert stats["global_intersection"] == len(expect)
    assert len(s_al.ids) == len(expect)


def test_three_owners():
    ids, X, y, slices, sci, owners = _setup(60, 0.8, 3, n_owners=3)
    s_al, o_al, _ = resolve(sci, owners, group=GROUP)
    assert len(o_al) == 3
    for ds in o_al.values():
        assert ds.ids == s_al.ids


def test_duplicate_ids_rejected():
    with pytest.raises(ValueError):
        VerticalDataset(["a", "a"], np.zeros((2, 1)))


@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 1000))
@settings(max_examples=25)
def test_partition_unpartition_roundtrip(n_owners, per_owner, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(7, n_owners * per_owner)).astype(np.float32)
    np.testing.assert_array_equal(
        unpartition(partition_features(x, n_owners)), x)
    t = rng.integers(0, 100, size=(3, n_owners * per_owner))
    np.testing.assert_array_equal(
        unpartition(partition_sequence(t, n_owners), axis=1), t)


def test_partition_rejects_indivisible():
    with pytest.raises(ValueError):
        partition_features(np.zeros((2, 7)), 2)
