import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.entropy import label_entropy, partition_entropies, partition_stats


def test_uniform_labels_max_entropy():
    labels = np.repeat(np.arange(8), 100)
    assert label_entropy(labels) == pytest.approx(np.log(8), abs=1e-9)


def test_single_class_zero_entropy():
    assert label_entropy(np.zeros(100, dtype=int)) == 0.0


def test_unlabelled_ignored():
    labels = np.array([0, 0, 1, 1, -1, -1, -1])
    assert label_entropy(labels) == pytest.approx(np.log(2))


def test_empty():
    assert label_entropy(np.array([], dtype=int)) == 0.0
    assert label_entropy(np.full(10, -1)) == 0.0


@given(st.lists(st.integers(0, 9), min_size=1, max_size=300))
@settings(max_examples=50, deadline=None)
def test_entropy_bounds(labels):
    """0 <= H <= log(num_classes) for any label multiset."""
    h = label_entropy(np.array(labels), num_classes=10)
    assert -1e-12 <= h <= np.log(10) + 1e-12


@given(st.integers(2, 6), st.integers(20, 200))
@settings(max_examples=30, deadline=None)
def test_partition_entropies_shape_and_bounds(num_parts, n):
    rng = np.random.default_rng(n)
    labels = rng.integers(0, 4, n)
    parts = rng.integers(0, num_parts, n)
    ents = partition_entropies(labels, parts, num_parts, 4)
    assert ents.shape == (num_parts,)
    assert (ents >= 0).all() and (ents <= np.log(4) + 1e-12).all()


def test_partition_stats_cut_counts():
    # path graph 0-1-2-3, split {0,1} {2,3}: cut edges = (1,2),(2,1) = 2
    indptr = np.array([0, 1, 3, 5, 6])
    indices = np.array([1, 0, 2, 1, 3, 2])
    labels = np.array([0, 0, 1, 1])
    parts = np.array([0, 0, 1, 1])
    s = partition_stats(indptr, indices, labels, parts, 2)
    assert s.edge_cut == 2
    assert s.entropies.tolist() == [0.0, 0.0]
    assert s.balance == 1.0


def test_partition_stats_weighted_by_labelled_counts():
    """Unlabelled mass must not skew the weighted aggregates: a partition
    that is mostly unlabelled (papers-like) contributes by its LABELLED
    count, so stats match a graph with the unlabelled nodes deleted."""
    # partition 0: 4 labelled nodes (classes 0,1), 96 unlabelled
    # partition 1: 40 labelled nodes (class 0 only), 0 unlabelled
    labels = np.concatenate([
        np.array([0, 0, 1, 1]), np.full(96, -1), np.zeros(40, dtype=int)])
    parts = np.concatenate([np.zeros(100, dtype=int), np.ones(40, dtype=int)])
    n = len(labels)
    indptr = np.arange(n + 1)          # ring: node i -> (i+1) % n
    indices = (np.arange(n) + 1) % n
    s = partition_stats(indptr, indices, labels, parts, 2, num_classes=2)
    assert s.sizes.tolist() == [100, 40]
    assert s.labelled_sizes.tolist() == [4, 40]
    assert s.entropies[0] == pytest.approx(np.log(2))
    assert s.entropies[1] == 0.0
    # total: 4 * log2 + 40 * 0 — NOT 100 * log2
    assert s.total_entropy == pytest.approx(4 * np.log(2))
    # variance weights: 4/44 and 40/44
    mean_h = s.entropies.mean()
    want_var = ((s.entropies - mean_h) ** 2 * np.array([4, 40]) / 44).sum()
    assert s.entropy_variance == pytest.approx(want_var)
    # dropping the unlabelled nodes entirely must give the same aggregates
    keep = labels >= 0
    lab2, parts2 = labels[keep], parts[keep]
    m = len(lab2)
    s2 = partition_stats(np.arange(m + 1), (np.arange(m) + 1) % m,
                         lab2, parts2, 2, num_classes=2)
    assert s2.total_entropy == pytest.approx(s.total_entropy)
    assert s2.entropy_variance == pytest.approx(s.entropy_variance)


def test_partition_stats_all_unlabelled_partition():
    """A fully-unlabelled partition has zero weight, not its node count."""
    labels = np.array([0, 1, -1, -1, -1])
    parts = np.array([0, 0, 1, 1, 1])
    indptr = np.arange(6)
    indices = (np.arange(5) + 1) % 5
    s = partition_stats(indptr, indices, labels, parts, 2, num_classes=2)
    assert s.labelled_sizes.tolist() == [2, 0]
    assert s.total_entropy == pytest.approx(2 * np.log(2))
