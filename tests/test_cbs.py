import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.entropy import label_entropy
from repro.core.sampler import CBSampler, cbs_probabilities


@pytest.fixture
def imbalanced(homophilous_graph):
    a, feats, labels = homophilous_graph
    train_idx = np.arange(len(labels))
    return a, labels, train_idx


def test_probabilities_normalized(imbalanced):
    a, labels, train_idx = imbalanced
    p = cbs_probabilities(a.indptr, a.indices, labels, train_idx)
    assert p.shape == train_idx.shape
    assert p.sum() == pytest.approx(1.0)
    assert (p >= 0).all()


def test_minority_oversampled(imbalanced):
    """CBS must raise the sampling frequency of the rarest class above its
    population share — the class-balancing claim."""
    a, labels, train_idx = imbalanced
    s = CBSampler(a.indptr, a.indices, labels, train_idx, batch_size=64, seed=0)
    dist = s.empirical_class_distribution(num_draws=20)
    pop = np.bincount(labels, minlength=5) / len(labels)
    rare = int(np.argmin(pop))
    assert dist[rare] > pop[rare] * 1.5


def test_sampled_entropy_higher_than_population(imbalanced):
    """Balanced sampling => label distribution entropy goes UP."""
    a, labels, train_idx = imbalanced
    s = CBSampler(a.indptr, a.indices, labels, train_idx, batch_size=64, seed=0)
    dist = s.empirical_class_distribution(num_draws=20)
    h_sampled = -(dist[dist > 0] * np.log(dist[dist > 0])).sum()
    assert h_sampled > label_entropy(labels)


def test_mini_epoch_smaller(imbalanced):
    """The 25% mini-epoch is what buys the paper its epoch-time speedup."""
    a, labels, train_idx = imbalanced
    s = CBSampler(a.indptr, a.indices, labels, train_idx,
                  batch_size=16, subset_fraction=0.25, seed=0)
    assert s.mini_epoch_size <= 0.25 * len(train_idx) + 16
    baseline = CBSampler(a.indptr, a.indices, labels, train_idx,
                         batch_size=16, subset_fraction=1.0,
                         class_balanced=False, seed=0)
    assert baseline.mini_epoch_size == len(train_idx)
    assert len(s.batches()) < len(baseline.batches())


def test_batches_cover_mini_epoch(imbalanced):
    a, labels, train_idx = imbalanced
    s = CBSampler(a.indptr, a.indices, labels, train_idx, batch_size=50, seed=0)
    batches = s.batches()
    total = sum(len(b) for b in batches)
    assert total == s.mini_epoch_size
    assert all(len(b) <= 50 for b in batches)


@given(st.integers(1, 1000))
@settings(max_examples=25, deadline=None)
def test_cbs_probabilities_properties(seed):
    """P(v) > 0 for every train node; rarest-class nodes beat the same-degree
    majority-class nodes."""
    rng = np.random.default_rng(seed)
    n = 60
    deg = rng.integers(1, 5, n)
    indptr = np.concatenate([[0], np.cumsum(deg)])
    indices = rng.integers(0, n, indptr[-1])
    labels = np.concatenate([np.zeros(50, int), np.ones(10, int)])
    rng.shuffle(labels)
    p = cbs_probabilities(indptr, indices, labels, np.arange(n))
    assert (p > 0).all()
    # mean probability of minority class exceeds majority
    assert p[labels == 1].mean() > p[labels == 0].mean()
