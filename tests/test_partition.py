import numpy as np
import pytest
import scipy.sparse as sp
from hypothesis import given, settings, strategies as st

from repro.core.partition import assign_edge_weights, metis_kway, partition_graph
from repro.core.partition.api import METHODS


# ---------------------------------------------------------------- Alg. 1 ---

def test_edge_weights_positive_integer(homophilous_graph):
    a, feats, labels = homophilous_graph
    w = assign_edge_weights(a.indptr, a.indices, feats)
    assert w.dtype == np.int64
    assert (w >= 1).all()
    assert len(w) == a.nnz


def test_edge_weights_similar_features_heavier():
    """Two same-feature nodes must get a heavier edge than two orthogonal."""
    indptr = np.array([0, 2, 3, 4])
    indices = np.array([1, 2, 0, 0])   # node0 <- {1,2}; node1 <- 0; node2 <- 0
    feats = np.array([[1.0, 0.0], [1.0, 0.0], [0.0, 1.0]], np.float32)
    w = assign_edge_weights(indptr, indices, feats, c=1.0)
    w_same = w[0]      # edge (1 -> 0): identical features
    w_diff = w[1]      # edge (2 -> 0): orthogonal features
    assert w_same > w_diff


def test_edge_weights_low_degree_locality():
    """p = 1 - exp(-K/|N(v)|): low-degree destinations weigh in-edges higher."""
    # v=0 has 1 in-edge, v=1 has 4 in-edges; identical (orthogonal) features
    indptr = np.array([0, 1, 5])
    indices = np.array([1, 0, 0, 0, 0])
    feats = np.zeros((2, 4), np.float32)  # zero similarity everywhere
    w = assign_edge_weights(indptr, indices, feats, fanout_k=2)
    assert w[0] > w[1]


# ------------------------------------------------------------- partitioner --

@pytest.mark.parametrize("k", [2, 4, 8])
def test_metis_balance_and_cover(homophilous_graph, k):
    a, feats, labels = homophilous_graph
    parts = metis_kway(a, k, seed=0)
    assert parts.shape == (a.shape[0],)
    assert set(np.unique(parts)) <= set(range(k))
    sizes = np.bincount(parts, minlength=k)
    assert (sizes > 0).all()
    assert sizes.max() <= 1.06 * sizes.mean() + 1  # balance constraint


def test_metis_beats_random_cut(homophilous_graph):
    a, feats, labels = homophilous_graph
    rng = np.random.default_rng(1)
    parts_m = metis_kway(a, 4, seed=0)
    parts_r = rng.integers(0, 4, a.shape[0])
    src, dst = a.nonzero()
    cut_m = (parts_m[src] != parts_m[dst]).sum()
    cut_r = (parts_r[src] != parts_r[dst]).sum()
    assert cut_m < 0.7 * cut_r


@pytest.mark.parametrize("method", METHODS)
def test_partition_graph_all_methods(homophilous_graph, method):
    a, feats, labels = homophilous_graph
    r = partition_graph(a.indptr, a.indices, feats, labels, 4,
                        method=method, seed=0)
    assert len(r.parts) == a.shape[0]
    assert r.stats.num_parts == 4
    assert r.stats.avg_entropy >= 0


def test_ew_reduces_entropy_vs_random(homophilous_graph):
    """The paper's Table V claim, directionally: H(EW) < H(random)."""
    a, feats, labels = homophilous_graph
    r_ew = partition_graph(a.indptr, a.indices, feats, labels, 4,
                           method="ew", seed=0)
    r_rand = partition_graph(a.indptr, a.indices, feats, labels, 4,
                             method="random", seed=0)
    assert r_ew.stats.avg_entropy < r_rand.stats.avg_entropy


@given(st.integers(2, 5))
@settings(max_examples=8, deadline=None)
def test_metis_property_all_nodes_assigned(k):
    rng = np.random.default_rng(k)
    n = 120
    a = sp.random(n, n, density=0.05, random_state=int(k), format="csr")
    a = ((a + a.T) > 0).astype(np.float64).tocsr()
    a.setdiag(0)
    a.eliminate_zeros()
    parts = metis_kway(a, k, seed=k)
    assert parts.min() >= 0 and parts.max() < k
    assert len(parts) == n
