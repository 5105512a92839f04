"""The benchmark's own graph generator: a fixed copy of the program's
``graph/synthetic.py:make_benchmark`` (degree-corrected stochastic block
model with Zipf class sizes, homophily and class-prototype features), so a
later change to the program cannot move a cell's input graph.

One departure from the copied code: ``ood_test`` is a setting of the
configuration file like every other, and the configurations here turn it
off (the OOD ordering leaves one class in the training split at the
benchmark's node counts).

Imports nothing of the program; returns plain NumPy arrays.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import scipy.sparse as sp

__all__ = ["Graph", "GRAPH_KEYS", "generate"]

# the configuration keys the generator reads, with their defaults
GRAPH_KEYS = {
    "num_nodes": None, "avg_degree": None, "num_classes": None,
    "feature_dim": None, "class_zipf": 1.2, "homophily": 0.8,
    "feature_noise": 0.5, "degree_alpha": 0.8, "train_frac": 0.5,
    "val_frac": 0.2, "labelled_frac": 1.0, "ood_test": False,
    "graph_seed": 0,
}


@dataclass
class Graph:
    """CSR of in-neighbours (row v lists the message sources of v)."""

    indptr: np.ndarray           # (n+1,) int64
    indices: np.ndarray          # (nnz,) int64
    features: np.ndarray         # (n, d) float32
    labels: np.ndarray           # (n,) int64, -1 = unlabelled
    train_idx: np.ndarray
    val_idx: np.ndarray
    test_idx: np.ndarray
    num_classes: int

    @property
    def num_nodes(self) -> int:
        return len(self.indptr) - 1

    @property
    def num_edges(self) -> int:
        return len(self.indices)


def generate(cfg: dict) -> Graph:
    spec = {k: cfg.get(k, v) for k, v in GRAPH_KEYS.items()}
    missing = [k for k, v in spec.items() if v is None]
    if missing:
        raise ValueError(f"configuration lacks graph keys {missing}")
    rng = np.random.default_rng([int(spec["graph_seed"]), 0x5EED])
    n, k = int(spec["num_nodes"]), int(spec["num_classes"])
    d = int(spec["feature_dim"])

    ranks = np.arange(1, k + 1, dtype=np.float64)
    class_p = ranks ** (-float(spec["class_zipf"]))
    class_p /= class_p.sum()
    labels = rng.choice(k, size=n, p=class_p).astype(np.int64)

    protos = rng.normal(0.0, 1.0, size=(k, d))
    protos /= np.linalg.norm(protos, axis=1, keepdims=True)
    feats = protos[labels] + rng.normal(0.0, float(spec["feature_noise"]),
                                        (n, d))
    feats = feats.astype(np.float32)

    prop = (1.0 / (np.arange(n) + 1.0)) ** float(spec["degree_alpha"])
    rng.shuffle(prop)
    num_edges = int(n * float(spec["avg_degree"]))

    by_class = [np.flatnonzero(labels == c) for c in range(k)]
    w_by_class = [prop[idx] / prop[idx].sum() for idx in by_class]
    w_all = prop / prop.sum()

    src = rng.choice(n, size=num_edges, p=w_all)
    homo = rng.random(num_edges) < float(spec["homophily"])
    dst = np.empty(num_edges, dtype=np.int64)
    for c in range(k):
        m = homo & (labels[src] == c)
        cnt = int(m.sum())
        if cnt and len(by_class[c]):
            dst[m] = rng.choice(by_class[c], size=cnt, p=w_by_class[c])
        elif cnt:
            dst[m] = rng.choice(n, size=cnt, p=w_all)
    nh = ~homo
    dst[nh] = rng.choice(n, size=int(nh.sum()), p=w_all)
    keep = src != dst
    src, dst = src[keep], dst[keep]

    a = sp.csr_matrix(
        (np.ones(2 * len(src)),
         (np.concatenate([src, dst]), np.concatenate([dst, src]))),
        shape=(n, n))
    a.data[:] = 1.0
    a.setdiag(0)
    a.eliminate_zeros()

    perm = rng.permutation(n)
    labelled = perm[: int(n * float(spec["labelled_frac"]))]
    final_labels = np.full(n, -1, dtype=np.int64)
    final_labels[labelled] = labels[labelled]
    if spec["ood_test"]:
        head_score = class_p[labels[labelled]]
        noise = rng.random(len(labelled)) * float(class_p.max())
        order = labelled[np.argsort(-(head_score + noise))]
    else:
        order = labelled
    n_lab = len(labelled)
    n_tr = int(n_lab * float(spec["train_frac"]))
    n_va = int(n_lab * float(spec["val_frac"]))
    return Graph(
        indptr=a.indptr.astype(np.int64), indices=a.indices.astype(np.int64),
        features=feats, labels=final_labels,
        train_idx=np.sort(order[:n_tr]),
        val_idx=np.sort(order[n_tr: n_tr + n_va]),
        test_idx=np.sort(order[n_tr + n_va:]), num_classes=k)
