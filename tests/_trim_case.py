"""The graph and checks of the evaluation's trimmed last layer
(``SPMDEngine._eval_trim``), shared by ``test_eval_trim.py`` and the
four-device mesh script of ``test_engine_parity.py``.

The graph: 4,096 nodes under a P=4 EW partition, about 20 in-edges a node,
mostly from nearby ids, so every partition holds several 128-row node
blocks and a halo.  Node 0 is a hub with 600 in-edges from across the
graph; node 1 has none.  Its ``val`` split is a random 2%.  Its ``test``
split is set row by row on the partitioned graph so that it holds: the hub
(its in-edges span at least 3 chunks of the trimmed layout), node 1, a row
whose in-neighbours include halo rows, the first and the last owned row of
a partition (its first and last node block), a random 2% elsewhere, and no
row of one partition.
"""
import os
import sys

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

P = 4
N = 4096
HUB, LONE = 0, 1


def build_trim_graph():
    """``(graph, pg, empty)``: the graph, its partition with the ``test``
    split set as above, and the partition that holds no ``test`` row."""
    from repro.core import partition_graph
    from repro.graph import CSRGraph, build_partitioned_graph

    rng = np.random.default_rng(0)
    deg = rng.poisson(20, N)
    deg[HUB], deg[LONE] = 600, 0
    dst = np.repeat(np.arange(N), deg)
    near = (dst + rng.integers(-64, 65, dst.size)) % N
    src = np.where(rng.random(dst.size) < 0.9, near,
                   rng.integers(0, N, dst.size))
    src[dst == HUB] = rng.choice(N, 600, replace=False)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    indptr = np.zeros(N + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=N), out=indptr[1:])
    labels = rng.integers(0, 5, N)
    feats = rng.normal(0, 1.0, (N, 8)).astype(np.float32)
    feats[:, :5] += np.eye(5, dtype=np.float32)[labels]
    perm = rng.permutation(N)
    g = CSRGraph(indptr=indptr, indices=src.astype(np.int64), features=feats,
                 labels=labels, train_idx=np.sort(perm[:N // 2]),
                 val_idx=np.sort(perm[N // 2:N // 2 + N // 50]),
                 test_idx=np.sort(perm[N // 2 + N // 50:]), num_classes=5,
                 name="trim")
    parts = np.asarray(partition_graph(g.indptr, g.indices, g.features,
                                       g.labels, P, method="ew",
                                       seed=0).parts)
    pg = build_partitioned_graph(g, parts, P)

    test = np.zeros_like(pg.test_mask)
    owner = {v: int(parts[v]) for v in (HUB, LONE)}
    row = {v: int(np.flatnonzero(pg.global_ids[owner[v]] == v)[0])
           for v in (HUB, LONE)}
    empty = max(set(range(P)) - set(owner.values()))
    for p in range(P):
        if p == empty:
            continue
        n_own = int(pg.n_own[p])
        test[p, rng.choice(n_own, max(1, n_own // 50), replace=False)] = True
        test[p, [0, n_own - 1]] = True
    for v in (HUB, LONE):
        test[owner[v], row[v]] = True
    # a row whose in-neighbours include halo rows (a boundary row)
    p = next(q for q in range(P) if q != empty)
    real = pg.edge_mask[p] > 0
    halo_dst = pg.edge_dst[p][real][pg.edge_src[p][real] >= pg.n_own[p]]
    test[p, halo_dst[0]] = True
    pg.test_mask = test
    return g, pg, empty


def logits_of(eng, fwd, shards, params):
    """``fwd`` over every partition, as the engine's mode runs it:
    (P, maxN, C)."""
    import jax
    from jax.sharding import PartitionSpec as PS

    from repro.engine import AXIS

    if eng.mode == "spmd":
        run = jax.shard_map(
            lambda p, s: fwd(p, jax.tree.map(lambda x: x[0], s))[None],
            mesh=eng._mesh, in_specs=(PS(), PS(AXIS)), out_specs=PS(AXIS),
            check_vma=False)
    else:
        run = jax.vmap(fwd, in_axes=(None, 0), axis_name=AXIS)
    return np.asarray(jax.jit(run)(params, shards))


def trim_gaps(eng, params, split: str) -> dict:
    """The trimmed evaluate of ``split`` against the untrimmed forward on
    every split row: the largest logit gap over the largest logit, rows
    whose class differs, ``perfbench.check.eval_gap`` of the trimmed
    classes against the untrimmed logits, and non-split rows not reading
    -1."""
    from perfbench.check import eval_gap

    trim = eng._eval_trim(split)[0]
    assert trim is not None, "the trim did not engage"
    _, preds = eng.evaluate(params, split, per_partition_params=False)
    preds = np.asarray(preds)
    full = logits_of(eng, eng.fwd, eng.shards, params)
    fwd, shards = eng._eval_fwd(trim)
    trimmed = logits_of(eng, fwd, shards, params)
    m = np.asarray(eng.masks[split])
    return {"logit_rel": float(np.abs(trimmed[m] - full[m]).max()
                               / np.abs(full[m]).max()),
            "class_diff": int((preds[m] != full[m].argmax(-1)).sum()),
            "eval_gap": eval_gap([preds[m]], [full[m]]),
            "not_minus_one": int((preds[~m] != -1).sum())}
