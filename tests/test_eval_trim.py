"""The evaluation's last GraphSAGE layer over the evaluated split's in-edges
only (``SPMDEngine._eval_trim``, ``engine.stacking.build_stacked_trim_blocks``,
``graph.distributed.make_pallas_trim_agg``), on the CPU with the Pallas
kernel interpreted.

The graph (``_trim_case.py``) holds each way such a trim can go wrong: a
split row fed by halo rows, a hub over several chunks, a row with no
in-edges, a partition with no split row, partitions of unequal trimmed
sizes, and split rows in a partition's first and last node block.  The
trimmed evaluation must give each split row the untrimmed forward's class,
its logits to float32 rounding, and -1 on every other row; every program
the trim does not touch lowers as it did without it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _trim_case import HUB, LONE, build_trim_graph, trim_gaps
from repro.core import GPHyperParams, partition_graph
from repro.engine import EngineConfig, SPMDEngine, stack_pytrees
from repro.graph import (BENCHMARKS, GAT, GraphSAGE, NeighborSampler,
                         build_partitioned_graph, make_benchmark)
from repro.kernels.segment_agg import BN
from repro.train.optim import AdamW


@pytest.fixture(scope="module")
def case():
    g, pg, empty = build_trim_graph()
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(), pg,
                     GPHyperParams(),
                     EngineConfig(mode="stacked", use_pallas_agg=True))
    return g, pg, empty, model, eng


def test_trim_fixture_holds_every_hazard(case):
    g, pg, empty, _, eng = case
    t = jax.tree.map(np.asarray, eng._eval_trim("test")[0])
    test = np.asarray(eng.masks["test"])
    nb = -(-pg.max_nodes // BN)
    real_chunks = (t["mask"] > 0).any(-1).sum(-1)
    # no split row in one partition: all of its blocks are pads, dropped
    assert not test[empty].any()
    assert (t["out"][empty] == nb).all() and t["mask"][empty].sum() == 0
    # partitions of unequal trimmed sizes, padded to one shape
    assert len(set(real_chunks.tolist())) > 1
    assert len({int((t["out"][p] < nb).sum()) for p in range(4)}) > 1
    for v, at_least in ((HUB, 3), (LONE, 0)):
        p = int(np.flatnonzero((pg.global_ids == v).any(1))[0])
        row = int(np.flatnonzero(pg.global_ids[p] == v)[0])
        assert test[p, row]
        k = int(np.flatnonzero(t["out"][p] == row // BN)[0])
        mine = (t["blk"][p] == k)[:, None] & (t["dst"][p] == row % BN) \
            & (t["mask"][p] > 0)
        spanned = int(mine.any(-1).sum())
        if at_least:                       # the hub spans >= 3 chunks
            assert spanned >= at_least
        else:                              # no in-edges
            assert spanned == 0 and int(g.indptr[v + 1] - g.indptr[v]) == 0
    # a split row whose in-neighbours are halo rows
    n_own = np.asarray(pg.n_own)[:, None, None]
    assert ((t["src"] >= n_own) & (t["mask"] > 0)).any()
    # split rows in a partition's first and last node block
    for p in range(4):
        if p == empty:
            continue
        last = int(pg.n_own[p]) - 1
        assert test[p, 0] and test[p, last] and last // BN > 0
        assert {0, last // BN} <= set(t["out"][p].tolist())


@pytest.mark.parametrize("split", ["val", "test"])
def test_trimmed_eval_equals_the_untrimmed_forward(case, split):
    _, _, _, model, eng = case
    params = jax.tree.map(lambda x: 4.0 * x, model.init(0))
    gaps = trim_gaps(eng, params, split)
    assert gaps["logit_rel"] <= 1e-5, gaps
    assert gaps["class_diff"] == 0 and gaps["eval_gap"] == 0.0, gaps
    assert gaps["not_minus_one"] == 0, gaps


def test_trim_counts_its_slots(case):
    """``last_eval_agg_slots`` on the 2% val split: the last layer runs
    under a tenth of the full layout's slots."""
    _, _, _, model, eng = case
    eng.evaluate(model.init(0), "val", per_partition_params=False)
    trimmed, full = eng.last_eval_agg_slots
    assert full == eng.shards["blk"]["src"].size
    assert 0 < trimmed < 0.1 * full, eng.last_eval_agg_slots


def _program_texts(eng, names) -> dict:
    return {k[0]: exe.as_text() for k, exe in eng._cache.items()
            if k[0] in names}


def _trim_absent(eng):
    eng._fwd_trim = None
    return eng


def _sampled_batches(g, pg):
    neigh = NeighborSampler(g, fanouts=(3, 3), seed=0)
    feats = np.asarray(g.features, np.float32)

    def batch(p):
        nodes = g.train_idx[:32]
        x_t, x_1, x_2 = neigh.sample(nodes).feature_views(feats)
        return {"x_t": jnp.asarray(x_t), "x_1": jnp.asarray(x_1),
                "x_2": jnp.asarray(x_2),
                "labels": jnp.asarray(g.labels[nodes]),
                "mask": jnp.ones(32, jnp.float32)}

    return stack_pytrees([stack_pytrees([batch(p) for p in range(4)])])


def test_phase0_and_gat_programs_lower_as_without_the_trim(case):
    """The sampled and full-graph phase-0 programs of a mean engine, and
    a GAT engine's phase 0 and eval, compile to the same text with and
    without the trim; the mean engine's eval is the one that changes."""
    g, pg, _, model, _ = case
    texts = []
    for absent in (False, True):
        eng = SPMDEngine(model, model.make_loss_fn(), AdamW(), pg,
                         GPHyperParams(), EngineConfig(mode="stacked"))
        eng = _trim_absent(eng) if absent else eng
        params = model.init(0)
        opt = eng.optimizer.init(params)
        eng.phase0_epoch(params, opt, _sampled_batches(g, pg))
        eng.phase0_fullgraph_epoch(params, opt, 1)
        texts.append(_program_texts(eng, {"phase0", "phase0_fg-1",
                                          "eval-val-False"}))
    assert set(texts[0]) == {"phase0", "phase0_fg-1", "eval-val-False"}
    assert texts[0]["phase0"] == texts[1]["phase0"]
    assert texts[0]["phase0_fg-1"] == texts[1]["phase0_fg-1"]
    assert texts[0]["eval-val-False"] != texts[1]["eval-val-False"]

    tiny = make_benchmark(BENCHMARKS["tiny"])
    parts = partition_graph(tiny.indptr, tiny.indices, tiny.features,
                            tiny.labels, 4, method="ew", seed=0,
                            fanout_k=5).parts
    tpg = build_partitioned_graph(tiny, parts, 4)
    gat = GAT(feature_dim=tiny.feature_dim, num_classes=tiny.num_classes,
              heads=(4, 4, 6), head_dim=8)
    texts = []
    for absent in (False, True):
        eng = SPMDEngine(gat, None, AdamW(), tpg, GPHyperParams(),
                         EngineConfig(mode="stacked"))
        eng = _trim_absent(eng) if absent else eng
        params = gat.init(0)
        eng.phase0_fullgraph_epoch(params, eng.optimizer.init(params), 1)
        assert eng.last_eval_agg_slots == (eng.shards["blk"]["src"].size,) * 2
        texts.append(_program_texts(eng, {"phase0_fg-1", "eval-val-False"}))
    assert len(texts[0]) == 2 and texts[0] == texts[1]


@pytest.mark.parametrize("config", [
    EngineConfig(mode="stacked", use_pallas_agg=False),
    EngineConfig(mode="stacked", halo_cache=True),
    EngineConfig(mode="stacked", halo_compress="int8"),
    EngineConfig(mode="stacked", overlap_halo=True),
])
def test_other_spellings_keep_the_full_layout(case, config):
    """The spellings with an aggregation of their own evaluate every row
    and report the full layout's slots."""
    _, pg, _, model, _ = case
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(), pg,
                     GPHyperParams(), config)
    _, preds = eng.evaluate(model.init(0), "val", per_partition_params=False)
    assert eng._fwd_trim is None
    trimmed, full = eng.last_eval_agg_slots
    assert trimmed == full > 0
    assert (np.asarray(preds) >= 0).all()
