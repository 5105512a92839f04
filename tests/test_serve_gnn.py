"""Partitioned GNN serving engine tests (the PR-7 tentpole).

1. fp64 bitwise oracle (subprocess, so ``jax_enable_x64`` cannot leak):
   after ANY scripted sequence of feature updates, cross-partition edge
   additions (including a source the partition had never seen — halo
   growth) and edge removals, the served logits equal a from-scratch
   ``SequentialReference`` forward over the rebuilt graph bit-for-bit —
   across two stacked update rounds, so the incremental dirty-set path
   cannot drift from the full recompute.
2. Query batching: one fused device gather per owning partition per tick,
   results equal to the store rows.
3. Pallas aggregation path: serving with ``segment_mean_op`` on the
   recompute kernel agrees with the jnp segment-op path.
4. Layer-count comm accounting: a 3-layer SAGE reports
   ``num_layers * halo_bytes_per_layer`` per full refresh (regression for
   the hardcoded ``2 *`` in ``_halo_tick``) and still matches the
   sequential reference's predictions.
5. AOT cache-key stability: re-evaluating with FRESH identically-sharded
   arrays must not recompile (``compile_count`` regression).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jax_cache import CACHE_PRELUDE, REPO_ROOT, subprocess_env

SUBPROC_ENV = subprocess_env()


# --------------------------------------------------------------------------
# shared tiny-graph serving fixture (f32, in-process tests)
# --------------------------------------------------------------------------

def _build(num_layers=2, dtype=jnp.float32, **cfg_kw):
    from repro.core import GPHyperParams, partition_graph
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes, num_layers=num_layers)
    cfg = EngineConfig(mode="stacked", use_pallas_agg=False, dtype=dtype,
                      **cfg_kw)
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                     GPHyperParams(), cfg)
    prm = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(0))
    return g, r, pg, model, cfg, eng, prm


@pytest.fixture(scope="module")
def served():
    from repro.serve import GNNServingEngine

    g, r, pg, model, cfg, eng, prm = _build()
    export = eng.export_serving_state(prm)
    srv = GNNServingEngine(model, prm, pg, export)
    return g, pg, model, prm, export, srv


# --------------------------------------------------------------------------
# 1. the fp64 bitwise serving oracle
# --------------------------------------------------------------------------

ORACLE_SCRIPT = CACHE_PRELUDE + """
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
from repro.core import partition_graph, GPHyperParams
from repro.engine import EngineConfig, SPMDEngine
from repro.engine.sequential import SequentialReference
from repro.graph import BENCHMARKS, GraphSAGE, build_partitioned_graph, \\
    make_benchmark
from repro.serve import GNNServingEngine, apply_updates_to_graph
from repro.train.optim import AdamW

g = make_benchmark(BENCHMARKS["tiny"])
P = 4
r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                    method="ew", seed=0)
pg = build_partitioned_graph(g, r.parts, P)
model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                  num_classes=g.num_classes)
cfg = EngineConfig(mode="stacked", use_pallas_agg=False, dtype=jnp.float64)
eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                 GPHyperParams(), cfg)
prm = jax.tree.map(lambda x: jnp.asarray(x, jnp.float64), model.init(0))
srv = GNNServingEngine(model, prm, pg, eng.export_serving_state(prm),
                       planner_compact_after=1)


def oracle_logits(graph):
    # from-scratch forward on the REBUILT graph, same partition assignment
    pg2 = build_partitioned_graph(graph, r.parts, P)
    seq = SequentialReference(model, model.make_loss_fn(), AdamW(lr=1e-3),
                              pg2, config=cfg)
    logits = seq._full_forward([prm] * P)
    out = np.zeros((graph.num_nodes, model.num_classes))
    for p in range(P):
        n = int(pg2.n_own[p])
        out[np.asarray(pg2.global_ids[p])[:n]] = np.asarray(logits[p])[:n]
    return out


assert (srv.export_logits() == oracle_logits(g)).all(), "initial not bitwise"

# scripted round 1: random feature updates (float32 — graph features are
# f32, the oracle quantizes through them), a cross-partition edge add whose
# source the destination partition has NEVER seen (halo growth), a
# same-partition add, and a removal
rng = np.random.default_rng(7)
parts = r.parts
fupd = {int(v): rng.normal(0, 1, g.feature_dim).astype(np.float32)
        for v in rng.choice(g.num_nodes, 5, replace=False)}
target = None
for v in range(g.num_nodes):
    p = parts[v]
    for u in range(g.num_nodes):
        if u == v or parts[u] == p or u in srv.g2l[p] or u in g.neighbors(v):
            continue
        target = (u, v); break
    if target: break
adds = [target]
for v in range(g.num_nodes):
    p = parts[v]
    cand = [u for u in range(g.num_nodes)
            if u != v and parts[u] == p and u not in g.neighbors(v)]
    if cand:
        adds.append((cand[0], v)); break
v0 = next(v for v in range(g.num_nodes) if len(g.neighbors(v)) > 1)
rem = [(int(g.neighbors(v0)[0]), v0)]

for gid, vec in fupd.items():
    srv.update_features(gid, vec)
for u, v in adds:
    assert srv.add_edge(u, v)
for u, v in rem:
    assert srv.remove_edge(u, v)
st = srv.flush()
assert st["rows_recomputed"] > 0 and srv.stats["halo_rows_grown"] > 0
g2 = apply_updates_to_graph(g, fupd, adds, rem)
s2, o2 = srv.export_logits(), oracle_logits(g2)
bad = np.flatnonzero((s2 != o2).any(axis=1))
assert bad.size == 0, (bad.size, float(np.abs(s2 - o2).max()))

# round 2 ON TOP (sequence property): more features + remove the added edge
fupd2 = {int(v): rng.normal(0, 1, g.feature_dim).astype(np.float32)
         for v in rng.choice(g.num_nodes, 3, replace=False)}
rem2 = [adds[0]]
for gid, vec in fupd2.items():
    srv.update_features(gid, vec)
for u, v in rem2:
    assert srv.remove_edge(u, v)
srv.flush()
g3 = apply_updates_to_graph(g2, fupd2, (), rem2)
assert (srv.export_logits() == oracle_logits(g3)).all(), "round 2 not bitwise"
# compact_after=1: the static-CSC removal in round 1 compacted eagerly and
# serving stayed bitwise THROUGH the compaction
assert srv.planner.compactions >= 1, srv.planner.compactions

# query batching: one fused gather per owning partition, rows match store
q = [0, 1, 2, 3, 17, 101]
srv.submit(q)
before = srv.stats["gather_calls"]
res, _ = srv.tick()
assert srv.stats["gather_calls"] - before \\
    == len({int(srv.owner_part[x]) for x in q})
full = srv.export_logits()
assert all((v == full[k]).all() for k, v in res.items())
print("SERVE-ORACLE-OK")
"""


@pytest.mark.slow
def test_serving_bitwise_oracle_fp64():
    r = subprocess.run([sys.executable, "-c", ORACLE_SCRIPT],
                       capture_output=True, text=True, env=SUBPROC_ENV,
                       cwd=REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SERVE-ORACLE-OK" in r.stdout


# --------------------------------------------------------------------------
# 2-3. in-process f32: export handoff, batching counters, Pallas path
# --------------------------------------------------------------------------

def test_export_matches_evaluate(served):
    """export_serving_state's logits reproduce evaluate()'s predictions."""
    g, pg, model, prm, export, srv = served
    assert tuple(a.shape[-1] for a in export["layers"]) \
        == tuple(model.layer_dims[:-1])
    preds = np.full(g.num_nodes, -1)
    for p in range(pg.num_parts):
        n = int(pg.n_own[p])
        own = np.asarray(pg.global_ids[p])[:n]
        preds[own] = np.asarray(export["logits"][p])[:n].argmax(-1)
    assert (srv.export_logits().argmax(-1) == preds).all()


def test_query_batching_one_gather_per_partition(served):
    g, pg, model, prm, export, srv = served
    q = [0, 5, 9, 42, 311]
    srv.submit(q)
    before = srv.stats["gather_calls"]
    res, lat = srv.tick()
    owning = {int(srv.owner_part[x]) for x in q}
    assert srv.stats["gather_calls"] - before == len(owning)
    assert set(res) == set(q)
    full = srv.export_logits()
    assert all((v == full[k]).all() for k, v in res.items())


def test_pallas_recompute_path_matches_ref(served):
    """Serving with the Pallas segment kernel on the recompute path agrees
    with the jnp segment-op path after identical updates."""
    from repro.serve import GNNServingEngine

    g, pg, model, prm, export, _ = served
    rng = np.random.default_rng(3)
    upd = {int(v): rng.normal(0, 1, g.feature_dim).astype(np.float32)
           for v in rng.choice(g.num_nodes, 4, replace=False)}
    outs = []
    for pallas in (False, True):
        srv = GNNServingEngine(model, prm, pg, export,
                               use_pallas_agg=pallas)
        for gid, vec in upd.items():
            srv.update_features(gid, vec)
        srv.flush()
        outs.append(srv.export_logits())
    np.testing.assert_allclose(outs[1], outs[0], atol=5e-6, rtol=1e-5)


# --------------------------------------------------------------------------
# 4. layer-count comm accounting (regression: hardcoded ``2 *`` factor)
# --------------------------------------------------------------------------

def test_three_layer_halo_accounting_and_parity():
    """A 3-layer SAGE pays THREE exchanges per full refresh — the counter
    must say so (the old code hardcoded 2) — and the stacked engine still
    matches the sequential reference's predictions layer-for-layer."""
    from repro.core import GPHyperParams
    from repro.engine import EngineConfig, SPMDEngine
    from repro.engine.sequential import SequentialReference
    from repro.train.optim import AdamW

    g, r, pg, model, cfg, eng, prm = _build(num_layers=3, halo_cache=True,
                                            halo_refresh_every=1)
    assert model.num_layers == 3
    micro, preds = eng.evaluate(prm, "val", per_partition_params=False)
    assert eng.last_halo_exchange_bytes == 3 * pg.halo_bytes_per_layer

    seq = SequentialReference(model, model.make_loss_fn(), AdamW(lr=1e-3),
                              pg, GPHyperParams(),
                              EngineConfig(mode="stacked",
                                           use_pallas_agg=False,
                                           dtype=jnp.float32,
                                           halo_cache=True,
                                           halo_refresh_every=1))
    mS, pS = seq.evaluate(prm, "val", per_partition_params=False)
    assert (np.asarray(preds) == np.asarray(pS)).all()
    assert seq.last_halo_exchange_bytes == 3 * pg.halo_bytes_per_layer


def test_three_layer_serving_roundtrip():
    """Serving built from a 3-layer checkpoint: h stores for every layer,
    and an update round keeps predictions consistent with a fresh export."""
    from repro.serve import GNNServingEngine, apply_updates_to_graph
    from repro.core import GPHyperParams
    from repro.engine import SPMDEngine
    from repro.graph import build_partitioned_graph
    from repro.train.optim import AdamW

    g, r, pg, model, cfg, eng, prm = _build(num_layers=3)
    srv = GNNServingEngine(model, prm, pg, eng.export_serving_state(prm))
    assert len(srv.h) == model.num_layers + 1   # h0..h2 + logits

    rng = np.random.default_rng(11)
    upd = {int(v): rng.normal(0, 1, g.feature_dim).astype(np.float32)
           for v in rng.choice(g.num_nodes, 3, replace=False)}
    for gid, vec in upd.items():
        srv.update_features(gid, vec)
    srv.flush()

    g2 = apply_updates_to_graph(g, upd, (), ())
    pg2 = build_partitioned_graph(g2, r.parts, 4)
    eng2 = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg2,
                      GPHyperParams(), cfg)
    fresh = eng2.export_serving_state(prm)
    want = np.zeros((g.num_nodes, model.num_classes), np.float32)
    for p in range(pg2.num_parts):
        n = int(pg2.n_own[p])
        want[np.asarray(pg2.global_ids[p])[:n]] = \
            np.asarray(fresh["logits"][p])[:n]
    np.testing.assert_allclose(srv.export_logits(), want, atol=2e-5,
                               rtol=1e-5)


# --------------------------------------------------------------------------
# 5. AOT cache-key stability (compile_count regression)
# --------------------------------------------------------------------------

def test_no_recompile_on_fresh_identically_sharded_inputs():
    """Fresh arrays with identical shape/dtype/sharding must hit the AOT
    cache — a re-lowering per step was the serving-latency bug."""
    _, _, _, model, _, eng, prm = _build()
    eng.evaluate(prm, "val", per_partition_params=False)
    n0 = eng.compile_count
    assert n0 >= 1
    for _ in range(3):
        fresh = jax.tree.map(
            lambda x: jnp.asarray(np.asarray(x), x.dtype), prm)
        eng.evaluate(fresh, "val", per_partition_params=False)
    assert eng.compile_count == n0, "identically-sharded inputs recompiled"


# --------------------------------------------------------------------------
# 6. hot-row query cache + planner adjacency compaction (PR-9 satellites)
# --------------------------------------------------------------------------

def test_hot_row_cache_hits_and_invalidation():
    """Repeat queries hit the LRU hot-row cache (no extra gathers), a flush
    that recomputes a row evicts exactly it, and answers always equal the
    logits store."""
    from repro.serve import GNNServingEngine

    g, r, pg, model, cfg, eng, prm = _build()
    srv = GNNServingEngine(model, prm, pg, eng.export_serving_state(prm))
    q = [0, 5, 9]
    a = srv.query(q)
    assert srv.stats["cache_misses"] == len(q)
    assert srv.stats["cache_hits"] == 0

    before = srv.stats["gather_calls"]
    b = srv.query(q)
    assert srv.stats["cache_hits"] == len(q)
    assert srv.stats["gather_calls"] == before, "cache hit still gathered"
    assert (a == b).all()

    rng = np.random.default_rng(0)
    srv.update_features(q[0], rng.normal(0, 1, g.feature_dim)
                        .astype(np.float32))
    c = srv.query(q)
    full = srv.export_logits()
    assert (c == full[np.asarray(q)]).all(), "cache served a stale row"
    assert srv.stats["cache_misses"] >= len(q) + 1   # q[0] re-gathered


def test_hot_row_cache_lru_capacity():
    from repro.serve import GNNServingEngine

    g, r, pg, model, cfg, eng, prm = _build()
    srv = GNNServingEngine(model, prm, pg, eng.export_serving_state(prm),
                           hot_cache_rows=2)
    srv.query([0, 5, 9, 42])
    assert len(srv._hot) == 2
    # whatever survived the LRU eviction serves as hits, byte-for-byte
    resident = list(srv._hot)
    before = srv.stats["cache_hits"]
    res = srv.query(resident)
    assert srv.stats["cache_hits"] == before + len(resident)
    full = srv.export_logits()
    assert (res == full[np.asarray(resident)]).all()


def test_planner_compaction_exact_adjacency():
    """With compact_after=1 every static-edge removal compacts its shard:
    the planner's out_rows then equal EXACTLY the adjacency implied by the
    live aggregation lists (no stale over-propagating out-edges), and the
    compaction count surfaces in serving stats."""
    from repro.serve import GNNServingEngine

    g, r, pg, model, cfg, eng, prm = _build()
    srv = GNNServingEngine(model, prm, pg, eng.export_serving_state(prm),
                           planner_compact_after=1)
    removed = []
    for v in range(g.num_nodes):
        for u in g.neighbors(v):
            if u != v:
                removed.append((int(u), int(v)))
                break
        if len(removed) >= 6:
            break
    assert len(removed) >= 2, "tiny graph has no removable edges?"
    for u, v in removed:
        assert srv.remove_edge(u, v)
    assert srv.planner.compactions >= 1
    srv.flush()
    assert srv.stats["planner_compactions"] == srv.planner.compactions

    for p in range(pg.num_parts):
        want: dict[int, set] = {}
        for w in range(int(srv.n_own[p])):
            for s in srv.nbr_loc[p][w]:
                want.setdefault(int(s), set()).add(w)
        n_rows = len(srv.planner._csc[p][0]) - 1
        for row in range(n_rows):
            got = set(map(int, srv.planner.out_rows(p, np.asarray([row]))))
            assert got == want.get(row, set()), (p, row)


def test_export_serving_state_cached_compile():
    _, _, _, model, _, eng, prm = _build()
    eng.export_serving_state(prm)
    n0 = eng.compile_count
    fresh = jax.tree.map(lambda x: jnp.asarray(np.asarray(x), x.dtype), prm)
    out = eng.export_serving_state(fresh)
    assert eng.compile_count == n0
    assert len(out["layers"]) == model.num_layers
