import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import partition_graph
from repro.graph import (BENCHMARKS, GraphSAGE, NeighborSampler,
                         build_partitioned_graph, make_benchmark)


@pytest.fixture(scope="module")
def tiny():
    return make_benchmark(BENCHMARKS["tiny"])


def test_benchmark_properties(tiny):
    g = tiny
    assert g.num_nodes == 600
    assert len(g.indptr) == g.num_nodes + 1
    assert g.indices.max() < g.num_nodes
    # splits are disjoint
    tr, va, te = set(g.train_idx), set(g.val_idx), set(g.test_idx)
    assert not (tr & va) and not (tr & te) and not (va & te)
    # labelled fraction respected
    assert (g.labels[g.train_idx] >= 0).all()


def test_benchmark_homophily(tiny):
    """Generated graphs must actually be homophilous (EW's precondition)."""
    g = tiny
    src = g.indices
    dst = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    same = (g.labels[src] == g.labels[dst]).mean()
    k = g.num_classes
    base = np.square(np.bincount(g.labels[g.labels >= 0]) /
                     (g.labels >= 0).sum()).sum()
    assert same > 2 * base   # far above random mixing


def test_benchmark_class_imbalance():
    g = make_benchmark(BENCHMARKS["products-s"])
    counts = np.bincount(g.labels[g.labels >= 0])
    assert counts.max() > 5 * max(1, counts.min())   # Zipf tail


def test_neighbor_sampler_shapes(tiny):
    s = NeighborSampler(tiny, fanouts=(7, 3), seed=0)
    blocks = s.sample(tiny.train_idx[:32])
    assert blocks.nbrs1.shape == (32, 7)
    assert blocks.nbrs2.shape == (32 * 7, 3)
    x_t, x_1, x_2 = blocks.feature_views(tiny.features)
    assert x_t.shape == (32, tiny.feature_dim)
    assert x_1.shape == (32, 7, tiny.feature_dim)
    assert x_2.shape == (32, 7, 3, tiny.feature_dim)


def test_neighbor_sampler_valid_neighbors(tiny):
    """Every sampled neighbour is a true in-neighbour (or a self loop for
    isolated nodes)."""
    s = NeighborSampler(tiny, fanouts=(5, 5), seed=1)
    nodes = tiny.train_idx[:20]
    blocks = s.sample(nodes)
    for i, v in enumerate(nodes):
        nbrs = set(tiny.neighbors(v).tolist()) or {int(v)}
        assert set(blocks.nbrs1[i].tolist()) <= nbrs | {int(v)}


def test_sage_full_vs_pallas_segment_agg(tiny):
    """GraphSAGE full-graph forward through the ONE aggregation op: the
    Pallas path (default) == the jnp reference path, and ``jax.grad``
    through both paths agrees — the callback-free apply_full is
    differentiable end-to-end."""
    g = tiny
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    params = model.init(0)
    src = jnp.asarray(g.indices)
    dst = jnp.asarray(np.repeat(np.arange(g.num_nodes), np.diff(g.indptr)))
    feats = jnp.asarray(g.features)
    base = model.apply_full(params, feats, src, dst, g.num_nodes,
                            use_pallas=False)
    fused = model.apply_full(params, feats, src, dst, g.num_nodes)
    np.testing.assert_allclose(np.asarray(base), np.asarray(fused),
                               atol=1e-4, rtol=1e-4)

    def loss(params, use_pallas):
        out = model.apply_full(params, feats, src, dst, g.num_nodes,
                               use_pallas=use_pallas)
        return (out * out).mean()

    g_pal = jax.grad(lambda p: loss(p, True))(params)
    g_ref = jax.grad(lambda p: loss(p, False))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_pal),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


def test_sage_full_pallas_traced_edges_raise(tiny):
    """Under jit with traced edges and no prebuilt blocks the Pallas path
    cannot build its block structure: it raises rather than switching to
    the jnp backend behind the caller's back."""
    g = tiny
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    params = model.init(0)
    src = jnp.asarray(g.indices)
    dst = jnp.asarray(np.repeat(np.arange(g.num_nodes), np.diff(g.indptr)))
    feats = jnp.asarray(g.features)
    fwd = jax.jit(lambda s, d: model.apply_full(params, feats, s, d,
                                                g.num_nodes))
    with pytest.raises(ValueError, match="concrete edge lists"):
        fwd(src, dst)


def test_partitioned_graph_invariants(tiny):
    g = tiny
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="metis", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    # every node owned exactly once
    owned = np.concatenate([pg.global_ids[p, :pg.n_own[p]] for p in range(4)])
    assert sorted(owned.tolist()) == list(range(g.num_nodes))
    # halo slots reference real nodes of other partitions
    for p in range(4):
        halo = pg.global_ids[p, pg.n_own[p]: pg.n_own[p] + pg.n_halo[p]]
        assert (r.parts[halo] != p).all()
    # edge destinations are owned & local
    for p in range(4):
        real = pg.edge_mask[p] > 0
        assert (pg.edge_dst[p][real] < pg.n_own[p]).all()


def test_interior_boundary_split_invariants(tiny):
    """The [interior | boundary | halo | pad] layout (DESIGN.md §5):
    interior rows have NO halo in-neighbour, every boundary row has one,
    the destination-disjoint CSR shards exactly re-partition the combined
    edge list with per-row order preserved, and the static degree matches
    the combined edge mask."""
    g = tiny
    for method in ("ew", "random"):
        r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                            method=method, seed=0)
        pg = build_partitioned_graph(g, r.parts, 4)
        assert pg.own_cap == pg.n_own.max()
        for p in range(4):
            real = pg.edge_mask[p] > 0
            src, dst = pg.edge_src[p][real], pg.edge_dst[p][real]
            halo_src = src >= pg.n_own[p]
            # classification: boundary rows = exactly those with a halo src
            bnd_rows = np.unique(dst[halo_src])
            assert (bnd_rows >= pg.n_int[p]).all(), "interior row has halo src"
            expect_bnd = np.zeros(pg.max_nodes, bool)
            expect_bnd[bnd_rows] = True
            assert expect_bnd[pg.n_int[p]:pg.n_own[p]].all(), \
                "boundary row without halo src"
            # split shards re-partition the combined list, order preserved
            i_real = pg.int_mask[p] > 0
            b_real = pg.bnd_mask[p] > 0
            isrc, idst = pg.int_src[p][i_real], pg.int_dst[p][i_real]
            bsrc, bdst = pg.bnd_src[p][b_real], pg.bnd_dst[p][b_real]
            assert (idst < pg.n_int[p]).all() and (isrc < pg.n_own[p]).all()
            assert (bdst >= pg.n_int[p]).all() and (bdst < pg.n_own[p]).all()
            np.testing.assert_array_equal(np.concatenate([isrc, bsrc]), src)
            np.testing.assert_array_equal(np.concatenate([idst, bdst]), dst)
            # static degree == runtime mask degree, clamped
            counts = np.bincount(dst, minlength=pg.own_cap)[:pg.own_cap]
            np.testing.assert_array_equal(pg.deg[p], np.maximum(counts, 1))


def test_trash_row_is_explicit_and_unreferenced(tiny):
    """The trash-row convention is named state: ``trash_row`` is the last
    local row, real edges and real recv slots never reference it, and all
    padding does — so it stays all-zero through every layer."""
    g = tiny
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    assert pg.trash_row == pg.max_nodes - 1
    assert (pg.n_own + pg.n_halo <= pg.trash_row).all()
    for p in range(4):
        real = pg.edge_mask[p] > 0
        assert (pg.edge_src[p][real] != pg.trash_row).all()
        assert (pg.edge_dst[p][real] != pg.trash_row).all()
        assert (pg.edge_src[p][~real] == pg.trash_row).all()
        assert (pg.edge_dst[p][~real] == pg.trash_row).all()
        # features/labels on the trash row are zero / ignore-label
        assert np.abs(pg.features[p, pg.trash_row]).max() == 0.0
        assert pg.labels[p, pg.trash_row] == -1
    # recv_pos[p, q] aligns with send_mask[q, p]; real slots land in halo
    # space, pad slots land on the trash row
    recv_real = np.swapaxes(pg.send_mask, 0, 1) > 0
    assert (pg.recv_pos[recv_real] != pg.trash_row).all()
    assert (pg.recv_pos[~recv_real] == pg.trash_row).all()


def test_ew_reduces_halo_volume(tiny):
    """The paper's comm claim: EW cut < random cut => smaller halo."""
    g = tiny
    r_ew = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                           method="ew", seed=0)
    r_rnd = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                            method="random", seed=0)
    pg_ew = build_partitioned_graph(g, r_ew.parts, 4)
    pg_rnd = build_partitioned_graph(g, r_rnd.parts, 4)
    assert pg_ew.halo_bytes_per_layer < pg_rnd.halo_bytes_per_layer


DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.graph import make_benchmark, BENCHMARKS, GraphSAGE, build_partitioned_graph, make_distributed_forward
from repro.core import partition_graph

g = make_benchmark(BENCHMARKS["tiny"])
model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=32, num_classes=g.num_classes)
params = model.init(0)
r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4, method="ew", seed=0)
pg = build_partitioned_graph(g, r.parts, 4)
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
fwd = make_distributed_forward(model, {"max_nodes": pg.max_nodes}, axis_name="data")
shard = dict(features=pg.features, send_idx=pg.send_idx, send_mask=pg.send_mask,
             recv_pos=pg.recv_pos, edge_src=pg.edge_src, edge_dst=pg.edge_dst,
             edge_mask=pg.edge_mask)
specs = {k: P("data", *([None]*(v.ndim-1))) for k, v in shard.items()}
smfwd = jax.jit(jax.shard_map(
    lambda prm, sh: fwd(prm, jax.tree.map(lambda x: x[0], sh)),
    mesh=mesh, in_specs=(P(), specs), out_specs=P("data", None),
    check_vma=False))
dl = np.asarray(smfwd(params, shard)).reshape(4, pg.max_nodes, g.num_classes)
src = g.indices; dst = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
full = np.asarray(model.apply_full(params, jnp.asarray(g.features),
                                   jnp.asarray(src), jnp.asarray(dst), g.num_nodes))
err = 0.0
for p in range(4):
    for i in range(pg.n_own[p]):
        err = max(err, float(np.abs(dl[p, i] - full[pg.global_ids[p, i]]).max()))
assert err < 1e-4, err
print("OK", err)
"""


def test_distributed_forward_matches_centralized():
    """shard_map halo-exchange forward == centralized full-graph forward
    (run in a subprocess so the 4-device XLA flag doesn't leak)."""
    res = subprocess.run([sys.executable, "-c", DIST_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout

# ------------------------------------------------------- halo_refresh_plan --

def _plan_cycle(K, max_send, cv=True, start_age=0):
    """The chunk ranges one cache generation schedules: ages
    [start_age, start_age + K) with the full refresh at age % K == 0."""
    from repro.graph.distributed import halo_refresh_plan

    return [halo_refresh_plan(a, K, cv, max_send)
            for a in range(start_age, start_age + K)]


def test_refresh_plan_full_at_cycle_start():
    from repro.graph.distributed import halo_refresh_plan

    for K in (1, 2, 3, 7):
        for ms in (0, 1, 5, 64):
            for cv in (False, True):
                assert halo_refresh_plan(0, K, cv, ms) == (0, ms)
                assert halo_refresh_plan(3 * K, K, cv, ms) == (0, ms)


def test_refresh_plan_chunks_partition_slot_space():
    """CV cached epochs cut [0, max_send) into EXACTLY K-1 contiguous
    back-to-back chunks — no slot skipped, none re-sent within a cycle."""
    for K in (2, 3, 4, 5, 8):
        for ms in (0, 1, 2, K - 2, K - 1, K, 3 * K + 1, 257):
            if ms < 0:
                continue
            plans = _plan_cycle(K, ms)[1:]          # drop the full refresh
            assert plans[0][0] == 0
            assert plans[-1][1] == ms
            for (l0, h0), (l1, h1) in zip(plans, plans[1:]):
                assert h0 == l1                     # contiguous, gap-free
            assert all(lo <= hi for lo, hi in plans)
            assert sum(hi - lo for lo, hi in plans) == ms


def test_refresh_plan_small_max_send_covered_within_K():
    """max_send < K - 1: more chunks than slots, so some cached epochs ship
    nothing — but every slot is still refreshed within K epochs."""
    for K, ms in ((5, 2), (8, 3), (16, 1), (7, 0)):
        plans = _plan_cycle(K, ms)
        covered = set()
        for lo, hi in plans:
            covered.update(range(lo, hi))
        assert covered == set(range(ms))
        empties = sum(1 for lo, hi in plans[1:] if lo == hi)
        assert empties == (K - 1) - ms if ms < K - 1 else empties == 0


def test_refresh_plan_cv_off_ships_nothing_between_refreshes():
    from repro.graph.distributed import halo_refresh_plan

    for K in (2, 3, 9):
        for age in range(1, K):
            assert halo_refresh_plan(age, K, False, 40) == (0, 0)


@settings(max_examples=120)
@given(st.integers(1, 64), st.integers(0, 512), st.integers(0, 1000),
       st.booleans())
def test_refresh_plan_properties(K, max_send, age0, cv):
    """Adversarial (K, max_send) pairs: over ANY window of K consecutive
    ages the plan re-exchanges every slot at least once, ranges stay inside
    [0, max_send), and per-epoch payload never exceeds the full refresh."""
    from repro.graph.distributed import halo_refresh_plan

    covered = set()
    for age in range(age0, age0 + K):
        lo, hi = halo_refresh_plan(age, K, cv, max_send)
        assert 0 <= lo <= hi <= max_send
        covered.update(range(lo, hi))
    assert covered == set(range(max_send))   # staleness bound: <= K epochs
    if cv and K > 1 and max_send >= K - 1:
        # cached epochs pay ~1/(K-1) of the payload, never more than
        # ceil(max_send / (K-1))
        cap = -(-max_send // (K - 1))
        for age in range(age0, age0 + K):
            if age % K:
                lo, hi = halo_refresh_plan(age, K, cv, max_send)
                assert hi - lo <= cap
