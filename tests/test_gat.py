"""GAT (graph/gat.py, kernels/gat_agg.py) against a plain ``jax.numpy``
reference, on the CPU with the Pallas kernels interpreted.

The reference below follows the paper's equations (arXiv 1710.10903, §2.1)
with ``jax.ops.segment_*`` over an explicit edge list, in float32 at
``highest`` precision.  On the CPU every float32 matmul is exact float32,
so the program and the reference differ only in the order of their sums:
the tolerances below are a few float32 ulps of the values compared
(``RTOL``), never more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from repro.core import GPHyperParams, partition_graph
from repro.engine import EngineConfig, make_engine
from repro.graph import (BENCHMARKS, GAT, GraphSAGE, build_partitioned_graph,
                         make_benchmark, make_distributed_forward)
from repro.graph.distributed import _halo_exchange
from repro.kernels import segment_agg
from repro.kernels.gat_agg import (attention_agg_op, attention_agg_ref,
                                   build_gat_blocks, edge_softmax_blocks,
                                   gat_agg_op)
from repro.train.optim import AdamW

# float32 sums over <= a few hundred terms in another order: a few ulps
# (2**-23 ~ 1.2e-7) of the largest value, with room for the softmax's exp
RTOL = 2e-5
HEADS = (4, 4, 6)       # the published layout, at a tiny per-head width
HEAD_DIM = 8


# -- the plain reference ---------------------------------------------------
def ref_layer(lp, h, src, dst, n, last, skip):
    k = lp.a_src.shape[0]
    z = jnp.dot(h, lp.w, precision="highest").reshape(n, k, -1)
    s_src = (z * lp.a_src).sum(-1)
    s_dst = (z * lp.a_dst).sum(-1)
    # in-edges plus each node itself once
    s = jnp.concatenate([src, jnp.arange(n)])
    d = jnp.concatenate([dst, jnp.arange(n)])
    e = jax.nn.leaky_relu(s_src[s] + s_dst[d], 0.2)
    m = jax.ops.segment_max(e, d, num_segments=n)
    p = jnp.exp(e - m[d])
    alpha = p / jax.ops.segment_sum(p, d, num_segments=n)[d]
    o = jax.ops.segment_sum(alpha[..., None] * z[s], d, num_segments=n)
    if last:
        return o.mean(axis=1) + lp.b
    out = o.reshape(n, -1) + lp.b
    return jax.nn.elu(out + h if skip else out)


def ref_logits(params, x, src, dst):
    n = x.shape[0]
    h = x
    last = len(params.layers) - 1
    for i, lp in enumerate(params.layers):
        h = ref_layer(lp, h, src, dst, n, i == last, 0 < i < last)
    return h


def ragged_edges(n, seed=0):
    """Hubs (more than one chunk of in-edges), empty rows and a ragged last
    chunk, shuffled."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 6, n)
    deg[[0, 2, 5]] = [300, 0, 129]
    deg[n - 1] = 0
    dst = np.repeat(np.arange(n), deg)
    src = rng.integers(0, n, dst.size)
    perm = rng.permutation(dst.size)
    return src[perm], dst[perm]


# -- the kernels -----------------------------------------------------------
@pytest.mark.parametrize("heads,width", [(3, 5), (2, 128), (4, 256)])
def test_attention_aggregation_and_vjp_match_segment_ops(heads, width):
    """The Pallas aggregation (edge softmax, gat_agg, and through its VJP
    the transpose and gat_sddmm) equals the segment-op spelling, at narrow
    heads that share lanes, at lane-aligned heads, and at heads wider than
    one call gathers (two calls of two heads each way)."""
    n = 300
    src, dst = ragged_edges(n)
    blocks = {k: jnp.asarray(v) for k, v in
              build_gat_blocks(src, dst, n, n).items()}
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.normal(size=(n, heads * width)), jnp.float32)
    ss = jnp.asarray(rng.normal(size=(n, heads)), jnp.float32)
    sd = jnp.asarray(rng.normal(size=(n, heads)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(n, heads * width)), jnp.float32)

    def kern(z, ss, sd):
        return attention_agg_op(z, ss, sd, blocks, num_rows=n)

    def ref(z, ss, sd):
        return attention_agg_ref(z, ss, sd, jnp.asarray(src), jnp.asarray(dst),
                                 jnp.ones(src.size), n)

    with jax.default_matmul_precision("highest"):
        a, r = jax.jit(kern)(z, ss, sd), ref(z, ss, sd)
        np.testing.assert_allclose(a, r, rtol=0, atol=RTOL * np.abs(r).max())
        ga = jax.jit(jax.grad(lambda *x: (kern(*x) * g).sum(), (0, 1, 2)))(
            z, ss, sd)
        gr = jax.grad(lambda *x: (ref(*x) * g).sum(), (0, 1, 2))(z, ss, sd)
    for x, y in zip(ga, gr):
        np.testing.assert_allclose(x, y, rtol=0, atol=RTOL * np.abs(y).max())


def test_gat_agg_op_check_grads():
    """Numerical against analytic gradients of the kernel op in both of
    its inputs (float32 finite differences: the default tolerances of
    ``check_grads`` at that dtype)."""
    n, heads, width = 160, 2, 3
    src, dst = ragged_edges(n, seed=3)
    blocks = {k: jnp.asarray(v) for k, v in
              build_gat_blocks(src, dst, n, n).items()}
    rng = np.random.default_rng(4)
    z = jnp.asarray(rng.normal(size=(n, heads * width)), jnp.float32)
    alpha = jnp.asarray(rng.uniform(size=(blocks["src"].shape[0], heads,
                                          128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        check_grads(lambda z, a: gat_agg_op(z, a, blocks, num_rows=n),
                    (z, alpha), order=1, modes=("rev",))


@pytest.mark.parametrize("offset", [0.0, 1e3, -1e3])
def test_edge_softmax_sums_to_one_and_stays_finite(offset):
    n = 300
    src, dst = ragged_edges(n, seed=5)
    blocks = {k: jnp.asarray(v) for k, v in
              build_gat_blocks(src, dst, n, n).items()}
    rng = np.random.default_rng(6)
    ss = jnp.asarray(rng.normal(size=(n, 4)) + offset, jnp.float32)
    sd = jnp.asarray(rng.normal(size=(n, 4)) * 10, jnp.float32)
    alpha, alpha_self = edge_softmax_blocks(ss, sd, blocks, n)
    assert np.isfinite(np.asarray(alpha)).all()
    assert np.isfinite(np.asarray(alpha_self)).all()
    # padded slots take no weight
    mask = np.asarray(blocks["mask"])
    assert (np.asarray(alpha).transpose(0, 2, 1)[mask == 0] == 0).all()
    rows = (np.asarray(blocks["blk"])[:, None] * 128
            + np.asarray(blocks["dst"])).reshape(-1)
    a = np.asarray(alpha).transpose(0, 2, 1).reshape(-1, 4)
    total = np.asarray(alpha_self, np.float64).copy()
    np.add.at(total, rows[mask.reshape(-1) > 0], a[mask.reshape(-1) > 0])
    np.testing.assert_allclose(total, 1.0, rtol=0, atol=1e-5)
    # an empty row is its own only neighbour
    np.testing.assert_array_equal(np.asarray(alpha_self)[2], 1.0)


# -- the model through the engine -------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    graph = make_benchmark(BENCHMARKS["tiny"])
    parts = partition_graph(graph.indptr, graph.indices, graph.features,
                            graph.labels, 4, method="ew", seed=0,
                            fanout_k=5).parts
    pg = build_partitioned_graph(graph, parts, 4)
    model = GAT(feature_dim=graph.feature_dim, num_classes=graph.num_classes,
                heads=HEADS, head_dim=HEAD_DIM)
    n = graph.num_nodes
    src = jnp.asarray(graph.indices, jnp.int32)
    dst = jnp.asarray(np.repeat(np.arange(n), np.diff(graph.indptr)),
                      jnp.int32)
    return graph, pg, model, src, dst


def owned_rows(pg, local):
    """(P, maxN, ...) per-partition rows -> global order."""
    local = np.asarray(local)
    out = np.zeros((int(pg.global_ids.max()) + 1,) + local.shape[2:],
                   local.dtype)
    for p in range(pg.num_parts):
        k = int(pg.n_own[p])
        out[np.asarray(pg.global_ids[p, :k])] = local[p, :k]
    return out


@pytest.mark.parametrize("use_pallas", [True, False])
def test_partitioned_forward_and_grads_equal_the_whole_graph(tiny,
                                                             use_pallas):
    """The vmapped P=4 forward (halo exchange + attention per layer) gives
    the whole-graph logits on owned rows, and the mean of the partitions'
    training losses has the whole-graph loss's gradient on every leaf."""
    graph, pg, model, src, dst = tiny
    params = model.init(3)
    eng = make_engine(model, None, AdamW(lr=1e-3), pg,
                      config=EngineConfig(mode="stacked",
                                          use_pallas_agg=use_pallas))
    x = jnp.asarray(graph.features)
    with jax.default_matmul_precision("highest"):
        want = ref_logits(params, x, src, dst)
        whole = jax.jit(lambda p: model.apply_full(
            p, x, src, dst, graph.num_nodes))(params)
        got = jax.jit(jax.vmap(eng.fwd, in_axes=(None, 0), axis_name="parts"))(
            params, eng.shards)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(whole, want, rtol=0, atol=RTOL * scale)
    np.testing.assert_allclose(owned_rows(pg, got), want, rtol=0,
                               atol=RTOL * scale)

    train = owned_rows(pg, pg.train_mask)
    labels = jnp.asarray(np.maximum(graph.labels, 0))
    part_of = owned_rows(pg, np.broadcast_to(
        np.arange(4)[:, None], pg.train_mask.shape))

    def ref_loss(prm):
        logp = jax.nn.log_softmax(ref_logits(prm, x, src, dst))
        nll = -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
        per = [(nll * m).sum() / m.sum() for m in
               [jnp.asarray(train & (part_of == p), jnp.float32)
                for p in range(4)]]
        return sum(per) / 4

    def prog_loss(prm):
        per = jax.vmap(eng._fg_loss, in_axes=(None, 0), axis_name="parts")(
            prm, eng._fg_batch())
        return per.mean()

    with jax.default_matmul_precision("highest"):
        gw = jax.jit(jax.grad(ref_loss))(params)
        gp = jax.jit(jax.grad(prog_loss))(params)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=RTOL * np.abs(np.asarray(b)).max())


def test_fullgraph_step_equals_plain_adamw(tiny):
    """One ``phase0_fullgraph_epoch`` step through the Pallas kernels is one
    plain AdamW step (global-norm clip 5) on the reference's gradient."""
    graph, pg, model, src, dst = tiny
    params = model.init(4)
    opt = AdamW(lr=1e-3, grad_clip=5.0)
    eng = make_engine(model, None, opt, pg, hp=GPHyperParams(),
                      config=EngineConfig(mode="stacked"))
    before = segment_agg.pallas_call_count()
    with jax.default_matmul_precision("highest"):
        new, _, losses, val, _ = eng.phase0_fullgraph_epoch(
            params, opt.init(params))
    # forward and transpose gat_agg, gat_sddmm, per layer, and the eval
    assert segment_agg.pallas_call_count() - before >= 3 * 3
    assert np.isfinite(np.asarray(losses)).all()
    assert np.asarray(val).shape == (4,)

    x = jnp.asarray(graph.features)
    labels = jnp.asarray(np.maximum(graph.labels, 0))
    train = owned_rows(pg, pg.train_mask)
    part_of = owned_rows(pg, np.broadcast_to(
        np.arange(4)[:, None], pg.train_mask.shape))
    masks = [jnp.asarray(train & (part_of == p), jnp.float32)
             for p in range(4)]

    def ref_loss(prm):
        logp = jax.nn.log_softmax(ref_logits(prm, x, src, dst))
        nll = -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
        return sum((nll * m).sum() / m.sum() for m in masks) / 4

    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(ref_loss))(params)
    norm = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree.leaves(grads)))
    scale = min(1.0, 5.0 / (norm + 1e-9))
    for p0, g, p1 in zip(jax.tree.leaves(params), jax.tree.leaves(grads),
                         jax.tree.leaves(new)):
        g = np.asarray(g) * scale
        # Adam's first step moves every weight by lr * g / (|g| + eps)
        want = np.asarray(p0) - 1e-3 * g / (np.abs(g) + 1e-8)
        step = np.abs(np.asarray(p1) - np.asarray(p0))
        moved = np.abs(g) > 1e-3 * np.abs(g).max()
        np.testing.assert_allclose(np.asarray(p1)[moved], want[moved],
                                   rtol=0, atol=1e-6)
        assert (step <= 1e-3 + 1e-7).all()


def test_sage_forward_through_the_seam_is_the_old_line(tiny):
    """GraphSAGE's layer, now supplied by the model, is bitwise the line
    the forward wrote out before."""
    graph, pg, _, _, _ = tiny
    sage = GraphSAGE(feature_dim=graph.feature_dim, hidden_dim=16,
                     num_classes=graph.num_classes)
    params = sage.init(1)
    eng = make_engine(sage, sage.make_loss_fn(), AdamW(), pg,
                      config=EngineConfig(mode="stacked"))
    agg = eng._mean_agg

    def old(params, shard):
        h = shard["features"]
        last = len(params.layers) - 1
        for i, lp in enumerate(params.layers):
            h = _halo_exchange(h, shard["send_idx"], shard["send_mask"],
                               shard["recv_pos"], "parts")
            a = agg(h, shard)
            h = h @ lp.w_self + a @ lp.w_neigh + lp.b
            if i < last:
                h = jax.nn.relu(h)
        return h

    run = lambda f: jax.jit(jax.vmap(f, in_axes=(None, 0),
                                     axis_name="parts"))(params, eng.shards)
    np.testing.assert_array_equal(run(eng.fwd), run(old))
    fwd = make_distributed_forward(sage, {"max_nodes": pg.max_nodes},
                                   axis_name="parts", agg=agg)
    assert (jax.make_jaxpr(jax.vmap(fwd, in_axes=(None, 0),
                                    axis_name="parts"))(params, eng.shards)
            .jaxpr.pretty_print() ==
            jax.make_jaxpr(jax.vmap(old, in_axes=(None, 0),
                                    axis_name="parts"))(params, eng.shards)
            .jaxpr.pretty_print())


def test_sage_engine_resident_arrays_unchanged(tiny):
    graph, pg, _, _, _ = tiny
    sage = GraphSAGE(feature_dim=graph.feature_dim, hidden_dim=16,
                     num_classes=graph.num_classes)
    eng = make_engine(sage, sage.make_loss_fn(), AdamW(), pg,
                      config=EngineConfig(mode="stacked"))
    assert "t_slot" not in eng.shards["blk"]
    assert set(eng.shards["blk"]) == {"src", "dst", "mask", "blk", "deg",
                                      "t_src", "t_dst", "t_mask", "t_blk"}


# -- the spellings that cannot run it ---------------------------------------
@pytest.mark.parametrize("config,spelling", [
    (EngineConfig(mode="sequential"), "sequential"),
    (EngineConfig(mode="stacked", overlap_halo=True), "overlap_halo"),
    (EngineConfig(mode="stacked", halo_cache=True), "halo_cache"),
    (EngineConfig(mode="stacked", halo_compress="int8"), "compressed"),
])
def test_other_spellings_refuse_gat(tiny, config, spelling):
    _, pg, model, _, _ = tiny
    with pytest.raises(ValueError, match=spelling):
        make_engine(model, None, AdamW(), pg, config=config)


def test_export_serving_and_sampled_programs_refuse_gat(tiny):
    from repro.serve.gnn import GNNServingEngine

    _, pg, model, _, _ = tiny
    params = model.init(0)
    eng = make_engine(model, None, AdamW(), pg,
                      config=EngineConfig(mode="stacked"))
    with pytest.raises(ValueError, match="export_serving_state"):
        eng.export_serving_state(params)
    with pytest.raises(ValueError, match="phase0_epoch"):
        eng.phase0_epoch(params, AdamW().init(params), {})
    with pytest.raises(ValueError, match="GNNServingEngine"):
        GNNServingEngine(model, params, pg, {})
    with pytest.raises(ValueError, match="full-graph path"):
        model.make_loss_fn()
    with pytest.raises(ValueError, match="full-graph path"):
        model.apply_sampled(params, None, None, None)


# -- the normal path ---------------------------------------------------------
def test_pipeline_refuses_gat_off_the_full_graph_path():
    from repro.pipeline import EATConfig, run_eat_distgnn

    with pytest.raises(ValueError, match="full_graph_train"):
        run_eat_distgnn(EATConfig(dataset="tiny", arch="gat"))


def test_pipeline_trains_gat_full_graph():
    from repro.pipeline import EATConfig, run_eat_distgnn

    """GP's phase 1 trains on sampled mini-batches: GAT runs phase 0 only,
    with ``use_gp`` taken as False."""
    res = run_eat_distgnn(EATConfig(
        dataset="tiny", arch="gat", full_graph_train=True, max_epochs=2,
        hidden_dim=HEAD_DIM, engine_mode="stacked"))
    assert not res.config.use_gp and res.phase1_epochs == 0
    assert res.epochs_run == 2
    assert np.isfinite(res.loss_history).all()
    assert res.loss_history[1] < res.loss_history[0]
