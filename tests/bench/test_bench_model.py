"""The model module ``models/sage.py`` and the reference's blocked
aggregation, on a tiny graph on the CPU: the module's forward is the one
the reference held before it moved there, bit for bit, and aggregating in
edge blocks changes the reference by float32 rounding alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import byname, graphgen, harness
from perfbench.reference import Edges, Reference, edge_blocks

from conftest import DATA

sage = byname.load("model", "sage", harness.MODELS)


# -- the forward as the reference held it before it moved to the module ---
def _layer(lp, h_self, h_neigh, last):
    out = h_self @ lp["w_self"] + h_neigh @ lp["w_neigh"] + lp["b"]
    return out if last else jax.nn.relu(out)


def moved_sampled_logits(layers, x_t, x_1, x_2):
    l1, l2 = layers
    h_t = _layer(l1, x_t, x_1.mean(axis=1), last=False)
    h_1 = _layer(l1, x_1, x_2.mean(axis=2), last=False)
    return _layer(l2, h_t, h_1.mean(axis=1), last=True)


def moved_full_logits(layers, feats, src, dst, inv_deg):
    h = feats
    for i, lp in enumerate(layers):
        agg = jax.ops.segment_sum(h[src], dst, num_segments=h.shape[0])
        h = _layer(lp, h, agg * inv_deg[:, None], last=i == len(layers) - 1)
    return h


def moved_rows_logits(layers, feats, src, dst, inv_deg, rows, last_src,
                      last_pos):
    h = feats
    for lp in layers[:-1]:
        agg = jax.ops.segment_sum(h[src], dst, num_segments=h.shape[0],
                                  indices_are_sorted=True)
        h = _layer(lp, h, agg * inv_deg[:, None], last=False)
    agg = jax.ops.segment_sum(h[last_src], last_pos,
                              num_segments=rows.shape[0],
                              indices_are_sorted=True)
    return _layer(layers[-1], h[rows], agg * inv_deg[rows][:, None],
                  last=True)


@pytest.fixture(scope="module")
def tiny():
    cfg = harness.load_json(DATA / "tiny.json")
    g = graphgen.generate(cfg)
    n = g.num_nodes
    dst = np.repeat(np.arange(n), np.diff(g.indptr)).astype(np.int32)
    src = g.indices.astype(np.int32)
    inv_deg = (1.0 / np.maximum(np.diff(g.indptr), 1)).astype(np.float32)
    layers = sage.init_params(cfg, harness.seed_key(2**35 + 9))
    return cfg, g, src, dst, inv_deg, layers


def test_sage_forward_equals_the_moved_functions(tiny):
    cfg, g, src, dst, inv_deg, layers = tiny
    f = jnp.asarray(g.features)
    rng = np.random.default_rng(0)
    t = rng.integers(0, g.num_nodes, (8,))
    n1 = rng.integers(0, g.num_nodes, (8, 3))
    n2 = rng.integers(0, g.num_nodes, (8, 3, 2))
    np.testing.assert_array_equal(
        sage.sampled_logits(layers, f[t], f[n1], f[n2]),
        moved_sampled_logits(layers, f[t], f[n1], f[n2]))

    # one block holding every edge: the moved functions' arithmetic
    n = g.num_nodes
    s, d = edge_blocks(src, dst, n, block=len(src))
    edges = Edges(jnp.asarray(s), jnp.asarray(d), n, jnp.asarray(inv_deg))
    np.testing.assert_array_equal(
        sage.full_logits(layers, f, edges),
        moved_full_logits(layers, f, src, dst, inv_deg))

    rows = np.sort(g.val_idx).astype(np.int32)
    deg = np.diff(g.indptr)[rows]
    last_src = np.concatenate([g.indices[g.indptr[r]:g.indptr[r + 1]]
                               for r in rows]).astype(np.int32)
    last_pos = np.repeat(np.arange(len(rows)), deg).astype(np.int32)
    ls, ld = edge_blocks(last_src, last_pos, len(rows), block=len(last_src))
    last = Edges(jnp.asarray(ls), jnp.asarray(ld), len(rows),
                 jnp.asarray(inv_deg)[rows])
    np.testing.assert_array_equal(
        sage.rows_logits(layers, f, edges, jnp.asarray(rows), last),
        moved_rows_logits(layers, f, src, dst, inv_deg, rows, last_src,
                          last_pos))


def test_edge_blocks_pad_into_a_dropped_destination():
    s, d = edge_blocks(np.arange(5), np.array([0, 0, 1, 2, 2]), 3, block=2)
    assert s.shape == d.shape == (3, 2)
    assert d[-1].tolist() == [2, 3]            # the pad edge goes to 3
    edges = Edges(jnp.asarray(s), jnp.asarray(d), 3, jnp.ones(3))
    h = jnp.arange(5.0)[:, None] + 1
    out = edges.sum(lambda src, dst: h[src])
    np.testing.assert_array_equal(out[:, 0], [1 + 2, 3, 4 + 5])


@pytest.mark.parametrize("block", [64, 1000])
def test_blocked_reference_equals_the_whole_graph_one(tiny, block):
    """Three full-graph steps and the validation forward after each, with
    the aggregation cut into edge blocks, against one block holding every
    edge: equal to float32 rounding."""
    cfg, g, src, dst, inv_deg, layers = tiny
    parts = np.arange(g.num_nodes) % 4
    rows = np.sort(g.val_idx)
    whole = Reference(cfg, sage, g, parts, edge_block=1 << 30).run(
        layers, [1, 1, 1], eval_rows=rows)
    cut = Reference(cfg, sage, g, parts, edge_block=block)
    assert cut._full_inputs()["src"].shape[0] > 1
    blocked = cut.run(layers, [1, 1, 1], eval_rows=rows)
    # the cut changes the order of each destination's sum and nothing
    # else: a few float32 ulps (2**-23 = 1.2e-7) of the largest value
    for a, b in zip(blocked["losses"], whole["losses"]):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    for key in ("mu1", "params"):
        for la, lb in zip(blocked[key], whole[key]):
            for k in la:
                np.testing.assert_allclose(la[k], lb[k], rtol=0,
                                           atol=1e-6 * np.abs(lb[k]).max())
    for a, b in zip(blocked["val_logits"], whole["val_logits"]):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-6 * np.abs(b).max())
