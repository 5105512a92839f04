"""Compile-only checks of the ``segment_agg`` kernel and of GAT's
``gat_agg`` and ``gat_sddmm`` kernels for a TPU v5e.

Nothing runs: each test lowers the kernel for a described (not attached)
v5e chip and compiles it with the TPU compiler, which refuses what
interpret mode accepts — block shapes off the (8, 128) tiling, unsupported
in-kernel primitives, more VMEM than a kernel may use.  Shapes are the
default job's: products-s at P=4 under EW partitioning, whose hub-heavy
node blocks hold up to 13,952 in-edges (``BE``), with the 64-wide input
features and the 128-wide hidden layer.  GAT's kernels are compiled at
``gat-flickr``'s shapes: Flickr at P=4 under EW, 2,416 forward and 2,261
transpose chunks, 476 node blocks, the 1,024-wide hidden layers (four
heads of 256, two heads a call) and the last layer's six heads of 7
classes.  The evaluation's trimmed last layer (the kernel over a split's
in-edges in compact node blocks, and the scatter of its blocks into place)
is compiled over the same hub-heavy edges.

The topology is described inside a module-scoped fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gat_agg import (gat_agg_blocks, gat_sddmm_blocks,
                                   head_groups)
from repro.kernels.segment_agg import (BEC, BN, build_edge_blocks,
                                       build_vjp_blocks, segment_agg_rows,
                                       segment_mean_op)

N_ROWS = 17_408          # products-s P=4 max_nodes, rounded up to BN
BE = 13_952              # largest in-edge run of one 128-row node block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip; keep them out of it while this module runs
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def hub_edges():
    """Edge list with the default job's block geometry: ~110k edges over
    N_ROWS rows, one node block carrying BE in-edges (a power-law hub
    block), every other row a handful of in-edges."""
    rng = np.random.default_rng(0)
    deg = rng.integers(0, 12, N_ROWS)
    deg[:BN] = BE // BN                      # the first block holds BE
    dst = np.repeat(np.arange(N_ROWS), deg)
    src = rng.integers(0, N_ROWS, dst.size)
    return src, dst


def _spec(x, sharding, dtype=None):
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=sharding)


@pytest.mark.parametrize("d", [64, 128])
def test_segment_mean_op_fwd_and_grad_compile_for_v5e(one_chip, hub_edges, d):
    src, dst = hub_edges
    blocks = build_vjp_blocks(src, dst, N_ROWS, N_ROWS)
    blk_spec = {k: _spec(v, one_chip) for k, v in blocks.items()}
    x = jax.ShapeDtypeStruct((N_ROWS, d), jnp.float32, sharding=one_chip)

    def loss(x, b):
        out = segment_mean_op(x, b, num_rows=N_ROWS, interpret=False)
        return (out * out).sum()

    fwd = jax.jit(lambda x, b: segment_mean_op(
        x, b, num_rows=N_ROWS, interpret=False)).lower(x, blk_spec).compile()
    assert "tpu_custom_call" in fwd.as_text()
    grad = jax.jit(jax.grad(loss)).lower(x, blk_spec).compile()
    # the forward kernel (re-run by the VJP's forward) and the transpose
    # kernel both stay on the chip
    assert grad.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("d", [64, 128])
def test_segment_agg_rows_traced_row_base_compiles_for_v5e(one_chip,
                                                           hub_edges, d):
    src, dst = hub_edges
    keep = dst >= N_ROWS // 2                # a rebased upper sub-range
    rr = N_ROWS - N_ROWS // 2
    indptr = np.zeros(rr + 1, np.int64)
    np.cumsum(np.bincount(dst[keep] - N_ROWS // 2, minlength=rr),
              out=indptr[1:])
    b = build_edge_blocks(indptr, src[keep])
    msgs = jax.ShapeDtypeStruct((b.num_chunks * BEC, d), jnp.float32,
                                sharding=one_chip)
    row_base = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def rows(m, ldst, mask, blk, deg, base):
        return segment_agg_rows(m, ldst, mask, blk, deg, row_base=base,
                                num_rows=N_ROWS, interpret=False)

    c = jax.jit(rows).lower(
        msgs, _spec(b.local_dst, one_chip), _spec(b.mask, one_chip),
        _spec(b.chunk_block, one_chip), _spec(b.deg, one_chip),
        row_base).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("d", [128, 256])
def test_trimmed_eval_layer_compiles_for_v5e(one_chip, hub_edges, d,
                                             monkeypatch):
    """The evaluation's last layer over a 2% split's in-edges: compact
    renumbered node blocks, stacked for two partitions of unequal block
    counts (one holds the hub block), the kernel vmapped over them, and
    the block-map scatter of its output into a zero array."""
    from repro.engine.stacking import build_stacked_trim_blocks
    from repro.graph.distributed import make_pallas_trim_agg
    from repro.kernels import segment_agg

    src, dst = hub_edges
    rng = np.random.default_rng(1)
    e = np.full((2, src.size), N_ROWS - 1, np.int32)
    e_src, e_dst, e_mask = e.copy(), e.copy(), np.zeros(e.shape, np.float32)
    rows = np.zeros((2, N_ROWS), bool)
    for p, frac in enumerate((0.02, 0.005)):
        e_src[p], e_dst[p], e_mask[p] = src, dst, 1.0
        pick = rng.choice(N_ROWS - 1, int(frac * N_ROWS), replace=False)
        rows[p, pick] = True
    rows[0, 5] = True                        # a hub-block row
    deg = np.maximum(np.bincount(dst, minlength=N_ROWS), 1).astype(np.float32)
    trim = build_stacked_trim_blocks(e_src, e_dst, e_mask, rows,
                                     np.stack([deg.reshape(-1, BN)] * 2))
    nb = N_ROWS // BN
    kept = (trim["out"] < nb).sum(1)
    assert kept[0] != kept[1] and trim["out"].shape[1] < nb
    # the TPU lowering of the kernel, though the backend here is the CPU
    monkeypatch.setattr(segment_agg, "default_interpret", lambda: False)
    agg = make_pallas_trim_agg(N_ROWS)
    h = jax.ShapeDtypeStruct((2, N_ROWS, d), jnp.float32, sharding=one_chip)
    c = jax.jit(jax.vmap(lambda x, t: agg(x, {"blk_trim": t}))).lower(
        h, {k: _spec(v, one_chip) for k, v in trim.items()}).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "scatter" in text


# gat-flickr at P=4: forward / transpose chunks, node blocks
GAT_T, GAT_T_T, GAT_NB = 2416, 2261, 476


@pytest.mark.parametrize("heads,width", [(4, 1024), (6, 42)])
def test_gat_kernels_compile_for_v5e(one_chip, heads, width):
    """Each call of one layer's forward (and transpose: the same kernel on
    fewer chunks) and of its SDDMM, at the layer's head groups."""
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f = width // heads
    for h0, h1 in head_groups(heads, width):
        w = (h1 - h0) * f
        for t in (GAT_T, GAT_T_T):
            agg = jax.jit(lambda m, l, a, b: gat_agg_blocks(
                m, l, a, b, GAT_NB, heads=h1 - h0, head0=h0,
                interpret=False)).lower(
                spec((t * BEC, w)), spec((t, BEC), jnp.int32),
                spec((t, heads, BEC)), spec((t,), jnp.int32)).compile()
            assert "tpu_custom_call" in agg.as_text()
        sddmm = jax.jit(lambda g, m, l, b: gat_sddmm_blocks(
            g, m, l, b, h1 - h0, interpret=False)).lower(
            spec((GAT_NB * BN, w)), spec((GAT_T * BEC, w)),
            spec((GAT_T, BEC), jnp.int32), spec((GAT_T,), jnp.int32)
            ).compile()
        assert "tpu_custom_call" in sddmm.as_text()


def test_gat_train_step_runs_the_calls_the_benchmark_counts(one_chip):
    """A GAT training step at the published heads and widths (a small
    graph), compiled for the chip, holds the kernel calls that
    ``perfbench/kernels/gat_agg.py`` and ``gat_sddmm.py`` count for one
    step: per hidden layer two calls of two heads for each of the forward,
    its recomputation in the backward pass and the transpose, and for the
    last layer the forward and the transpose (its recomputation is
    dropped); one SDDMM call a head group."""
    import re

    from repro.graph import GAT
    from repro.kernels.gat_agg import attention_agg_op, build_gat_blocks

    n, d = 1024, 64
    rng = np.random.default_rng(0)
    blocks = build_gat_blocks(rng.integers(0, n, 8000),
                              np.sort(rng.integers(0, n, 8000)), n, n)
    model = GAT(feature_dim=d, num_classes=7)

    def loss(params, x, blocks):
        agg = lambda z, ss, sd, _: attention_agg_op(
            z, ss, sd, blocks, num_rows=n, interpret=False)
        h = x
        for i, lp in enumerate(params.layers):
            h = model.layer(lp, h, None, agg, i, i == model.num_layers - 1)
        return (h * h).sum()

    spec = lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                          sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss)).lower(
        jax.tree.map(spec, model.init(0)), spec(np.zeros((n, d), np.float32)),
        jax.tree.map(spec, blocks)).compile().as_text()
    calls = [line for line in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    count = lambda name: sum(
        re.match(rf"\s*%\w*{name}\w*(\.\d+)? = ", line) is not None
        for line in calls)
    assert count("gat_agg") == 2 * 3 * 2 + 2 == 14
    assert count("gat_sddmm") == 2 * 2 + 1 == 5
