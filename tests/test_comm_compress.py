"""Compressed-communication unit tests (the PR-9 tentpole's codecs).

1. Wire codecs (``quantize_rows`` / ``dequantize_rows``): hypothesis
   property sweep over row counts, widths, dynamic-range exponents, modes
   and input dtypes (f32 AND bf16) — deterministic payloads, all-zero rows
   round-trip to exact zeros (pad/trash hygiene), single-element rows, and
   the int8 worst-case round-trip error stays within the per-row
   ``amax / 127`` quantization-step bound.
2. Error feedback: over a repeated EF-quantized send of a fixed tensor the
   time-mean residual vanishes (the telescoping identity ``mean(deq) - x =
   -r_T / T``), a chi-squared-style statistic over normalized per-element
   mean residuals stays far below its degrees of freedom, and the EF
   cumulative error beats feedback-free requantization by a wide margin.
3. Gradient reducers: the stacked bucketed mean is BITWISE the plain
   ``sum/P`` (the property that lets compress=off share one oracle), and
   the stacked top-k reducer satisfies the EF conservation identity, ships
   exactly k entries per partition, and is deterministic.
4. Byte accounting: ``wire_row_bytes`` / ``grad_sync_wire_bytes`` formulas
   (dtype-truthful itemsize, no hardcoded fp32), the engine's
   ``halo_wire_bytes_per_layer`` == ``pg.halo_bytes_per_layer`` at
   compress=off on BOTH engines, and compressed eval reports the shrunken
   wire size.
5. Config validation: unknown modes, halo_compress × overlap_halo, and
   full-graph × top-k all raise.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.gp.trainer import (GRAD_COMPRESS_MODES, grad_sync_wire_bytes,
                                   grad_topk_size,
                                   make_bucketed_reduce_stacked,
                                   make_topk_reduce_stacked)
from repro.graph.distributed import (HALO_COMPRESS_MODES, dequantize_rows,
                                     quantize_rows, wire_row_bytes)


# --------------------------------------------------------------------------
# 1. codec property sweep
# --------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(1, 6), st.integers(1, 24), st.integers(-12, 12),
       st.sampled_from(["fp16", "int8"]), st.booleans())
def test_quantize_roundtrip_properties(n, d, scale_exp, mode, use_bf16):
    dtype = jnp.bfloat16 if use_bf16 else jnp.float32
    rng = np.random.default_rng((n * 7919 + d * 131 + scale_exp) & 0xFFFF)
    x_np = rng.normal(0.0, 1.0, (n, d)) * 2.0 ** scale_exp
    x_np[0] = 0.0                                   # all-zero row always in
    x = jnp.asarray(x_np, dtype)

    payload, scale = quantize_rows(x, mode)
    payload2, scale2 = quantize_rows(x, mode)
    assert (np.asarray(payload) == np.asarray(payload2)).all()
    if mode == "int8":
        assert payload.dtype == jnp.int8 and scale.dtype == jnp.float32
        assert (np.asarray(scale2) == np.asarray(scale)).all()
        assert float(np.asarray(scale).ravel()[0]) == 0.0   # zero-row scale
    else:
        assert payload.dtype == jnp.float16 and scale is None

    deq = np.asarray(dequantize_rows(payload, scale, mode, x.dtype),
                     np.float64)
    assert (deq[0] == 0.0).all(), "all-zero row must round-trip exactly"

    xf = np.asarray(x, np.float64)
    amax = np.abs(xf).max(axis=-1, keepdims=True)
    eps = float(jnp.finfo(dtype).eps)
    if mode == "int8":
        # one quantization step is amax/127; the round-trip error per
        # element is half a step plus the low-precision arithmetic slack
        # (x/scale and q*scale each round in the input dtype)
        limit = amax / 127.0 * (0.5 + 130.0 * eps) + 1e-30
    else:
        # fp16 downcast: half-ulp relative in the normal range, absolute
        # smallest-subnormal floor below it, plus input-dtype slack
        limit = np.maximum(np.abs(xf) * (2.0 ** -11 + eps), 2.0 ** -25)
    assert (np.abs(deq - xf) <= limit).all(), \
        (mode, dtype, float(np.abs(deq - xf).max()), float(limit.max()))


def test_quantize_single_element_rows():
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray([[3.5], [0.0], [-2.0 ** -9]], dtype)
        q, s = quantize_rows(x, "int8")
        deq = np.asarray(dequantize_rows(q, s, "int8", dtype), np.float64)
        # d=1: the single element IS the row amax, so the round-trip error
        # collapses to pure dtype rounding (q lands on +-127 up to one ulp
        # of the division) — far inside the half-step bound
        xf = np.asarray(x, np.float64)
        eps = float(jnp.finfo(dtype).eps)
        assert (np.abs(deq - xf)
                <= np.abs(xf) * (1.0 / 127.0 + 4 * eps) + 1e-30).all()
        assert deq[1, 0] == 0.0


def test_quantize_unknown_mode_raises():
    x = jnp.ones((2, 3), jnp.float32)
    with pytest.raises(ValueError):
        quantize_rows(x, "int4")
    with pytest.raises(ValueError):
        dequantize_rows(x, None, "int4", jnp.float32)
    with pytest.raises(ValueError):
        wire_row_bytes(8, "int4")


# --------------------------------------------------------------------------
# 2. error feedback drives the mean residual to ~0
# --------------------------------------------------------------------------

def _ef_series(x, mode, steps):
    r = jnp.zeros_like(x)
    deqs, resids = [], []
    for _ in range(steps):
        y = x + r
        payload, scale = quantize_rows(y, mode)
        deq = dequantize_rows(payload, scale, mode, x.dtype)
        r = y - deq
        deqs.append(np.asarray(deq, np.float64))
        resids.append(np.asarray(r, np.float64))
    return np.stack(deqs), np.stack(resids)


@pytest.mark.parametrize("mode", ["fp16", "int8"])
def test_error_feedback_mean_residual_vanishes(mode):
    T = 64
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(0.0, 3.0, (4, 32)), jnp.float32)
    xf = np.asarray(x, np.float64)
    deqs, resids = _ef_series(x, mode, T)

    # telescoping identity: mean_t(deq_t) - x == -r_T / T (up to f32
    # accumulation), so the time-averaged transmission converges to x at
    # rate 1/T regardless of where the EF orbit settles
    amax = np.abs(xf).max(axis=-1, keepdims=True)
    step = (np.broadcast_to(amax / 127.0, xf.shape) if mode == "int8"
            else np.maximum(np.abs(xf) * 2.0 ** -10, 2.0 ** -24))
    slack = 64 * 1.2e-7 * amax
    err = deqs - xf                              # (T, n, d) transmit errors
    mu = err.mean(0)
    assert (np.abs(mu) <= step / T + slack).all()

    # chi-squared-style statistic over half-step-normalized mean errors:
    # with error feedback every element's time-mean error is ~1/T of its
    # quantization step, so the sum of squares sits orders of magnitude
    # inside the envelope of feedback-free requantization (which re-sends
    # the SAME error each step: z ~ O(1) per element)
    z_ef = mu / step
    stat_ef = float(np.sum(z_ef ** 2))
    assert stat_ef <= xf.size * (2.0 / T) ** 2, stat_ef

    payload, scale = quantize_rows(x, mode)
    deq1 = np.asarray(dequantize_rows(payload, scale, mode, x.dtype),
                      np.float64)
    stat_plain = float(np.sum(((deq1 - xf) / step) ** 2))
    assert stat_plain > 100 * stat_ef, (stat_plain, stat_ef)


# --------------------------------------------------------------------------
# 3. gradient reducers
# --------------------------------------------------------------------------

def _rand_grads(P, rng, dtype=np.float32):
    return {"w1": jnp.asarray(rng.normal(0, 1, (P, 13, 7)), dtype),
            "b1": jnp.asarray(rng.normal(0, 1, (P, 7)), dtype),
            "w2": jnp.asarray(rng.normal(0, 1, (P, 7, 3)), dtype)}


def test_bucketed_stacked_bitwise_equals_plain_mean():
    P = 4
    rng = np.random.default_rng(3)
    grads = _rand_grads(P, rng)
    # 64-byte buckets force many chunks with a ragged tail
    red = make_bucketed_reduce_stacked(P, 64)
    out = red(grads)
    ref = jax.tree.map(lambda g: jnp.sum(g, axis=0) / P, grads)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_topk_reduce_stacked_ef_conservation_and_sparsity():
    from jax.flatten_util import ravel_pytree

    P, frac = 4, 0.05
    rng = np.random.default_rng(5)
    grads = _rand_grads(P, rng)
    flat = jax.vmap(lambda g: ravel_pytree(g)[0])(grads)
    N = flat.shape[1]
    k = grad_topk_size(N, frac)
    res0 = jnp.asarray(rng.normal(0, 0.1, (P, N)), jnp.float32)

    red = make_topk_reduce_stacked(P, frac)
    avg, res1 = red(grads, res0)
    avg2, res1b = red(grads, res0)
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(jax.tree_util.tree_leaves(avg),
                               jax.tree_util.tree_leaves(avg2)))
    assert (np.asarray(res1) == np.asarray(res1b)).all()

    # conservation: sent_p = (g_p + r_p) - r'_p has exactly k nonzeros and
    # P * avg == sum_p sent_p
    g_ef = np.asarray(flat) + np.asarray(res0)
    sent = g_ef - np.asarray(res1)
    assert ((np.abs(sent) > 0).sum(axis=1) <= k).all()
    assert ((np.abs(sent) > 0).sum(axis=1) >= 1).all()
    avg_flat, _ = ravel_pytree(avg)
    np.testing.assert_allclose(np.asarray(avg_flat) * P, sent.sum(0),
                               rtol=1e-6, atol=1e-6)

    # error feedback keeps what wasn't shipped: residual equals the unsent
    # remainder elementwise
    np.testing.assert_allclose(np.asarray(res1), g_ef - sent, rtol=1e-6,
                               atol=1e-6)


def test_grad_topk_size_bounds():
    assert grad_topk_size(1000, 0.01) == 10
    assert grad_topk_size(10, 0.001) == 1           # floor at one entry
    assert grad_topk_size(10, 9.9) == 10            # cap at param_count


# --------------------------------------------------------------------------
# 4. byte accounting (dtype-truthful, no hardcoded fp32)
# --------------------------------------------------------------------------

def test_wire_row_bytes_formula():
    assert wire_row_bytes(16, "none") == 64
    assert wire_row_bytes(16, "none", itemsize=8) == 128   # fp64 payload
    assert wire_row_bytes(16, "none", itemsize=2) == 32    # fp16 store
    assert wire_row_bytes(16, "fp16") == 32
    assert wire_row_bytes(16, "int8") == 20                # d + f32 scale
    assert wire_row_bytes(1, "int8") == 5


def test_grad_sync_wire_bytes_modes_and_ratios():
    B = 1000
    for P in (4, 8):
        none = grad_sync_wire_bytes("none", P, B)
        buck = grad_sync_wire_bytes("bucketed", P, B)
        assert none == P * (P - 1) * B * 4
        assert buck == 2 * (P - 1) * B * 4
        assert buck / none == 2 / P                 # 0.5 @ P=4, 0.25 @ P=8
    k = grad_topk_size(B, 0.01)
    assert grad_sync_wire_bytes("topk", 4, B, itemsize=4, topk_frac=0.01) \
        == 4 * 3 * k * 8
    assert grad_sync_wire_bytes("none", 4, B, itemsize=8) \
        == 2 * grad_sync_wire_bytes("none", 4, B, itemsize=4)
    assert grad_sync_wire_bytes("bucketed", 1, B) == 0
    with pytest.raises(ValueError):
        grad_sync_wire_bytes("stochastic", 4, B)


@pytest.fixture(scope="module")
def tiny_setup():
    from repro.core import GPHyperParams, partition_graph
    from repro.engine import EngineConfig, SPMDEngine, SequentialReference
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)

    def mk(cls, **over):
        cfg = EngineConfig(mode="stacked", use_pallas_agg=False,
                           dtype=jnp.float32, **over)
        return cls(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                   GPHyperParams(), cfg)

    return g, pg, model, mk, SPMDEngine, SequentialReference


def test_halo_wire_bytes_matches_pg_then_shrinks(tiny_setup):
    g, pg, model, mk, SPMDEngine, SequentialReference = tiny_setup
    d = int(pg.features.shape[-1])
    rows = int(np.asarray(pg.n_halo).sum())
    for cls in (SPMDEngine, SequentialReference):
        none = mk(cls)
        fp16 = mk(cls, halo_compress="fp16")
        int8 = mk(cls, halo_compress="int8")
        # compress=off reports EXACTLY the existing accounting (the lock
        # every pre-PR-9 byte assertion relies on)
        assert none.halo_wire_bytes_per_layer == pg.halo_bytes_per_layer
        assert fp16.halo_wire_bytes_per_layer == rows * wire_row_bytes(
            d, "fp16")
        assert int8.halo_wire_bytes_per_layer == rows * wire_row_bytes(
            d, "int8")
        assert (int8.halo_wire_bytes_per_layer
                < fp16.halo_wire_bytes_per_layer
                < none.halo_wire_bytes_per_layer)


def test_compressed_eval_reports_wire_bytes(tiny_setup):
    g, pg, model, mk, SPMDEngine, _ = tiny_setup
    eng = mk(SPMDEngine, halo_compress="int8")
    prm = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), model.init(0))
    eng.evaluate(prm, "val", per_partition_params=False)
    want = model.num_layers * eng.halo_wire_bytes_per_layer
    assert eng.last_halo_exchange_bytes == want
    assert want < model.num_layers * pg.halo_bytes_per_layer


# --------------------------------------------------------------------------
# 5. config validation
# --------------------------------------------------------------------------

def test_rejects_invalid_compression_configs(tiny_setup):
    g, pg, model, mk, SPMDEngine, SequentialReference = tiny_setup
    for cls in (SPMDEngine, SequentialReference):
        with pytest.raises(ValueError, match="halo_compress"):
            mk(cls, halo_compress="int4")
        with pytest.raises(ValueError, match="grad_compress"):
            mk(cls, grad_compress="stochastic")
        with pytest.raises(ValueError, match="overlap"):
            mk(cls, halo_compress="int8", overlap_halo=True)


def test_fullgraph_rejects_topk(tiny_setup):
    g, pg, model, mk, SPMDEngine, SequentialReference = tiny_setup
    from repro.train.optim import AdamW

    for cls in (SPMDEngine, SequentialReference):
        eng = mk(cls, grad_compress="topk")
        prm = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                           model.init(0))
        opt_state = AdamW(lr=1e-3).init(prm)
        with pytest.raises(ValueError, match="top-k"):
            eng.phase0_fullgraph_epoch(prm, opt_state, 1)


def test_mode_tuples_exported():
    assert HALO_COMPRESS_MODES == ("none", "fp16", "int8")
    assert GRAD_COMPRESS_MODES == ("none", "bucketed", "topk")
