"""Per-kernel correctness: Pallas (interpreted off a TPU) vs pure-jnp oracle,
swept over shapes and dtypes — including the custom VJP of the unified
aggregation op (``segment_mean_op``), whose backward must stage the
transpose-blocked kernel and match ``jax.grad`` of the jnp reference."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jax_cache import CACHE_PRELUDE, subprocess_env
from repro.kernels import ops, ref

RNG = np.random.default_rng(11)


def _rand(shape, dtype):
    x = RNG.normal(0, 1, shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


# ------------------------------------------------------------ segment_agg --

def _random_csr(n, max_deg, seed):
    rng = np.random.default_rng(seed)
    indptr = [0]
    indices = []
    for _ in range(n):
        k = int(rng.integers(0, max_deg + 1))
        indices.extend(rng.integers(0, n, k))
        indptr.append(indptr[-1] + k)
    return np.asarray(indptr), np.asarray(indices, dtype=np.int64)


@pytest.mark.parametrize("n,d,max_deg", [(64, 16, 4), (200, 48, 9), (300, 130, 6)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mean", [True, False])
def test_segment_agg_sweep(n, d, max_deg, dtype, mean):
    indptr, indices = _random_csr(n, max_deg, seed=n + max_deg)
    x = _rand((n, d), dtype)
    agg = ops.make_segment_agg(indptr, indices, mean=mean)
    got = agg(x)
    src = jnp.asarray(indices)
    dst = jnp.asarray(np.repeat(np.arange(n), np.diff(indptr)))
    want = ref.segment_agg_ref(x, src, dst, n, mean=mean)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_segment_agg_isolated_nodes():
    indptr = np.array([0, 0, 2, 2])
    indices = np.array([0, 2])
    x = _rand((3, 8), jnp.float32)
    agg = ops.make_segment_agg(indptr, indices, mean=True)
    out = agg(x)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0)       # no in-edges
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray((x[0] + x[2]) / 2), rtol=1e-6)


# -------------------------------------------------- segment_mean_op (VJP) --

def _edges_of(indptr, indices):
    n = len(indptr) - 1
    return (np.asarray(indices, np.int64),
            np.repeat(np.arange(n), np.diff(indptr)))


@pytest.mark.parametrize("n,d,max_deg", [(64, 16, 4), (300, 130, 6)])
@pytest.mark.parametrize("mean", [True, False])
def test_segment_mean_op_grad_matches_ref(n, d, max_deg, mean):
    """``jax.grad`` through the custom-VJP op == ``jax.grad`` through the
    jnp reference, on ragged CSR graphs including zero-degree rows."""
    indptr, indices = _random_csr(n, max_deg, seed=n + max_deg)
    src, dst = _edges_of(indptr, indices)
    x = _rand((n, d), jnp.float32)
    w = _rand((n, d), jnp.float32)
    agg = ops.make_segment_agg(indptr, indices, mean=mean)
    srcj, dstj = jnp.asarray(src), jnp.asarray(dst)
    g_op = jax.grad(lambda x: (agg(x) * w).sum())(x)
    g_ref = jax.grad(lambda x: (ref.segment_agg_ref(
        x, srcj, dstj, n, mean=mean) * w).sum())(x)
    np.testing.assert_allclose(np.asarray(g_op), np.asarray(g_ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("split_kind", ["mixed", "zero_range", "full_range"])
@pytest.mark.parametrize("mean", [True, False])
def test_segment_mean_op_rows_grad(split_kind, mean):
    """The row-range variant (traced ``row_base`` placement — the overlapped
    forward's boundary half) has the same VJP treatment: gradients match the
    jnp row-range oracle, including the empty (all-pad-block) range."""
    from repro.kernels.segment_agg import build_vjp_blocks, segment_mean_op

    rng = np.random.default_rng(5)
    n, d = 300, 24
    n_int = {"mixed": 141, "zero_range": n, "full_range": 0}[split_kind]
    rr = n - n_int
    deg = rng.integers(0, 6, rr) if rr else np.zeros(0, np.int64)
    rdst = np.repeat(np.arange(rr), deg)
    rsrc = rng.integers(0, n, int(deg.sum())).astype(np.int64)
    blocks = {k: jnp.asarray(v)
              for k, v in build_vjp_blocks(rsrc, rdst, rr, n).items()}
    x = _rand((n, d), jnp.float32)
    w = _rand((n, d), jnp.float32)
    f_op = lambda x: (segment_mean_op(
        x, blocks, num_rows=n, row_base=n_int, mean=mean) * w).sum()
    f_ref = lambda x: (ref.segment_agg_rows_ref(
        x, jnp.asarray(rsrc), jnp.asarray(rdst), max(1, rr), n_int, n,
        mean=mean) * w).sum()
    np.testing.assert_allclose(np.asarray(jax.grad(f_op)(x)),
                               np.asarray(jax.grad(f_ref)(x)),
                               atol=1e-5, rtol=1e-5)
    if split_kind == "zero_range":
        assert np.abs(np.asarray(jax.grad(f_op)(x))).max() == 0.0


def test_segment_mean_op_stages_fwd_and_bwd_kernels():
    """BOTH directions of the pass stage the Pallas kernel: the vjp's
    forward stages >= 1 call, applying the vjp stages >= 1 more (the
    transpose-blocked backward), and a ``jax.jit(jax.grad(...))`` trace
    stages both."""
    from repro.kernels import segment_agg as sa

    indptr, indices = _random_csr(100, 5, seed=3)
    agg = ops.make_segment_agg(indptr, indices, mean=True)
    x = _rand((100, 32), jnp.float32)

    before = sa.pallas_call_count()
    out, vjp = jax.vjp(agg, x)
    mid = sa.pallas_call_count()
    assert mid - before >= 1, "forward kernel never staged under jax.vjp"
    (gx,) = vjp(jnp.ones_like(out))
    after = sa.pallas_call_count()
    assert after - mid >= 1, "BACKWARD kernel never staged by the custom VJP"

    before = sa.pallas_call_count()
    jax.jit(jax.grad(lambda x: agg(x).sum())).lower(x)
    staged = sa.pallas_call_count() - before
    assert staged >= 2, f"expected fwd+bwd kernels in the grad trace, {staged}"


FP64_GRAD_SCRIPT = (
    CACHE_PRELUDE
    + "jax.config.update('jax_enable_x64', True)\n"
    + r"""
import numpy as np, jax.numpy as jnp
from jax.test_util import check_grads
from repro.kernels import ref
from repro.kernels.segment_agg import build_vjp_blocks, segment_mean_op

# NOTE on "fwd": forward-mode AD is undefined for jax.custom_vjp ops, so the
# forward direction is checked as bitwise primal equality against the fp64
# oracle (exact inputs — see below); "rev" runs numeric check_grads to
# SECOND order — the backward re-enters the custom VJP (transpose of the
# transpose), so grad-of-grad exercises the kernel too.
#
# "Bit-for-bit where exact": integer-valued features with POWER-OF-TWO
# degrees make every quantity dyadic — sums are exact in any order and the
# mean's divisions are exact — so kernel and oracle must agree to the last
# bit even though their reduction orders differ.  Non-dyadic degrees make
# the mean-mode GRADIENT order-dependent in the last ulp (each edge adds a
# rounded w/deg), which is what check_grads covers instead.
rng = np.random.default_rng(2)
n, d = 200, 16

def ragged_pow2_case(zero_frac, seed):
    r = np.random.default_rng(seed)
    deg = r.choice([1, 2, 4, 8], n)
    deg[r.random(n) < zero_frac] = 0          # zero-degree rows
    dst = np.repeat(np.arange(n), deg)
    src = r.integers(0, n, int(deg.sum())).astype(np.int64)
    return src, dst

for zero_frac, seed in ((0.25, 0), (0.9, 1)):
    src, dst = ragged_pow2_case(zero_frac, seed)
    blocks = {k: jnp.asarray(v) for k, v in build_vjp_blocks(src, dst, n, n).items()}
    xi = jnp.asarray(rng.integers(-8, 9, (n, d)).astype(np.float64))
    wi = jnp.asarray(rng.integers(-4, 5, (n, d)).astype(np.float64))
    xr = jnp.asarray(rng.normal(0, 1, (n, d)))
    for mean in (True, False):
        got = segment_mean_op(xi, blocks, num_rows=n, mean=mean)
        want = ref.segment_agg_ref(xi, jnp.asarray(src), jnp.asarray(dst), n, mean=mean)
        assert (np.asarray(got) == np.asarray(want)).all(), "fwd not bitwise"
        import jax
        g_op = jax.grad(lambda x: (segment_mean_op(x, blocks, num_rows=n, mean=mean) * wi).sum())(xi)
        g_rf = jax.grad(lambda x: (ref.segment_agg_ref(x, jnp.asarray(src), jnp.asarray(dst), n, mean=mean) * wi).sum())(xi)
        assert (np.asarray(g_op) == np.asarray(g_rf)).all(), "grad not bitwise"
        check_grads(lambda x: segment_mean_op(x, blocks, num_rows=n, mean=mean),
                    (xr,), order=2, modes=("rev",))

# row-range sub-ranges: block-unaligned offset AND the empty range whose
# structure is one all-pad block
for n_int in (137, n):
    rr = n - n_int
    deg = rng.integers(0, 5, rr) if rr else np.zeros(0, np.int64)
    rdst = np.repeat(np.arange(rr), deg)
    rsrc = rng.integers(0, n, int(deg.sum())).astype(np.int64)
    rb = {k: jnp.asarray(v) for k, v in build_vjp_blocks(rsrc, rdst, rr, n).items()}
    xr = jnp.asarray(rng.normal(0, 1, (n, d)))
    check_grads(lambda x: segment_mean_op(x, rb, num_rows=n, row_base=n_int),
                (xr,), order=2, modes=("rev",))
    if rr == 0:
        import jax
        g = jax.grad(lambda x: segment_mean_op(x, rb, num_rows=n, row_base=n_int).sum())(xr)
        assert np.abs(np.asarray(g)).max() == 0.0, "all-pad block leaked grad"
print("FP64_GRAD_OK")
"""
)


def test_segment_mean_op_fp64_check_grads():
    """fp64 gradient tier (subprocess: x64 must not leak): primal bitwise vs
    the fp64 oracle on exact inputs, bitwise grad parity, second-order
    ``check_grads`` on the ragged sweep, row-range sub-ranges and the
    all-pad block."""
    res = subprocess.run([sys.executable, "-c", FP64_GRAD_SCRIPT],
                         capture_output=True, text=True, timeout=1200,
                         env=subprocess_env())
    assert res.returncode == 0, res.stderr[-3000:]
    assert "FP64_GRAD_OK" in res.stdout


# --------------------------------------------------------- flash_attention --

CASES = [
    # b, hq, hkv, sq, sk, dh, causal, window, q_off
    (2, 4, 2, 128, 128, 64, True, None, 0),
    (1, 8, 8, 200, 200, 32, True, None, 0),       # MHA, ragged seq
    (1, 4, 1, 96, 96, 64, True, None, 0),         # MQA
    (2, 4, 2, 256, 256, 64, True, 64, 0),         # sliding window
    (1, 4, 2, 1, 300, 64, True, None, 300),       # decode, ragged kv
    (1, 2, 2, 64, 64, 128, False, None, 0),       # encoder (bidirectional)
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    b, hq, hkv, sq, sk, dh, causal, window, q_off = case
    q = _rand((b, hq, sq, dh), dtype)
    k = _rand((b, hkv, sk, dh), dtype)
    v = _rand((b, hkv, sk, dh), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_off, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_off)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_matches_model_chunked_attention():
    """The Pallas kernel and the model's pure-JAX chunked attention are
    twins: same math, different execution substrate."""
    from repro.models.layers import chunked_attention
    q = _rand((1, 4, 160, 64), jnp.float32)
    k = _rand((1, 2, 160, 64), jnp.float32)
    v = _rand((1, 2, 160, 64), jnp.float32)
    a = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    b = chunked_attention(q, k, v, causal=True, chunk_q=32, chunk_k=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------- rmsnorm --

@pytest.mark.parametrize("shape", [(4, 128), (3, 7, 512), (2, 5, 33, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _rand(shape, dtype)
    w = _rand((shape[-1],), jnp.float32)
    got = ops.rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
