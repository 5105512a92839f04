"""Compile-only checks of the ``segment_agg`` kernel for a TPU v5e.

Nothing runs: each test lowers the kernel for a described (not attached)
v5e chip and compiles it with the TPU compiler, which refuses what
interpret mode accepts — block shapes off the (8, 128) tiling, unsupported
in-kernel primitives, more VMEM than a kernel may use.  Shapes are the
default job's: products-s at P=4 under EW partitioning, whose hub-heavy
node blocks hold up to 13,952 in-edges (``BE``), with the 64-wide input
features and the 128-wide hidden layer.

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
