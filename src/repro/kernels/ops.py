"""jit'd public wrappers around the Pallas kernels.

The segment-aggregation wrappers run the Pallas kernel compiled on a TPU and
interpreted elsewhere (``segment_agg.default_interpret``).  Every wrapper
has the same signature as its `ref.py` oracle so call sites (and tests) can
swap them 1:1.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention_pallas
from .rmsnorm import rmsnorm_pallas
from .segment_agg import (EdgeBlocks, build_edge_blocks, build_vjp_blocks,
                          segment_mean_op)

__all__ = [
    "segment_agg", "make_segment_agg", "segment_mean_op", "build_vjp_blocks",
    "make_mean_blocks", "flash_attention", "rmsnorm",
    "build_edge_blocks", "EdgeBlocks",
]


def make_mean_blocks(indptr: np.ndarray, indices: np.ndarray) -> dict:
    """Host-side: paired forward/transpose block structure for
    :func:`segment_mean_op` from a CSR graph (``num_src_rows == num_rows``)."""
    indptr = np.asarray(indptr)
    n = len(indptr) - 1
    dst = np.repeat(np.arange(n), np.diff(indptr))
    return build_vjp_blocks(np.asarray(indices), dst, num_rows=n,
                            num_src_rows=n)


def make_segment_agg(indptr: np.ndarray, indices: np.ndarray, *, mean: bool = True,
                     use_pallas: bool = True):
    """Bind the static CSR block structure once per graph; returns
    ``agg(x) -> (N, D)`` suitable for jit closure.

    The Pallas path routes through :func:`segment_mean_op`, so the returned
    closure is DIFFERENTIABLE: ``jax.grad`` through it stages the transpose
    aggregation kernel instead of falling back to jnp scatter ops.
    """
    n = len(indptr) - 1
    if not use_pallas:
        src = jnp.asarray(indices)
        dst = jnp.asarray(np.repeat(np.arange(n), np.diff(indptr)))
        return lambda x: ref.segment_agg_ref(x, src, dst, n, mean=mean)

    blocks = {k: jnp.asarray(v)
              for k, v in make_mean_blocks(indptr, indices).items()}

    def agg(x: jnp.ndarray) -> jnp.ndarray:
        return segment_mean_op(x, blocks, num_rows=n, mean=mean)

    return agg


def segment_agg(x, indptr, indices, *, mean: bool = True):
    """One-shot convenience (rebuilds block structure; prefer make_segment_agg)."""
    return make_segment_agg(np.asarray(indptr), np.asarray(indices),
                            mean=mean)(x)


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "interpret",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_offset: int = 0, block_q: int = 128, block_k: int = 256,
                    interpret: bool = True):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, weight, *, eps: float = 1e-6, interpret: bool = True):
    return rmsnorm_pallas(x, weight, eps=eps, interpret=interpret)
