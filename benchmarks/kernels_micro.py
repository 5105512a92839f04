"""Kernel microbenchmarks: us_per_call of the jnp reference path, and
allclose drift vs the Pallas kernel (interpreted off a TPU; this guards
correctness + tracks the oracle's cost, not a device time)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import emit


def _time(fn, *args, iters=5) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> None:
    rng = np.random.default_rng(0)

    # segment_agg on a power-law graph
    n, d = 4096, 128
    deg = np.minimum(np.random.default_rng(1).zipf(1.5, n), 64)
    indptr = np.concatenate([[0], np.cumsum(deg)])
    indices = rng.integers(0, n, indptr[-1])
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    src = jnp.asarray(indices)
    dst = jnp.asarray(np.repeat(np.arange(n), deg))
    ref_fn = jax.jit(lambda x_: ref.segment_agg_ref(x_, src, dst, n))
    us = _time(ref_fn, x)
    agg = ops.make_segment_agg(indptr, indices)
    err = float(jnp.abs(agg(x) - ref_fn(x)).max())
    emit("kernel", {"name": "segment_agg", "n": n, "d": d, "edges": int(indptr[-1]),
                    "us_per_call_ref_cpu": round(us, 1), "pallas_max_err": err})

    # flash attention
    b, hq, hkv, s, dh = 1, 8, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(b, hq, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, dh)).astype(np.float32))
    ref_fn = jax.jit(lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=True))
    us = _time(ref_fn, q, k, v)
    err = float(jnp.abs(ops.flash_attention(q, k, v, causal=True)
                        - ref_fn(q, k, v)).max())
    emit("kernel", {"name": "flash_attention", "bhsd": f"{b}x{hq}x{s}x{dh}",
                    "us_per_call_ref_cpu": round(us, 1), "pallas_max_err": err})

    # rmsnorm
    x = jnp.asarray(rng.normal(size=(8192, 1024)).astype(np.float32))
    w = jnp.ones((1024,), jnp.float32)
    ref_fn = jax.jit(lambda x_: ref.rmsnorm_ref(x_, w))
    us = _time(ref_fn, x)
    err = float(jnp.abs(ops.rmsnorm(x, w) - ref_fn(x)).max())
    emit("kernel", {"name": "rmsnorm", "shape": "8192x1024",
                    "us_per_call_ref_cpu": round(us, 1), "pallas_max_err": err})


if __name__ == "__main__":
    main()
