"""Pallas TPU kernel: flash attention with GQA, causal mask and sliding
window — the transformer-side compute hot-spot (prefill_32k, long_500k-swa).

Classic online-softmax tiling [Dao et al.], re-thought for the TPU memory
hierarchy: (BQ × Dh) query tiles and (BK × Dh) key/value tiles live in VMEM,
the (BQ × BK) logits tile is produced on the MXU, and the softmax running
statistics (m, l) plus the (BQ × Dh) accumulator are VMEM scratch carried
across the *sequential* innermost grid dimension (TPU grids execute the last
axis in order — the idiomatic replacement for a CUDA persistent-CTA loop).

Grid: (B, Hq, Sq/BQ, Sk/BK); KV tiles for query head h come from KV head
``h // (Hq // Hkv)`` via the BlockSpec index map (GQA without materialising
repeated KV).  Causal and sliding-window structure short-circuits whole
(q-tile, k-tile) cells with ``pl.when`` — skipped tiles cost no FLOPs, which
is exactly how the kernel turns the 500k-context decode into O(window).

VMEM per cell ≈ (BQ + 2·BK)·Dh·4 + BQ·BK·4 ≈ (128+512)·128·4 + 64 KiB ≈ 0.4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

DEFAULT_BQ = 128
DEFAULT_BK = 256
NEG_INF = -1e30



def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int | None,
                  q_offset: int, bq: int, bk: int, nk: int, kv_len: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qi = pl.program_id(2)
    q_start = qi * bq + q_offset          # absolute position of this q tile
    k_start = ki * bk

    # tile-level structural skip
    live = True
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)          # (BQ, Dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (BK, Dh)
        v = v_ref[0, 0].astype(jnp.float32)          # (BK, Dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # (BQ, BK)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < kv_len          # tail-padding of the KV sequence
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (BQ, BK)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,            # (B, Hq, Sq, Dh)
    k: jnp.ndarray,            # (B, Hkv, Sk, Dh)
    v: jnp.ndarray,            # (B, Hkv, Sk, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = True,
) -> jnp.ndarray:
    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, "GQA requires Hq to be a multiple of Hkv"
    group = hq // hkv
    scale = float(1.0 / (dh**0.5))

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    sq_pad = ((sq + bq - 1) // bq) * bq
    sk_pad = ((sk + bk - 1) // bk) * bk
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        # tail-padded key positions are excluded by the kv_len mask in-kernel
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
    nq, nk = sq_pad // bq, sk_pad // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, nk=nk, kv_len=sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_pad, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :]
