"""Pallas TPU kernels for multi-head edge attention (GAT, Veličković et al.
2018) over the ragged chunk layout of :mod:`.segment_agg`.

A GAT layer with ``K`` heads of width ``F`` sums, per destination ``v`` and
head ``k``, its in-neighbours' projected rows weighted by a learned
attention ``alpha[u->v, k]`` (a softmax over ``v``'s in-edges and ``v``
itself).  On the chunk layout this is the mean kernel's one-hot x message
matmul with the one-hot entries set to ``alpha`` in place of the 0/1 mask,
once per head:

    out(BN, K*F)[:, head k] += onehot_alpha_k(BN, BEC) @ msgs(BEC, K*F)[:, head k]

``alpha`` enters as ``(T, K, BEC)``: per chunk one row of 128 edge weights
per head, edges on the lanes like the one-hot's columns.  Its VJP has two
parts, each a kernel over the same layout:

  ``gat_agg``    over the CSC mirror, the cotangent flows dst -> src weighted
                 by the same ``alpha``, permuted from the dst-blocked chunk
                 slots to the src-blocked ones by a host-built slot map
                 (``t_slot``);
  ``gat_sddmm``  ``dL/dalpha[e, k] = <g[dst_e, k], z[src_e, k]>``: one dot
                 product per edge and head over the dst-blocked chunks, the
                 block's cotangent rows resident in VMEM and ``g[dst_e]``
                 formed on the MXU as ``onehot^T`` products, with no gather.

The edge softmax itself runs in XLA on the chunk layout
(:func:`edge_softmax_blocks`, named scope ``gat.edge_softmax``): the
per-destination maximum over the in-edges and the self score, then the
normalised exponentials, so it is exact and no denominator is ever 0.
:func:`attention_agg_ref` is the same layer aggregation over a flat edge
list in ``jax.numpy`` segment ops (the ``use_pallas_agg=False`` backend).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .segment_agg import (BEC, BN, build_vjp_blocks, count_pallas_call,
                          default_interpret, edge_slots)

__all__ = ["GROUP_LANES", "LEAKY_SLOPE", "attention_agg_op", "attention_agg_ref",
           "build_gat_blocks", "edge_softmax_blocks", "gat_agg_blocks",
           "gat_agg_op", "gat_sddmm_blocks", "head_groups",
           "transpose_slot_map"]

LEAKY_SLOPE = 0.2     # the paper's LeakyReLU negative slope
# the widest slice of message columns one kernel call gathers per edge: the
# per-edge gather is the largest temporary (at width 1,024 on Flickr's
# 1.24M padded edge slots, 4.7 GB), so wider layers go in groups of heads
GROUP_LANES = 512


def transpose_slot_map(src: np.ndarray, dst: np.ndarray, num_rows: int,
                       num_src_rows: int, num_t_chunks: int,
                       pad: int) -> np.ndarray:
    """``(num_t_chunks, BEC)`` int32: for each slot of the src-blocked
    (transpose) chunk layout of the edges ``src -> dst``, the slot of the
    same edge in the dst-blocked (forward) layout; ``pad`` (one past the
    forward layout, where the transpose reads a zero weight) where the
    slot is padding."""
    out = np.full(num_t_chunks * BEC, pad, dtype=np.int64)
    out[edge_slots(dst, src, num_src_rows)] = edge_slots(src, dst, num_rows)
    return out.reshape(num_t_chunks, BEC).astype(np.int32)


def build_gat_blocks(src: np.ndarray, dst: np.ndarray, num_rows: int,
                     num_src_rows: int) -> dict[str, np.ndarray]:
    """:func:`~.segment_agg.build_vjp_blocks` plus the slot map
    ``t_slot`` the attention's transpose reads alpha through."""
    blocks = build_vjp_blocks(src, dst, num_rows, num_src_rows)
    blocks["t_slot"] = transpose_slot_map(
        src, dst, num_rows, num_src_rows, blocks["t_src"].shape[0],
        pad=blocks["src"].size)
    return blocks


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _head_cols(width: int, heads: int, k: int):
    """(1, width) bool: the columns of head ``k`` (heads are contiguous)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    f = width // heads
    return (cols >= k * f) & (cols < (k + 1) * f)


def _gat_agg_kernel(blk_ref, ldst_ref, alpha_ref, msgs_ref, out_ref, *,
                    heads: int, head0: int):
    """One edge chunk of one node block; ``out_ref`` is the block's
    accumulator, resident across the block's consecutive chunks.  The
    messages hold ``heads`` heads, whose weights are the rows ``head0``
    onwards of ``alpha_ref``."""
    t = pl.program_id(0)
    b = blk_ref[t]
    acc_dt = out_ref.dtype
    width = out_ref.shape[-1]
    f = width // heads

    @pl.when((t == 0) | (blk_ref[jnp.maximum(t - 1, 0)] != b))
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, acc_dt)

    rows = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0],
                                                ldst_ref.shape[-1]), 0)
    hit = rows == ldst_ref[...]                               # (BN, BEC)
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=acc_dt)
    zero = jnp.zeros((), acc_dt)
    if f % 128 == 0:
        # lane-aligned heads: one (BN, BEC) x (BEC, F) matmul per head
        for k in range(heads):
            a = alpha_ref[head0 + k:head0 + k + 1, :].astype(acc_dt)
            onehot = jnp.where(hit, a, zero)
            sl = slice(k * f, (k + 1) * f)
            out_ref[:, sl] += dot(onehot, msgs_ref[:, sl].astype(acc_dt))
    else:
        # narrow heads share lanes: full-width products, each head's
        # columns kept from its own
        msgs = msgs_ref[...].astype(acc_dt)
        acc = jnp.zeros(out_ref.shape, acc_dt)
        for k in range(heads):
            a = alpha_ref[head0 + k:head0 + k + 1, :].astype(acc_dt)
            onehot = jnp.where(hit, a, zero)
            acc += jnp.where(_head_cols(width, heads, k), dot(onehot, msgs),
                             zero)
        out_ref[...] += acc


def gat_agg_blocks(msgs: jnp.ndarray,       # (T * BEC, G * F) gathered rows
                   local_dst: jnp.ndarray,  # (T, BEC) int32 in [0, BN)
                   alpha: jnp.ndarray,      # (T, K, BEC) edge weights
                   chunk_block: jnp.ndarray,  # (T,) int32
                   num_blocks: int, *, heads: int | None = None,
                   head0: int = 0,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Attention-weighted sum per destination row of the ``heads`` heads
    (all ``K`` by default) from ``head0`` on: ``(num_blocks * BN, G * F)``.
    Padded slots must carry ``alpha == 0``."""
    if interpret is None:
        interpret = default_interpret()
    count_pallas_call(interpret)
    t, k_all, bec = alpha.shape
    heads = k_all if heads is None else heads
    width = msgs.shape[-1]
    acc_dt = jnp.float64 if msgs.dtype == jnp.float64 else jnp.float32
    out = pl.pallas_call(
        functools.partial(_gat_agg_kernel, heads=heads, head0=head0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t,),
            in_specs=[
                pl.BlockSpec((None, 1, bec), lambda i, blk: (i, 0, 0)),
                pl.BlockSpec((None, k_all, bec), lambda i, blk: (i, 0, 0)),
                pl.BlockSpec((bec, width), lambda i, blk: (i, 0)),
            ],
            out_specs=pl.BlockSpec((BN, width), lambda i, blk: (blk[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks * BN, width), acc_dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gat_agg",
    )(
        jnp.asarray(chunk_block, jnp.int32),
        jnp.asarray(local_dst, jnp.int32).reshape(t, 1, bec),
        alpha.astype(acc_dt),
        msgs.reshape(t * bec, width),
    )
    return out.astype(msgs.dtype)


def _gat_sddmm_kernel(blk_ref, ldst_ref, g_ref, msgs_ref, out_ref, *,
                      heads: int):
    """Per edge of one chunk and per head, the dot product of the edge's
    destination cotangent row with its source message row."""
    acc_dt = out_ref.dtype
    width = msgs_ref.shape[-1]
    f = width // heads
    rows = jax.lax.broadcasted_iota(jnp.int32, (g_ref.shape[0],
                                                ldst_ref.shape[-1]), 0)
    hit = rows == ldst_ref[...]                               # (BN, BEC)
    # s[v, e] = <g[v], msgs[e]> over one head's columns; the edge's own
    # destination row is picked by the one-hot
    dot_nt = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=acc_dt)
    zero = jnp.zeros((), acc_dt)
    for k in range(heads):
        if f % 128 == 0:
            sl = slice(k * f, (k + 1) * f)
            s = dot_nt(g_ref[:, sl].astype(acc_dt),
                       msgs_ref[:, sl].astype(acc_dt))
        else:
            g = jnp.where(_head_cols(width, heads, k),
                          g_ref[...].astype(acc_dt), zero)
            s = dot_nt(g, msgs_ref[...].astype(acc_dt))
        out_ref[k:k + 1, :] = jnp.sum(jnp.where(hit, s, zero), axis=0,
                                      keepdims=True)


def gat_sddmm_blocks(g: jnp.ndarray,          # (nb * BN, K * F) cotangent
                     msgs: jnp.ndarray,       # (T * BEC, K * F) source rows
                     local_dst: jnp.ndarray,  # (T, BEC) int32
                     chunk_block: jnp.ndarray,  # (T,) int32
                     heads: int, *,
                     interpret: bool | None = None) -> jnp.ndarray:
    """``(T, K, BEC)``: per chunk slot and head, ``<g[dst], msgs[slot]>``
    over the head's columns (padded slots read garbage: mask them)."""
    if interpret is None:
        interpret = default_interpret()
    count_pallas_call(interpret)
    t, bec = local_dst.shape
    width = msgs.shape[-1]
    acc_dt = jnp.float64 if msgs.dtype == jnp.float64 else jnp.float32
    out = pl.pallas_call(
        functools.partial(_gat_sddmm_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t,),
            in_specs=[
                pl.BlockSpec((None, 1, bec), lambda i, blk: (i, 0, 0)),
                pl.BlockSpec((BN, width), lambda i, blk: (blk[i], 0)),
                pl.BlockSpec((bec, width), lambda i, blk: (i, 0)),
            ],
            out_specs=pl.BlockSpec((None, heads, bec),
                                   lambda i, blk: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, heads, bec), acc_dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gat_sddmm",
    )(
        jnp.asarray(chunk_block, jnp.int32),
        jnp.asarray(local_dst, jnp.int32).reshape(t, 1, bec),
        g.astype(acc_dt),
        msgs.reshape(t * bec, width).astype(acc_dt),
    )
    return out.astype(msgs.dtype)


# ---------------------------------------------------------------------------
# the differentiable op: forward over the dst-blocked chunks, VJP = the
# transpose (gat_agg over the CSC mirror) and the SDDMM (gat_sddmm)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _GatMeta:
    """Static (hashable) config of one gat_agg_op call site."""

    num_rows: int      # output rows
    n_in: int          # rows of z the gradient must cover
    num_blocks: int    # node blocks of the forward layout
    interpret: bool


def head_groups(heads: int, width: int) -> list[tuple[int, int]]:
    """The heads ``[h0, h1)`` of each kernel call: as many whole heads as
    fit :data:`GROUP_LANES` message lanes, so one call's per-edge gather
    is at most that wide."""
    f = width // heads
    g = max(1, min(heads, GROUP_LANES // f))
    return [(h, min(heads, h + g)) for h in range(0, heads, g)]


def _gat_fwd_impl(meta, z, alpha, src, dst, blk, mask):
    alpha = alpha * mask[:, None, :].astype(alpha.dtype)   # padding: 0
    heads = alpha.shape[1]
    f = z.shape[-1] // heads
    outs = []
    for h0, h1 in head_groups(heads, z.shape[-1]):
        if outs:
            # one group's per-edge gather alive at a time
            z, outs[-1] = jax.lax.optimization_barrier((z, outs[-1]))
        msgs = z[:, h0 * f:h1 * f][src.reshape(-1)]   # XLA gather, per chunk
        outs.append(gat_agg_blocks(msgs, dst, alpha, blk, meta.num_blocks,
                                   heads=h1 - h0, head0=h0,
                                   interpret=meta.interpret))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out[:meta.num_rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gat_core(meta, z, alpha, src, dst, blk, mask, t_src, t_dst, t_blk,
              t_slot):
    return _gat_fwd_impl(meta, z, alpha, src, dst, blk, mask)


def _gat_core_fwd(meta, z, alpha, src, dst, blk, mask, t_src, t_dst, t_blk,
                  t_slot):
    out = _gat_fwd_impl(meta, z, alpha, src, dst, blk, mask)
    return out, (z, alpha, src, dst, blk, mask, t_src, t_dst, t_blk, t_slot)


def _gat_core_bwd(meta, res, g):
    z, alpha, src, dst, blk, mask, t_src, t_dst, t_blk, t_slot = res
    t, heads, bec = alpha.shape
    f = z.shape[-1] // heads
    groups = head_groups(heads, z.shape[-1])
    gpad = jnp.concatenate(
        [g, jnp.zeros((meta.num_blocks * BN - meta.num_rows, g.shape[-1]),
                      g.dtype)], axis=0)
    # alpha in the transpose's chunk slots (pad slots read the appended 0)
    flat = jnp.concatenate(
        [jnp.swapaxes(alpha, 0, 1).reshape(heads, t * bec),
         jnp.zeros((heads, 1), alpha.dtype)], axis=1)
    t_t = t_slot.shape[0]
    alpha_t = jnp.swapaxes(
        flat[:, t_slot.reshape(-1)].reshape(heads, t_t, bec), 0, 1)
    # the messages are gathered again here, not kept from the forward: tied
    # to the cotangent, XLA cannot merge this gather with the forward's
    # (which would hold every layer's per-edge rows until its backward);
    # each group's gathers wait for the previous group's kernel, so one
    # per-edge gather is alive at a time
    z, gpad = jax.lax.optimization_barrier((z, gpad))
    dalpha = []
    for h0, h1 in groups:
        if dalpha:
            z, gpad, dalpha[-1] = jax.lax.optimization_barrier(
                (z, gpad, dalpha[-1]))
        cols = slice(h0 * f, h1 * f)
        dalpha.append(gat_sddmm_blocks(
            gpad[:, cols], z[:, cols][src.reshape(-1)], dst, blk, h1 - h0,
            interpret=meta.interpret))
    dalpha = dalpha[0] if len(dalpha) == 1 else jnp.concatenate(dalpha,
                                                                 axis=1)
    dalpha = dalpha * mask[:, None, :].astype(dalpha.dtype)
    gpad, dalpha = jax.lax.optimization_barrier((gpad, dalpha))
    dz = []
    for h0, h1 in groups:
        if dz:
            gpad, dz[-1] = jax.lax.optimization_barrier((gpad, dz[-1]))
        dz.append(gat_agg_blocks(
            gpad[:, h0 * f:h1 * f][t_src.reshape(-1)], t_dst, alpha_t, t_blk,
            -(-meta.n_in // BN), heads=h1 - h0, head0=h0,
            interpret=meta.interpret))
    dz = dz[0] if len(dz) == 1 else jnp.concatenate(dz, axis=1)
    # the block structure is static graph data: zero cotangents
    return (dz[:meta.n_in], dalpha) + (None,) * 8


_gat_core.defvjp(_gat_core_fwd, _gat_core_bwd)


def gat_agg_op(z: jnp.ndarray, alpha: jnp.ndarray, blocks: dict, *,
               num_rows: int, interpret: bool | None = None) -> jnp.ndarray:
    """``out[v, head k] = sum over in-edges e of v of alpha[e, k] *
    z[src_e, head k]`` as a ``(num_rows, K * F)`` array, differentiable in
    ``z`` and ``alpha``.  ``alpha`` is ``(T, K, BEC)`` on the forward chunk
    slots of ``blocks`` (:func:`build_gat_blocks` arrays, traced ok), zero
    on padded slots."""
    if interpret is None:
        interpret = default_interpret()
    meta = _GatMeta(num_rows=int(num_rows), n_in=int(z.shape[0]),
                    num_blocks=int(blocks["deg"].shape[0]),
                    interpret=bool(interpret))
    return _gat_core(meta, z, alpha, blocks["src"], blocks["dst"],
                     blocks["blk"], blocks["mask"], blocks["t_src"],
                     blocks["t_dst"], blocks["t_blk"], blocks["t_slot"])


# ---------------------------------------------------------------------------
# edge softmax and the layer's aggregation (self term included)
# ---------------------------------------------------------------------------

def _scores(s_src, s_dst):
    return jax.nn.leaky_relu(s_src + s_dst, LEAKY_SLOPE)


def _softmax(e, e_self, seg, real, num_rows: int):
    """Softmax of the edge scores ``e`` (K, E) per destination ``seg`` (E,)
    together with each destination's self score ``e_self`` (K, num_rows):
    ``(alpha (K, E), alpha_self (K, num_rows))``; ``real`` (E,) marks the
    edges that count (padding takes weight 0).  Heads lead, so the edges
    lie along the lanes (an (E, K) array would pad K to 128 lanes)."""
    heads = e.shape[0]
    seg = jnp.where(real, seg, num_rows)          # padding -> a dropped row
    neg = jnp.full((), -jnp.inf, e.dtype)
    per_head = lambda op, x: jax.vmap(
        lambda row: op(row, seg, num_segments=num_rows + 1))(x)[:, :num_rows]
    m = per_head(jax.ops.segment_max, jnp.where(real, e, neg))
    # the shift cancels in the ratio: no gradient through it
    m = jax.lax.stop_gradient(jnp.maximum(m, e_self))
    m_e = jnp.concatenate([m, jnp.zeros((heads, 1), m.dtype)], axis=1)[:, seg]
    p = jnp.exp(jnp.where(real, e - m_e, neg))
    p_self = jnp.exp(e_self - m)
    den = p_self + per_head(jax.ops.segment_sum, p)
    den_e = jnp.concatenate([den, jnp.ones((heads, 1), den.dtype)],
                            axis=1)[:, seg]
    return p / den_e, p_self / den


def _edge_scores(s_src, s_dst, src, seg, num_rows: int):
    """Per-head scores of the edges ``src -> seg`` and of each destination
    with itself, heads leading: ``(e (K, E), e_self (K, num_rows))``."""
    s_src, s_dst = s_src.T, s_dst.T                # (K, N)
    return (_scores(s_src[:, src], s_dst[:, seg]),
            _scores(s_src[:, :num_rows], s_dst[:, :num_rows]))


def edge_softmax_blocks(s_src: jnp.ndarray, s_dst: jnp.ndarray, blocks: dict,
                        num_rows: int):
    """Attention over the forward chunk layout of ``blocks``: per-node score
    halves ``s_src``, ``s_dst`` (N, K) -> ``(alpha (T, K, BEC), alpha_self
    (num_rows, K))``."""
    with jax.named_scope("gat.edge_softmax"):
        t, bec = blocks["src"].shape
        src = blocks["src"].reshape(-1)
        seg = (blocks["blk"][:, None] * BN + blocks["dst"]).reshape(-1)
        real = blocks["mask"].reshape(-1) > 0
        seg = jnp.minimum(seg, num_rows - 1)
        e, e_self = _edge_scores(s_src, s_dst, src, seg, num_rows)
        alpha, alpha_self = _softmax(e, e_self, seg, real, num_rows)
        alpha = jnp.swapaxes(alpha.reshape(-1, t, bec), 0, 1)
    return alpha, alpha_self.T


def _with_self(out, z, alpha_self):
    n, heads = alpha_self.shape
    zs = z[:n].reshape(n, heads, -1) * alpha_self[..., None]
    return out + zs.reshape(n, -1)


def attention_agg_op(z: jnp.ndarray, s_src: jnp.ndarray, s_dst: jnp.ndarray,
                     blocks: dict, *, num_rows: int,
                     interpret: bool | None = None) -> jnp.ndarray:
    """One GAT layer's aggregation through the Pallas kernels: the edge
    softmax on the chunk layout, the alpha-weighted sum of ``z`` (N, K * F)
    over each destination's in-edges, and the destination's own row at its
    self weight.  ``(num_rows, K * F)``."""
    alpha, alpha_self = edge_softmax_blocks(s_src, s_dst, blocks, num_rows)
    out = gat_agg_op(z, alpha, blocks, num_rows=num_rows, interpret=interpret)
    return _with_self(out, z, alpha_self)


def attention_agg_ref(z: jnp.ndarray, s_src: jnp.ndarray, s_dst: jnp.ndarray,
                      src: jnp.ndarray, dst: jnp.ndarray, mask: jnp.ndarray,
                      num_rows: int) -> jnp.ndarray:
    """:func:`attention_agg_op` over a flat edge list in ``jax.numpy``
    segment ops (``mask`` 0 marks padding edges)."""
    with jax.named_scope("gat.edge_softmax"):
        real = mask > 0
        seg = jnp.minimum(dst, num_rows - 1)
        e, e_self = _edge_scores(s_src, s_dst, src, seg, num_rows)
        alpha, alpha_self = _softmax(e, e_self, seg, real, num_rows)
    heads = alpha.shape[0]
    msg = z[src].reshape(src.shape[0], heads, -1) * alpha.T[..., None]
    out = jax.ops.segment_sum(msg.reshape(src.shape[0], -1),
                              jnp.where(real, seg, num_rows),
                              num_segments=num_rows + 1)[:num_rows]
    return _with_self(out, z, alpha_self.T)
