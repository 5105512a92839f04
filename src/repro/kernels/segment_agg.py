"""Pallas TPU kernel: blocked CSR segment aggregation (the GNN hot-spot).

GraphSAGE's Eq. 1 mean-aggregation is an SpMM: out[v] = Σ_{u∈N(v)} x[u] / |N(v)|.
A CUDA implementation scatters with atomics; TPUs have no scatter-atomics, so
we ADAPT (DESIGN.md §2): destination nodes are grouped into blocks of ``BN``
consecutive rows, whose incoming edges (contiguous in CSR!) are cut into
chunks of ``BEC`` edges; the gather ``msgs = x[src]`` stays in XLA (which
lowers it to efficient dynamic-slices), and the kernel performs the
reduction as a **one-hot × message matmul on the MXU**:

    out(BN, D) += onehot(local_dst)(BN, BEC) @ msgs(BEC, D)

i.e. the irregular segment-sum becomes a dense systolic matmul — the
TPU-native rendering of scatter-add.

Layout is RAGGED: a node block owns ``max(1, ceil(edges / BEC))`` chunks
(only its last chunk is padded), so padded edges are at most one chunk per
block instead of the power-law hub's in-degree times every block.  The grid
walks the chunks in order (an ``"arbitrary"`` reduction axis); a
scalar-prefetched chunk→block map points the output BlockSpec at the
chunk's node block, so the ``(BN, D)`` accumulator stays resident in VMEM
across the block's consecutive chunks and is written back once.  VMEM per
grid step is one ``(BEC, D)`` message chunk plus one ``(BN, D)``
accumulator, whatever the in-degree.  The feature axis is never padded in
HBM: each block spans the full ``D``.

The op is DIFFERENTIABLE end-to-end: :func:`segment_mean_op` wraps the
forward in a ``jax.custom_vjp`` whose backward is the transpose aggregation
(grad flows dst → src over the same edges) through the same one-hot × matmul
kernel on a CSC-ordered :class:`EdgeBlocks` mirror (DESIGN.md §6), so
full-graph training keeps both directions of the pass on the MXU.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["EdgeBlocks", "build_edge_blocks", "build_edge_blocks_from_edges",
           "build_transpose_blocks", "build_vjp_blocks", "count_pallas_call",
           "default_interpret", "edge_slots",
           "segment_agg_blocks", "segment_agg_rows", "segment_agg_bwd_blocks",
           "segment_mean_op", "pallas_call_count", "interpreted_call_count",
           "reset_pallas_call_count"]

BN = 128    # destination nodes per block
BEC = 128   # edges per chunk: the MXU contraction of one grid step

# Trace-time observability: bumped every time the Pallas kernel is staged
# into a jaxpr (and, separately, every time it is staged in interpret mode).
# Lets callers (and tests) assert the kernel is actually on the hot path
# rather than silently swapped for the jnp reference, and that a chip run
# staged no interpreted kernel.
_PALLAS_CALLS = 0
_INTERPRETED_CALLS = 0


def pallas_call_count() -> int:
    return _PALLAS_CALLS


def interpreted_call_count() -> int:
    return _INTERPRETED_CALLS


def reset_pallas_call_count() -> None:
    global _PALLAS_CALLS, _INTERPRETED_CALLS
    _PALLAS_CALLS = 0
    _INTERPRETED_CALLS = 0


def count_pallas_call(interpret: bool) -> None:
    """Record one Pallas kernel staged into a jaxpr (every kernel of the
    package counts here)."""
    global _PALLAS_CALLS, _INTERPRETED_CALLS
    _PALLAS_CALLS += 1
    _INTERPRETED_CALLS += bool(interpret)


def default_interpret() -> bool:
    """Pallas interpret mode, derived from the backend: compiled on a TPU,
    interpreted (the kernel body run as plain XLA ops) everywhere else."""
    return jax.default_backend() != "tpu"


@dataclass(frozen=True)
class EdgeBlocks:
    """Static, ragged block structure for one CSR graph (host preprocessing).

    Chunks are in node-block order; every block owns at least one chunk so
    the kernel writes every output row."""

    num_nodes: int
    num_blocks: int            # nb = max(1, ceil(num_nodes / BN))
    num_chunks: int            # T >= nb
    src: np.ndarray            # (T, BEC) int32, pad -> 0 (masked)
    local_dst: np.ndarray      # (T, BEC) int32 in [0, BN), pad -> 0
    mask: np.ndarray           # (T, BEC) float32
    chunk_block: np.ndarray    # (T,) int32 node block of each chunk, sorted
    deg: np.ndarray            # (nb, BN) float32 (>=1 where real)


def _chunk_layout(indptr: np.ndarray, bn: int, bec: int):
    """The ragged layout of a CSR: ``(chunks per node block, total chunks,
    each CSR edge's slot in the flat (T * bec) chunk layout)``."""
    indptr = np.asarray(indptr, dtype=np.int64)
    n = len(indptr) - 1
    nb = max(1, (n + bn - 1) // bn)
    lo = indptr[np.minimum(np.arange(nb) * bn, n)]
    hi = indptr[np.minimum((np.arange(nb) + 1) * bn, n)]
    chunks = np.maximum(1, (hi - lo + bec - 1) // bec)
    first = np.cumsum(chunks) - chunks            # first chunk of each block
    blk = np.repeat(np.arange(n), np.diff(indptr)) // bn
    slot = first[blk] * bec + (np.arange(len(blk)) - lo[blk])
    return chunks, int(chunks.sum()), slot


def edge_slots(src: np.ndarray, dst: np.ndarray, num_rows: int,
               bn: int = BN, bec: int = BEC) -> np.ndarray:
    """Slot of each edge ``src[e] -> dst[e]`` in the flat chunk layout that
    :func:`build_edge_blocks_from_edges` gives the same edge list."""
    dst = np.asarray(dst, dtype=np.int64)
    order = np.argsort(dst, kind="stable")
    indptr = np.zeros(num_rows + 1, dtype=np.int64)
    np.cumsum(np.bincount(dst, minlength=num_rows)[:num_rows], out=indptr[1:])
    slot = np.empty(len(dst), dtype=np.int64)
    slot[order] = _chunk_layout(indptr, bn, bec)[2]
    return slot


def build_edge_blocks(indptr: np.ndarray, indices: np.ndarray, bn: int = BN,
                      bec: int = BEC) -> EdgeBlocks:
    indptr = np.asarray(indptr, dtype=np.int64)
    n = len(indptr) - 1
    nb = max(1, (n + bn - 1) // bn)
    chunks, t, slot = _chunk_layout(indptr, bn, bec)

    dst = np.repeat(np.arange(n), np.diff(indptr))
    blk = dst // bn
    src = np.zeros(t * bec, dtype=np.int32)
    ldst = np.zeros(t * bec, dtype=np.int32)
    mask = np.zeros(t * bec, dtype=np.float32)
    src[slot] = np.asarray(indices)[: len(dst)]
    ldst[slot] = dst - blk * bn
    mask[slot] = 1.0
    deg = np.ones(nb * bn, dtype=np.float32)
    deg[:n] = np.maximum(np.diff(indptr), 1)
    return EdgeBlocks(
        num_nodes=n, num_blocks=nb, num_chunks=t,
        src=src.reshape(t, bec), local_dst=ldst.reshape(t, bec),
        mask=mask.reshape(t, bec),
        chunk_block=np.repeat(np.arange(nb, dtype=np.int32), chunks),
        deg=deg.reshape(nb, bn),
    )


def build_edge_blocks_from_edges(src: np.ndarray, dst: np.ndarray,
                                 num_rows: int, bn: int = BN,
                                 bec: int = BEC) -> EdgeBlocks:
    """:func:`build_edge_blocks` over an explicit edge list (``dst`` need not
    be sorted; a stable dst-sort reproduces the CSR per-row edge order)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    order = np.argsort(dst, kind="stable")
    counts = np.bincount(dst, minlength=num_rows)[:num_rows]
    indptr = np.zeros(num_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return build_edge_blocks(indptr, src[order], bn=bn, bec=bec)


def build_transpose_blocks(src: np.ndarray, dst: np.ndarray,
                           num_src_rows: int, bn: int = BN,
                           bec: int = BEC) -> EdgeBlocks:
    """CSC-ordered mirror of a CSR block structure: blocks for the TRANSPOSE
    aggregation over the same edges (grad flows dst -> src), i.e. edges
    re-grouped by SOURCE with the original destinations as the gather index.
    This is the static structure of the backward kernel of
    :func:`segment_mean_op`."""
    return build_edge_blocks_from_edges(dst, src, num_src_rows, bn=bn, bec=bec)


def build_vjp_blocks(src: np.ndarray, dst: np.ndarray, num_rows: int,
                     num_src_rows: int, bn: int = BN,
                     bec: int = BEC) -> dict[str, np.ndarray]:
    """Paired forward (dst-blocked CSR) + backward (src-blocked CSC mirror)
    structures for :func:`segment_mean_op`, as a flat dict of arrays (a
    pytree: stacks along a leading partition axis and nests cleanly under
    ``vmap`` / ``shard_map``).

    ``num_rows`` is the aggregation's output row range (destinations live in
    ``[0, num_rows)``); ``num_src_rows`` is the gathered-from row space the
    gradient must cover (sources live in ``[0, num_src_rows)``; the op's
    input ``x`` has exactly this many rows).
    """
    fwd = build_edge_blocks_from_edges(src, dst, num_rows, bn=bn, bec=bec)
    bwd = build_transpose_blocks(src, dst, num_src_rows, bn=bn, bec=bec)
    return {"src": fwd.src, "dst": fwd.local_dst, "mask": fwd.mask,
            "blk": fwd.chunk_block, "deg": fwd.deg,
            "t_src": bwd.src, "t_dst": bwd.local_dst, "t_mask": bwd.mask,
            "t_blk": bwd.chunk_block}


def _segment_agg_kernel(blk_ref, ldst_ref, mask_ref, msgs_ref, deg_ref,
                        out_ref, *, mean: bool):
    """One edge chunk of one node block; ``out_ref`` is the block's
    accumulator, resident across the block's consecutive chunks."""
    t = pl.program_id(0)
    last = pl.num_programs(0) - 1
    b = blk_ref[t]
    # accumulate in the input precision for float64 (interpret-mode oracles
    # and the fp64 grad checks need exact arithmetic), float32 otherwise
    acc_dt = out_ref.dtype

    @pl.when((t == 0) | (blk_ref[jnp.maximum(t - 1, 0)] != b))
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, acc_dt)

    rows = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0],
                                                ldst_ref.shape[-1]), 0)
    onehot = jnp.where(rows == ldst_ref[...], mask_ref[...].astype(acc_dt),
                       jnp.zeros((), acc_dt))                 # (BN, BEC)
    out_ref[...] += jax.lax.dot_general(
        onehot, msgs_ref[...].astype(acc_dt), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=acc_dt)

    if mean:
        @pl.when((t == last) | (blk_ref[jnp.minimum(t + 1, last)] != b))
        def _finish():
            out_ref[...] = out_ref[...] / deg_ref[...].astype(acc_dt)


def segment_agg_blocks(
    msgs: jnp.ndarray,        # (T * BEC, D) gathered edge messages
    local_dst: jnp.ndarray,   # (T, BEC) int32 in [0, BN)
    mask: jnp.ndarray,        # (T, BEC) float32
    chunk_block: jnp.ndarray,  # (T,) int32 node block of each chunk
    deg: jnp.ndarray,         # (nb, BN) float32 (>=1 where real)
    *,
    mean: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Array-based kernel entry: the block structure arrives as (possibly
    traced) arrays, so the call nests cleanly under ``vmap`` / ``shard_map``
    where each program instance owns a different partition's blocks.  Only
    the SHAPES must agree across instances (the SPMD engine pads them to a
    common (T, nb)).  Returns (nb * BN, D); caller unpads rows.

    ``interpret`` defaults to :func:`default_interpret`; passing ``False``
    asks for the TPU lowering (e.g. to compile for a described chip).
    """
    if interpret is None:
        interpret = default_interpret()
    count_pallas_call(interpret)
    t, bec = local_dst.shape
    nb, bn = deg.shape
    d = msgs.shape[-1]
    acc_dt = jnp.float64 if msgs.dtype == jnp.float64 else jnp.float32

    out = pl.pallas_call(
        functools.partial(_segment_agg_kernel, mean=mean),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t,),
            in_specs=[
                pl.BlockSpec((None, 1, bec), lambda i, blk: (i, 0, 0)),
                pl.BlockSpec((None, 1, bec), lambda i, blk: (i, 0, 0)),
                pl.BlockSpec((bec, d), lambda i, blk: (i, 0)),
                pl.BlockSpec((bn, 1), lambda i, blk: (blk[i], 0)),
            ],
            out_specs=pl.BlockSpec((bn, d), lambda i, blk: (blk[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb * bn, d), acc_dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="segment_agg",
    )(
        jnp.asarray(chunk_block, jnp.int32),
        jnp.asarray(local_dst, jnp.int32).reshape(t, 1, bec),
        jnp.asarray(mask).reshape(t, 1, bec),
        msgs.reshape(t * bec, d),
        jnp.asarray(deg).reshape(nb * bn, 1),
    )
    return out.astype(msgs.dtype)


def _place(out: jnp.ndarray, row_base, num_rows: int) -> jnp.ndarray:
    """Place kernel output rows at the (possibly traced) ``row_base`` inside
    a zero ``(num_rows, D)`` output; the target is padded by the block rows
    so dynamic_update_slice never clamps for row_base <= num_rows."""
    target = jnp.zeros((num_rows + out.shape[0], out.shape[1]), out.dtype)
    target = jax.lax.dynamic_update_slice(
        target, out, (jnp.asarray(row_base, jnp.int32), jnp.int32(0)))
    return target[:num_rows]


def segment_agg_rows(
    msgs: jnp.ndarray,        # (T * BEC, D) gathered edge messages
    local_dst: jnp.ndarray,   # (T, BEC) int32 in [0, BN)
    mask: jnp.ndarray,        # (T, BEC) float32
    chunk_block: jnp.ndarray,  # (T,) int32
    deg: jnp.ndarray,         # (nb, BN) float32 (>=1 where real)
    *,
    row_base,                 # int or traced scalar: first output row
    num_rows: int,            # static total output rows
    mean: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Row-range (masked) kernel entry: aggregate a REBASED sub-range of the
    node space and place it at ``row_base`` inside a zero ``(num_rows, D)``
    output.

    The block structure covers only the sub-range's rows (e.g. the boundary
    rows ``[n_int, n_own)`` of a partition, rebased to start at 0), so the
    kernel pays for ``ceil(range / BN)`` node blocks instead of the whole
    local space; ``row_base`` may be a traced scalar, which is what lets the
    per-partition boundary offset vary under ``vmap``/``shard_map``.  Rows
    outside ``[row_base, row_base + num_blocks * BN)`` are exactly zero; an
    empty range (all-pad blocks, the zero-boundary partition) yields an
    all-zero output.
    """
    out = segment_agg_blocks(msgs, local_dst, mask, chunk_block, deg,
                             mean=mean, interpret=interpret)
    return _place(out, row_base, num_rows)


# ---------------------------------------------------------------------------
# differentiable unified op: forward (CSR-blocked) + backward (CSC-blocked)
# ---------------------------------------------------------------------------
#
# out[r] = (1/deg[r]) * sum_{edges (u, r)} x[u]   (placed at row_base in a
# zero (num_rows, D) output).  The VJP is ITSELF a segment aggregation over
# the same edges with source and destination swapped:
#
#     dL/dx[u] = sum_{edges (u, r)} g[r] / deg[r]
#
# so the backward reuses the one-hot x matmul kernel on the CSC-ordered
# transpose structure (build_transpose_blocks) — both directions of the pass
# stay on the MXU, no scatter-add anywhere.

@dataclass(frozen=True)
class _MeanOpMeta:
    """Static (hashable) config of one segment_mean_op call site."""

    num_rows: int    # output rows
    n_in: int        # rows of x the gradient must cover
    mean: bool
    interpret: bool


def _segment_mean_fwd_impl(meta: _MeanOpMeta, x, src, dst, mask, blk, deg,
                           row_base):
    msgs = x[src.reshape(-1)]                   # XLA gather, per-chunk layout
    out = segment_agg_blocks(msgs, dst, mask, blk, deg, mean=meta.mean,
                             interpret=meta.interpret)
    return _place(out, row_base, meta.num_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _segment_mean_core(meta, x, src, dst, mask, blk, deg, t_src, t_dst,
                       t_mask, t_blk, row_base):
    return _segment_mean_fwd_impl(meta, x, src, dst, mask, blk, deg, row_base)


def segment_agg_bwd_blocks(
    g: jnp.ndarray,           # (num_rows, D) cotangent of the op's output
    blocks: dict,             # the SAME build_vjp_blocks arrays as the fwd
    *,
    n_in: int,                # rows of the x space to produce
    mean: bool = True,
    row_base=0,               # int or traced scalar (matches the forward)
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Source-blocked BACKWARD kernel entry: scale the output cotangent by
    the forward 1/deg (mean) and aggregate it dst -> src through the same
    one-hot × matmul kernel over the CSC-ordered transpose blocks.  Returns
    ``(n_in, D) = dL/dx``.

    Implemented as the core op with forward and transpose structures
    SWAPPED (the transpose of the transpose is the forward), so the
    backward pass is itself differentiable — second-order ``check_grads``
    recurses through the same custom VJP instead of hitting the raw
    ``pallas_call``.
    """
    if interpret is None:
        interpret = default_interpret()
    deg = blocks["deg"]
    d_feat = g.shape[-1]
    range_cap = deg.shape[0] * deg.shape[1]     # rows the fwd kernel produced
    # un-place: rows [row_base, row_base + range_cap) of the padded cotangent
    # are the fwd kernel's output rows (rows sliced off by the forward's
    # [:num_rows] read zero cotangent here, exactly mirroring the placement)
    gpad = jnp.concatenate(
        [g, jnp.zeros((range_cap, d_feat), g.dtype)], axis=0)
    gsub = jax.lax.dynamic_slice(
        gpad, (jnp.asarray(row_base, jnp.int32), jnp.int32(0)),
        (range_cap, d_feat))
    if mean:
        gsub = gsub / deg.reshape(-1)[:, None].astype(gsub.dtype)
    meta_t = _MeanOpMeta(num_rows=n_in, n_in=range_cap, mean=False,
                         interpret=interpret)
    # the transpose blocks cover the n_in source rows (build_vjp_blocks'
    # num_src_rows); their degree is unused by the sum
    t_deg = jnp.ones((max(1, -(-n_in // deg.shape[1])), deg.shape[1]),
                     jnp.float32)
    return _segment_mean_core(
        meta_t, gsub, blocks["t_src"], blocks["t_dst"], blocks["t_mask"],
        blocks["t_blk"], t_deg, blocks["src"], blocks["dst"], blocks["mask"],
        blocks["blk"], jnp.int32(0))


def _segment_mean_fwd(meta, x, src, dst, mask, blk, deg, t_src, t_dst, t_mask,
                      t_blk, row_base):
    # re-enter the custom-vjp op (not the raw impl): higher-order AD
    # differentiates the fwd/bwd RULES, so both must resolve to the custom
    # VJP again instead of exposing the raw pallas_call to jvp/transpose
    out = _segment_mean_core(meta, x, src, dst, mask, blk, deg, t_src, t_dst,
                             t_mask, t_blk, row_base)
    return out, (src, dst, mask, blk, deg, t_src, t_dst, t_mask, t_blk,
                 row_base)


def _segment_mean_bwd(meta, res, g):
    src, dst, mask, blk, deg, t_src, t_dst, t_mask, t_blk, row_base = res
    blocks = {"src": src, "dst": dst, "mask": mask, "blk": blk, "deg": deg,
              "t_src": t_src, "t_dst": t_dst, "t_mask": t_mask,
              "t_blk": t_blk}
    gx = segment_agg_bwd_blocks(g, blocks, n_in=meta.n_in, mean=meta.mean,
                                row_base=row_base, interpret=meta.interpret)
    # block structure and row offset are static graph data: zero cotangents
    return (gx,) + (None,) * 10


_segment_mean_core.defvjp(_segment_mean_fwd, _segment_mean_bwd)


def segment_mean_op(
    x: jnp.ndarray,                 # (n_in, D) node features / embeddings
    blocks: dict,                   # build_vjp_blocks arrays (traced ok)
    *,
    num_rows: int,                  # static output rows
    row_base=0,                     # int or traced scalar: first output row
    mean: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """THE differentiable blocked aggregation op (every forward's Eq. 1).

    Forward: gather ``x`` by the CSR block structure and reduce on the MXU
    (:func:`segment_agg_blocks`), placing the aggregated sub-range at
    ``row_base`` inside a zero ``(num_rows, D)`` output — ``row_base=0`` with
    ``num_rows = n`` is the plain full-space aggregation, a nonzero traced
    ``row_base`` is the overlapped forward's boundary half.  Backward: a
    ``jax.custom_vjp`` that runs the transpose aggregation through the same
    kernel over the CSC-ordered mirror (:func:`segment_agg_bwd_blocks`), so
    ``jax.grad`` stages a SECOND Pallas call instead of falling back to jnp
    scatter ops.  ``blocks`` may be (possibly traced, e.g. per-partition
    stacked) arrays from :func:`build_vjp_blocks`; only shapes must be
    static.
    """
    if interpret is None:
        interpret = default_interpret()
    meta = _MeanOpMeta(num_rows=int(num_rows), n_in=int(x.shape[0]),
                       mean=bool(mean), interpret=bool(interpret))
    return _segment_mean_core(
        meta, x, blocks["src"], blocks["dst"], blocks["mask"], blocks["blk"],
        blocks["deg"], blocks["t_src"], blocks["t_dst"], blocks["t_mask"],
        blocks["t_blk"], jnp.asarray(row_base, jnp.int32))
