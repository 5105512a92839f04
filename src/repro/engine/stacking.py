"""Host-side preprocessing for the SPMD engine: stack every partition's
blocked-CSR aggregation structure (and per-epoch minibatches) into uniform
``(P, ...)`` arrays.

The Pallas ``segment_agg`` kernel needs a static block layout; partitions
have ragged edge counts, so each partition's :class:`EdgeBlocks` is padded to
the fleet-wide maximum ``(num_chunks, num_blocks)``.  Padding edges carry
``mask == 0`` and source id 0, so they gather a real row but contribute
nothing to the reduction — the same trick the kernel already uses for the
tail of each block's last chunk.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..graph.distributed import PartitionedGraph
from ..graph.featstore import PartitionFeatStore, build_partition_feat_store
from ..kernels.segment_agg import (BEC, BN, build_edge_blocks,
                                   build_edge_blocks_from_edges,
                                   build_transpose_blocks)

__all__ = ["StackedBlocks", "build_stacked_vjp_blocks",
           "build_stacked_gat_blocks",
           "build_stacked_split_vjp_blocks", "build_stacked_trim_blocks",
           "build_stacked_feat_store",
           "build_stacked_halo_cache", "build_stacked_halo_residual",
           "stack_pytrees"]


def build_stacked_feat_store(pg: PartitionedGraph, hot_frac: float,
                             policy: str, dtype) -> tuple[dict, PartitionFeatStore]:
    """Stacked device/host split of the feature plane (DESIGN.md §12).

    Returns ``(device_entries, fs)``: ``device_entries`` holds the
    shard-dict additions — ``fs_hot`` (P, H, D) resident hot rows plus the
    ``fs_rows_hot``/``fs_rows_cold`` (P, H)/(P, C) int32 scatter maps —
    ready to merge into the engine's stacked shards in place of
    ``features``; ``fs`` is the underlying :class:`PartitionFeatStore`
    whose ``cold`` (P, C, D) numpy array is the per-call host staging
    buffer (it must stay OFF device — shipping it as a compiled-call
    argument is the whole point of the store).
    """
    import jax.numpy as jnp

    fs = build_partition_feat_store(pg, hot_frac, policy, np.dtype(dtype))
    entries = {"fs_hot": jnp.asarray(fs.hot, dtype),
               "fs_rows_hot": jnp.asarray(fs.rows_hot),
               "fs_rows_cold": jnp.asarray(fs.rows_cold)}
    return entries, fs


def build_stacked_halo_cache(pg: PartitionedGraph,
                             layer_dims: tuple[int, ...]) -> dict:
    """Zero-initialised historical-embedding halo cache, stacked ``(P, ...)``
    for the fused epoch programs (one leading axis per partition, carried
    through the cached eval as state).

    Per partition the cache keeps each layer's last-received exchange
    buffers in recv layout ``(P, maxS, D_layer)``; ``layer_dims`` is the
    width each layer's exchange ships (``model.layer_input_dims``: raw
    features first, then hidden embeddings).  All-zero is the correct empty
    state: pad slots must stay zero forever (trash-row hygiene), and
    :func:`halo_refresh_plan` always schedules a FULL refresh at age 0, so
    no real cached row is ever read before it has been received once.
    """
    P = pg.num_parts
    max_s = pg.send_idx.shape[-1]
    return {f"h{i}": np.zeros((P, P, max_s, d), dtype=np.float32)
            for i, d in enumerate(layer_dims)}


def build_stacked_halo_residual(pg: PartitionedGraph,
                                layer_dims: tuple[int, ...]) -> dict:
    """Zero-initialised error-feedback residual for the quantized halo
    exchange (DESIGN.md §11), stacked ``(P, ...)`` like the halo cache.

    Per partition, ``r{i}`` holds layer i's SEND-side quantization error in
    send-list layout ``(P, maxS, D_layer)`` — ``r{i}[q, s]`` is the error
    left behind the last time send slot s's row was quantized for peer q.
    Zero is the exact empty state: before the first exchange nothing has
    been rounded away, and pad slots (``send_mask == 0``) are kept zero by
    the masked residual update so they never leak into the trash row.
    """
    P = pg.num_parts
    max_s = pg.send_idx.shape[-1]
    return {f"r{i}": np.zeros((P, P, max_s, d), dtype=np.float32)
            for i, d in enumerate(layer_dims)}


@dataclass(frozen=True)
class StackedBlocks:
    """Per-partition ragged blocked CSR, padded to common shapes (leading
    axis P)."""

    num_blocks: int            # nb (common across partitions)
    num_chunks: int            # T (fleet-wide max)
    src: np.ndarray            # (P, T, BEC) int32 local source ids, pad -> 0
    local_dst: np.ndarray      # (P, T, BEC) int32 in [0, BN)
    mask: np.ndarray           # (P, T, BEC) float32
    chunk_block: np.ndarray    # (P, T) int32 node block of each chunk
    deg: np.ndarray            # (P, nb, BN) float32 (>=1 where real)


def _local_csr(pg: PartitionedGraph, p: int) -> tuple[np.ndarray, np.ndarray]:
    """Rebuild partition p's local CSR (dst-major, ascending — the order
    build_partitioned_graph emits) from its padded edge arrays."""
    real = pg.edge_mask[p] > 0
    src = pg.edge_src[p][real].astype(np.int64)
    dst = pg.edge_dst[p][real].astype(np.int64)
    counts = np.bincount(dst, minlength=pg.max_nodes)
    indptr = np.zeros(pg.max_nodes + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, src


def _stack_blocks(per_part, num_parts: int, bn: int) -> StackedBlocks:
    """Pad a list of per-partition EdgeBlocks to fleet-common shapes.  A
    partition with fewer node blocks gets one all-pad chunk per missing
    block (every block must own a chunk), and the chunk axis is padded with
    all-pad chunks reducing into the last block, keeping the chunk->block
    map sorted."""
    nb = max(b.num_blocks for b in per_part)
    T = max(b.num_chunks + nb - b.num_blocks for b in per_part)
    P = num_parts
    bec = per_part[0].src.shape[-1]
    src = np.zeros((P, T, bec), dtype=np.int32)
    ldst = np.zeros((P, T, bec), dtype=np.int32)
    mask = np.zeros((P, T, bec), dtype=np.float32)
    blk = np.full((P, T), nb - 1, dtype=np.int32)
    deg = np.ones((P, nb, bn), dtype=np.float32)
    for p, b in enumerate(per_part):
        t = b.num_chunks
        src[p, :t] = b.src
        ldst[p, :t] = b.local_dst
        mask[p, :t] = b.mask
        blk[p, :t] = b.chunk_block
        blk[p, t: t + nb - b.num_blocks] = np.arange(b.num_blocks, nb)
        deg[p, : b.num_blocks] = b.deg
    return StackedBlocks(num_blocks=nb, num_chunks=T, src=src,
                         local_dst=ldst, mask=mask, chunk_block=blk, deg=deg)


def _sub_csr(src: np.ndarray, dst: np.ndarray, mask: np.ndarray,
             num_rows: int, row_base: int = 0):
    """CSR over a destination sub-range rebased to start at row 0 (edges
    must already be dst-major ascending, as build_partitioned_graph emits)."""
    real = mask > 0
    s = src[real].astype(np.int64)
    d = dst[real].astype(np.int64) - row_base
    counts = np.bincount(d, minlength=num_rows) if num_rows else np.zeros(0, np.int64)
    indptr = np.zeros(num_rows + 1, dtype=np.int64)
    np.cumsum(counts[:num_rows], out=indptr[1:])
    return indptr, s


def _stack_vjp_dict(fwd_list, bwd_list, num_parts: int, bn: int) -> dict:
    """Pair per-partition forward + transpose EdgeBlocks into the flat
    ``segment_mean_op`` blocks dict, each side padded fleet-wide."""
    f = _stack_blocks(fwd_list, num_parts, bn)
    b = _stack_blocks(bwd_list, num_parts, bn)
    return {"src": f.src, "dst": f.local_dst, "mask": f.mask,
            "blk": f.chunk_block, "deg": f.deg,
            "t_src": b.src, "t_dst": b.local_dst, "t_mask": b.mask,
            "t_blk": b.chunk_block}


def build_stacked_vjp_blocks(pg: PartitionedGraph, bn: int = BN,
                             bec: int = BEC) -> dict:
    """Stacked paired forward/transpose block structure for the whole-space
    aggregation (``segment_mean_op`` over all ``max_nodes`` local rows):
    the forward is dst-blocked CSR, the transpose is the CSC-ordered mirror
    over the same edges (grad flows dst -> src, covering owned AND halo
    source rows so the halo exchange's VJP can route gradient back to the
    owning partition)."""
    fwds, bwds = [], []
    for p in range(pg.num_parts):
        indptr, indices = _local_csr(pg, p)
        fwds.append(build_edge_blocks(indptr, indices, bn=bn, bec=bec))
        real = pg.edge_mask[p] > 0
        bwds.append(build_transpose_blocks(
            pg.edge_src[p][real], pg.edge_dst[p][real], pg.max_nodes,
            bn=bn, bec=bec))
    return _stack_vjp_dict(fwds, bwds, pg.num_parts, bn)


def build_stacked_gat_blocks(pg: PartitionedGraph) -> dict:
    """:func:`build_stacked_vjp_blocks` plus the attention's slot map
    ``t_slot`` (P, T_t, BEC): per transpose chunk slot, the forward slot of
    the same edge, whose alpha the transpose reads (GAT only; pad slots
    point one past the forward layout, at a zero weight)."""
    from ..kernels.gat_agg import transpose_slot_map

    out = build_stacked_vjp_blocks(pg)
    real = pg.edge_mask > 0
    out["t_slot"] = np.stack([
        transpose_slot_map(pg.edge_src[p][real[p]], pg.edge_dst[p][real[p]],
                           pg.max_nodes, pg.max_nodes, out["t_src"].shape[1],
                           pad=out["src"][0].size)
        for p in range(pg.num_parts)])
    return out


def build_stacked_trim_blocks(edge_src: np.ndarray, edge_dst: np.ndarray,
                              edge_mask: np.ndarray, rows: np.ndarray,
                              deg: np.ndarray, bn: int = BN,
                              bec: int = BEC) -> dict:
    """Forward block structure over only the in-edges of the rows in
    ``rows`` (P, maxN) bool — the aggregation of a last layer whose output
    is read on those rows alone (the evaluated split).

    Blocks are COMPACT: only the node blocks that hold a ``rows`` row get
    chunks, renumbered ``0..nbk-1`` in ascending order, each owning at
    least one chunk, and are padded across partitions by
    :func:`_stack_blocks` (a partition with no such row gets all-pad
    chunks only).  A kept row keeps every in-edge, and ``deg`` (P, nb, bn)
    is the full layout's degree, sliced per kept block, so its mean is the
    full layout's.  ``out`` (P, nbk) maps each compact block to its node
    block; a pad block maps to ``nb``, one past the last node block."""
    P = edge_src.shape[0]
    nb = deg.shape[1]
    per_part, kept = [], []
    for p in range(P):
        blocks = np.unique(np.flatnonzero(rows[p]) // bn)
        compact = np.full(nb, -1, np.int64)
        compact[blocks] = np.arange(len(blocks))
        dst = edge_dst[p].astype(np.int64)
        keep = (edge_mask[p] > 0) & rows[p][dst]
        cdst = compact[dst[keep] // bn] * bn + dst[keep] % bn
        per_part.append(build_edge_blocks_from_edges(
            edge_src[p][keep], cdst, len(blocks) * bn, bn=bn, bec=bec))
        kept.append(blocks)
    s = _stack_blocks(per_part, P, bn)
    out = np.full((P, s.num_blocks), nb, np.int32)
    deg_t = np.ones((P, s.num_blocks, bn), np.float32)
    for p, blocks in enumerate(kept):
        out[p, :len(blocks)] = blocks
        deg_t[p, :len(blocks)] = deg[p, blocks]
    return {"src": s.src, "dst": s.local_dst, "mask": s.mask,
            "blk": s.chunk_block, "deg": deg_t, "out": out}


def build_stacked_split_vjp_blocks(pg: PartitionedGraph, bn: int = BN,
                                   bec: int = BEC) -> tuple[dict, dict]:
    """The overlapped forward's interior/boundary aggregation split
    (DESIGN.md §5) with the transpose mirrors attached: ``(interior,
    boundary)`` blocks dicts for the two ``segment_mean_op`` row-range
    calls.  Each half blocks ONLY its own row range — interior rows
    ``[0, n_int)``, boundary rows rebased to ``[0, n_own - n_int)`` (a
    zero-range partition contributes all-pad blocks that aggregate to
    exact zeros) — while its transpose covers the full ``max_nodes``
    source space, the gather side indexing the REBASED gradient sub-range
    the forward produced."""
    ints_f, ints_b, bnds_f, bnds_b = [], [], [], []
    for p in range(pg.num_parts):
        n_int = int(pg.n_int[p])
        ip, isrc = _sub_csr(pg.int_src[p], pg.int_dst[p], pg.int_mask[p],
                            n_int)
        ints_f.append(build_edge_blocks(ip, isrc, bn=bn, bec=bec))
        real_i = pg.int_mask[p] > 0
        ints_b.append(build_transpose_blocks(
            pg.int_src[p][real_i], pg.int_dst[p][real_i], pg.max_nodes,
            bn=bn, bec=bec))

        n_bnd = int(pg.n_own[p] - pg.n_int[p])
        bp, bsrc = _sub_csr(pg.bnd_src[p], pg.bnd_dst[p], pg.bnd_mask[p],
                            n_bnd, row_base=n_int)
        bnds_f.append(build_edge_blocks(bp, bsrc, bn=bn, bec=bec))
        real_b = pg.bnd_mask[p] > 0
        bnds_b.append(build_transpose_blocks(
            pg.bnd_src[p][real_b], pg.bnd_dst[p][real_b] - n_int,
            pg.max_nodes, bn=bn, bec=bec))
    return (_stack_vjp_dict(ints_f, ints_b, pg.num_parts, bn),
            _stack_vjp_dict(bnds_f, bnds_b, pg.num_parts, bn))


def stack_pytrees(trees):
    """Stack a list of identical-structure pytrees along a new leading axis."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
