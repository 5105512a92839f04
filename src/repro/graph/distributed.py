"""Distributed graph storage + halo exchange — DistDGL's communication
pattern rendered as TPU-native SPMD collectives.

Each partition owns a contiguous local index space (DESIGN.md §5):

    [0, n_int)                interior owned nodes: every in-neighbour is
                              local, so their aggregation needs NO halo data
    [n_int, n_own)            boundary owned nodes: >= 1 in-neighbour lives
                              on another partition
    [n_own, n_own + n_halo)   halo slots (1-hop remote in-neighbours, recv'd)
    [n_local, maxN)           padding, with ONE trash row at ``trash_row``
                              (== maxN - 1) that is guaranteed all-zero and
                              never referenced by a real edge

Per layer, boundary embeddings are exchanged with either a single
``jax.lax.all_to_all`` or a chunked ``ppermute`` ring over the data axis,
using *precomputed, padded* send lists (DistDGL's dynamic RPC → static
collective; DESIGN.md §2).  The bytes on the wire are exactly
``2 · Σ_p halo_p · D · dtype`` per forward — i.e. proportional to the
edge-cut that EW partitioning minimises.

The interior/boundary split exists so the exchange can OVERLAP compute
(:func:`make_overlap_forward`): interior rows aggregate — and the self-term
matmul runs — while the halo exchange is in flight; only the boundary rows'
aggregation waits for the landed halo embeddings.  Local edges are therefore
classified into two destination-disjoint CSR shards (interior-dst vs
boundary-dst) whose per-row edge order matches the combined edge list, so
the split aggregation is bit-for-bit identical to the synchronous one on
owned rows.

Everything is padded to identical shapes across partitions so the whole
structure stacks into (P, ...) arrays sharded over the data axis.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .csr import CSRGraph
from .sage import GraphSAGE, SAGEParams

__all__ = ["PartitionedGraph", "build_partitioned_graph", "make_distributed_forward",
           "make_overlap_forward", "make_cached_forward", "make_export_forward",
           "halo_refresh_plan", "RecomputePlanner",
           "HALO_COMPRESS_MODES", "quantize_rows", "dequantize_rows",
           "wire_row_bytes",
           "make_ref_mean_agg", "make_pallas_mean_agg",
           "make_ref_split_agg", "make_pallas_split_agg"]


@dataclass
class PartitionedGraph:
    """Stacked, padded per-partition arrays (leading axis = partition)."""

    num_parts: int
    n_own: np.ndarray          # (P,) owned-node counts
    n_int: np.ndarray          # (P,) interior counts (first n_int owned rows)
    n_halo: np.ndarray         # (P,) halo counts
    max_nodes: int             # padded local size (incl. trash row)
    own_cap: int               # max(n_own): static owned-row cap
    features: np.ndarray       # (P, maxN, D)   halo+pad rows zero
    labels: np.ndarray         # (P, maxN)      -1 on non-owned
    edge_src: np.ndarray       # (P, maxE) local ids  (pad -> trash row)
    edge_dst: np.ndarray       # (P, maxE) local ids  (pad -> trash row)
    edge_mask: np.ndarray      # (P, maxE) float32
    int_src: np.ndarray        # (P, maxEi) interior-dst edges (owned src only)
    int_dst: np.ndarray        # (P, maxEi) dst in [0, n_int)  (pad -> own_cap)
    int_mask: np.ndarray       # (P, maxEi) float32
    bnd_src: np.ndarray        # (P, maxEb) boundary-dst edges (owned+halo src)
    bnd_dst: np.ndarray        # (P, maxEb) dst in [n_int, n_own) (pad -> own_cap)
    bnd_mask: np.ndarray       # (P, maxEb) float32
    deg: np.ndarray            # (P, own_cap) float32 in-degree, clamped >= 1
    send_idx: np.ndarray       # (P, P, maxS) local owned ids to send to q
    send_mask: np.ndarray      # (P, P, maxS)
    recv_pos: np.ndarray       # (P, P, maxS) local halo slot for recv from q
    global_ids: np.ndarray     # (P, maxN) global node id (-1 pad)
    train_mask: np.ndarray     # (P, maxN) bool, owned train nodes
    val_mask: np.ndarray       # (P, maxN)
    test_mask: np.ndarray      # (P, maxN)

    @property
    def trash_row(self) -> int:
        """The one sacrificial local row (== max_nodes - 1).  Padding in the
        combined edge arrays and in ``recv_pos`` points here; the forward
        keeps it all-zero at every layer, and :func:`build_partitioned_graph`
        asserts no real edge or real recv slot ever references it."""
        return self.max_nodes - 1

    @property
    def n_boundary(self) -> np.ndarray:
        return self.n_own - self.n_int

    @property
    def halo_bytes_per_layer(self) -> int:
        d = self.features.shape[-1]
        return int(self.n_halo.sum()) * d * self.features.dtype.itemsize

    def halo_slot_bytes(self, lo: int, hi: int) -> int:
        """Real (unpadded) payload of exchanging send slots ``[lo, hi)`` of
        every partition pair, per layer — the refreshed-row bytes a cached
        forward puts on the wire.  ``halo_slot_bytes(0, maxS)`` equals
        :attr:`halo_bytes_per_layer` (every real slot lives in some pair's
        slot range, and Σ_q n_halo[q] counts each exactly once)."""
        d = self.features.shape[-1]
        real = int(self.send_mask[:, :, lo:hi].sum())
        return real * d * self.features.dtype.itemsize

    @property
    def padded_wire_bytes_per_exchange(self) -> int:
        """Bytes the padded static collective actually moves per layer
        (all pair slots padded to maxS), vs the real payload of
        :attr:`halo_bytes_per_layer`."""
        d = self.features.shape[-1]
        return int(np.prod(self.send_idx.shape)) * d * self.features.dtype.itemsize

    def summary(self) -> str:
        return (
            f"P={self.num_parts} own={self.n_own.tolist()} "
            f"int={self.n_int.tolist()} halo={self.n_halo.tolist()} "
            f"maxN={self.max_nodes} ownCap={self.own_cap} "
            f"maxE={self.edge_src.shape[1]} "
            f"maxEi={self.int_src.shape[1]} maxEb={self.bnd_src.shape[1]} "
            f"halo_bytes/layer={self.halo_bytes_per_layer}"
        )


def build_partitioned_graph(
    graph: CSRGraph, parts: np.ndarray, num_parts: int
) -> PartitionedGraph:
    parts = np.asarray(parts)
    n = graph.num_nodes
    P = num_parts
    owned0 = [np.flatnonzero(parts == p) for p in range(P)]

    # per-partition edge lists (grouped per owned dst), 1-hop halo, and the
    # interior/boundary classification: a node is BOUNDARY iff any of its
    # in-neighbours lives on another partition
    owned, halos, local_edges, n_int = [], [], [], np.zeros(P, np.int64)
    for p in range(P):
        own = owned0[p]
        src_all, dst_all = [], []
        for v in own:
            nbrs = graph.neighbors(v)
            src_all.append(nbrs)
            dst_all.append(np.full(len(nbrs), v))
        src = np.concatenate(src_all) if src_all else np.zeros(0, np.int64)
        dst = np.concatenate(dst_all) if dst_all else np.zeros(0, np.int64)
        remote = parts[src] != p
        halos.append(np.unique(src[remote]))
        is_bnd = np.zeros(n, dtype=bool)
        is_bnd[dst[remote]] = True
        interior = own[~is_bnd[own]]
        boundary = own[is_bnd[own]]
        owned.append(np.concatenate([interior, boundary]))
        n_int[p] = len(interior)
        local_edges.append((src, dst))

    n_own = np.array([len(o) for o in owned])
    n_halo = np.array([len(h) for h in halos])
    max_nodes = int((n_own + n_halo).max()) + 1          # +1 trash row
    own_cap = int(n_own.max())
    max_edges = max(1, int(max(len(e[0]) for e in local_edges)))

    d = graph.feature_dim
    feats = np.zeros((P, max_nodes, d), dtype=np.float32)
    labels = np.full((P, max_nodes), -1, dtype=np.int64)
    gids = np.full((P, max_nodes), -1, dtype=np.int64)
    trash = max_nodes - 1
    e_src = np.full((P, max_edges), trash, dtype=np.int32)
    e_dst = np.full((P, max_edges), trash, dtype=np.int32)
    e_msk = np.zeros((P, max_edges), dtype=np.float32)
    deg = np.ones((P, own_cap), dtype=np.float32)
    tr_m = np.zeros((P, max_nodes), dtype=bool)
    va_m = np.zeros((P, max_nodes), dtype=bool)
    te_m = np.zeros((P, max_nodes), dtype=bool)

    # global -> (partition, local id); locals follow the [interior | boundary]
    # owned order so boundary rows are the contiguous range [n_int, n_own)
    g2l = np.full(n, -1, dtype=np.int64)
    for p in range(P):
        g2l[owned[p]] = np.arange(n_own[p])

    halo_l = []            # (P,) global id -> halo slot, as a dense map
    for p in range(P):
        hmap = np.full(n, trash, dtype=np.int64)
        hmap[halos[p]] = n_own[p] + np.arange(n_halo[p])
        halo_l.append(hmap)

    tr, va, te = set(graph.train_idx), set(graph.val_idx), set(graph.test_idx)
    split_src, split_dst = [], []   # per-partition local edges, dst-major
    for p in range(P):
        own = owned[p]
        feats[p, : n_own[p]] = graph.features[own]
        labels[p, : n_own[p]] = graph.labels[own]
        gids[p, : n_own[p]] = own
        if len(halos[p]):
            # halo features start zero; they arrive via exchange
            gids[p, n_own[p] : n_own[p] + n_halo[p]] = halos[p]
        for j, v in enumerate(own):
            tr_m[p, j] = int(v) in tr
            va_m[p, j] = int(v) in va
            te_m[p, j] = int(v) in te

        # re-emit edges dst-major in the NEW local order (interior rows
        # first), keeping each destination's in-neighbour order — that order
        # is what makes split and combined aggregation bit-identical per row
        src, dst = local_edges[p]
        loc_src0 = np.where(parts[src] == p, g2l[src], halo_l[p][src]).astype(np.int64)
        loc_dst0 = g2l[dst]
        order = np.argsort(loc_dst0, kind="stable")
        loc_src = loc_src0[order].astype(np.int32)
        loc_dst = loc_dst0[order].astype(np.int32)
        e_src[p, : len(src)] = loc_src
        e_dst[p, : len(dst)] = loc_dst
        e_msk[p, : len(src)] = 1.0
        split_src.append(loc_src)
        split_dst.append(loc_dst)
        counts = np.bincount(loc_dst, minlength=own_cap)[:own_cap]
        deg[p] = np.maximum(counts, 1).astype(np.float32)

    # destination-disjoint CSR shards: dst-major order puts all interior-dst
    # edges (dst < n_int) ahead of the boundary-dst edges
    n_int_edges = [int(np.searchsorted(split_dst[p], n_int[p]))
                   for p in range(P)]
    max_ei = max(1, max(n_int_edges))
    max_eb = max(1, max(len(split_dst[p]) - n_int_edges[p] for p in range(P)))
    # split pads: src -> trash row (guaranteed zero, so no mask multiply is
    # needed on the hot path), dst -> the sacrificial segment row ``own_cap``
    i_src = np.full((P, max_ei), trash, dtype=np.int32)
    i_dst = np.full((P, max_ei), own_cap, dtype=np.int32)
    i_msk = np.zeros((P, max_ei), dtype=np.float32)
    b_src = np.full((P, max_eb), trash, dtype=np.int32)
    b_dst = np.full((P, max_eb), own_cap, dtype=np.int32)
    b_msk = np.zeros((P, max_eb), dtype=np.float32)
    for p in range(P):
        k = n_int_edges[p]
        i_src[p, :k] = split_src[p][:k]
        i_dst[p, :k] = split_dst[p][:k]
        i_msk[p, :k] = 1.0
        kb = len(split_src[p]) - k
        b_src[p, :kb] = split_src[p][k:]
        b_dst[p, :kb] = split_dst[p][k:]
        b_msk[p, :kb] = 1.0

    # send lists: p sends owned node g to q whenever g is in q's halo
    send_lists = [[[] for _ in range(P)] for _ in range(P)]
    recv_lists = [[[] for _ in range(P)] for _ in range(P)]
    for q in range(P):
        for g in halos[q]:
            p = int(parts[g])
            send_lists[p][q].append(int(g2l[g]))
            recv_lists[q][p].append(int(halo_l[q][g]))
    max_s = max(1, max(len(send_lists[p][q]) for p in range(P) for q in range(P)))
    s_idx = np.zeros((P, P, max_s), dtype=np.int32)
    s_msk = np.zeros((P, P, max_s), dtype=np.float32)
    r_pos = np.full((P, P, max_s), trash, dtype=np.int32)  # pad -> trash
    for p in range(P):
        for q in range(P):
            ks = len(send_lists[p][q])
            if ks:
                s_idx[p, q, :ks] = send_lists[p][q]
                s_msk[p, q, :ks] = 1.0
            kr = len(recv_lists[p][q])  # aligned with send_lists[q][p]
            if kr:
                r_pos[p, q, :kr] = recv_lists[p][q]

    # trash-row hygiene (the invariant the fast path relies on): no REAL
    # edge endpoint and no REAL recv slot may reference the trash row, so it
    # stays all-zero through every layer
    assert not (e_src[e_msk > 0] == trash).any(), "real edge src hit trash row"
    assert not (e_dst[e_msk > 0] == trash).any(), "real edge dst hit trash row"
    assert not (i_src[i_msk > 0] == trash).any()
    assert not (b_src[b_msk > 0] == trash).any()
    # recv_pos[p, q] aligns with send_lists[q][p], i.e. with s_msk[q, p]
    assert not (r_pos[np.swapaxes(s_msk, 0, 1) > 0] == trash).any(), \
        "real recv slot hit trash row"

    return PartitionedGraph(
        num_parts=P, n_own=n_own, n_int=n_int, n_halo=n_halo,
        max_nodes=max_nodes, own_cap=own_cap,
        features=feats, labels=labels, edge_src=e_src, edge_dst=e_dst,
        edge_mask=e_msk, int_src=i_src, int_dst=i_dst, int_mask=i_msk,
        bnd_src=b_src, bnd_dst=b_dst, bnd_mask=b_msk, deg=deg,
        send_idx=s_idx, send_mask=s_msk, recv_pos=r_pos,
        global_ids=gids, train_mask=tr_m, val_mask=va_m, test_mask=te_m,
    )


# ---------------------------------------------------------------------------
# halo exchange collectives
# ---------------------------------------------------------------------------

def _exchange(sent, axis_name: str, ring_chunks: int = 0):
    """Move ``sent[q]`` (this partition's rows for q) to partition q; returns
    ``recv`` with ``recv[q]`` = the rows q sent here.

    ``ring_chunks == 0``: one ``all_to_all``.  ``ring_chunks >= 1``: a P-1
    step ``ppermute`` ring where each step's payload is split into that many
    chunks, each an independent collective — on a real mesh chunk c+1's send
    overlaps chunk c's landing/compute (DESIGN.md §5).  Both deliver
    bit-identical buffers; only the schedule differs.
    """
    if ring_chunks <= 0:
        return jax.lax.all_to_all(sent, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
    P, S = sent.shape[0], sent.shape[1]
    p = jax.lax.axis_index(axis_name)
    nc = max(1, min(ring_chunks, S))
    bounds = [round(c * S / nc) for c in range(nc + 1)]
    # self block never carries payload (a node is never its own halo), but
    # keeping it makes recv layout identical to the all_to_all's
    recv = jnp.zeros_like(sent)
    recv = jax.lax.dynamic_update_index_in_dim(
        recv, jax.lax.dynamic_index_in_dim(sent, p, axis=0, keepdims=False),
        p, axis=0)
    for s in range(1, P):
        perm = [(i, (i + s) % P) for i in range(P)]
        blk = jax.lax.dynamic_index_in_dim(sent, (p + s) % P, axis=0,
                                           keepdims=False)
        got = [jax.lax.ppermute(blk[lo:hi], axis_name, perm)
               for lo, hi in zip(bounds[:-1], bounds[1:])]
        recv = jax.lax.dynamic_update_index_in_dim(
            recv, got[0] if len(got) == 1 else jnp.concatenate(got),
            (p - s) % P, axis=0)
    return recv


def _halo_exchange(h, send_idx, send_mask, recv_pos, axis_name: str,
                   ring_chunks: int = 0):
    """One exchange round: ship owned boundary rows, land them in halo
    slots.  h: (maxN, D); send_idx/mask/recv_pos: (P, maxS[, 1])."""
    out = h[send_idx] * send_mask[..., None]          # (P, maxS, D)
    recv = _exchange(out, axis_name, ring_chunks)
    # recv[q] = rows partition q sent me; scatter into my halo slots
    flat_pos = recv_pos.reshape(-1)
    flat_val = recv.reshape(-1, h.shape[-1])
    return h.at[flat_pos].set(flat_val.astype(h.dtype))


# ---------------------------------------------------------------------------
# wire codecs (compressed communication)
# ---------------------------------------------------------------------------

HALO_COMPRESS_MODES = ("none", "fp16", "int8")


def quantize_rows(x, mode: str):
    """Quantize ``x`` (..., D) row-wise -> ``(payload, scale)``.

    ``fp16``  plain downcast, no side channel (scale is None).
    ``int8``  symmetric per-row scale ``max(|row|) / 127``: payload is int8
              in [-127, 127], scale travels as one float32 per row.  An
              all-zero row quantizes to (0, scale 0) and dequantizes to
              exact zeros — the property that keeps pad slots (and through
              them the trash row) clean across a compressed exchange.

    All arithmetic runs in ``x``'s dtype, so under ``jax_enable_x64`` the
    sequential fp64 oracle models the engine's quantization EXACTLY.
    """
    if mode == "fp16":
        return x.astype(jnp.float16), None
    if mode == "int8":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = amax / x.dtype.type(127.0)
        safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
        q = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.float32)
    raise ValueError(f"unknown halo compression mode {mode!r} "
                     f"(expected one of {HALO_COMPRESS_MODES[1:]})")


def dequantize_rows(payload, scale, mode: str, dtype):
    """Inverse of :func:`quantize_rows` into ``dtype``.  Deterministic and
    elementwise, so sender-side (error feedback) and receiver-side
    dequantization of the same payload are bitwise identical."""
    if mode == "fp16":
        return payload.astype(dtype)
    if mode == "int8":
        return payload.astype(dtype) * scale.astype(dtype)
    raise ValueError(f"unknown halo compression mode {mode!r}")


def wire_row_bytes(d: int, mode: str, itemsize: int = 4) -> int:
    """Bytes ONE exchanged embedding row of width ``d`` occupies on the
    wire: the uncompressed row is ``d * itemsize``, fp16 halves it, int8
    ships one byte per element plus the row's float32 scale."""
    if mode == "none":
        return d * itemsize
    if mode == "fp16":
        return d * 2
    if mode == "int8":
        return d + 4
    raise ValueError(f"unknown halo compression mode {mode!r}")


def _ef_quantized_exchange(sent, mask3, residual, mode: str, axis_name: str,
                           ring_chunks: int, out_dtype):
    """Error-compensated quantized exchange of an already-gathered send
    buffer.  Returns ``(recv, new_residual)``:

      sent_ef = (sent + residual) * mask        # carry last round's error
      payload = quantize(sent_ef)               # what goes on the wire
      new_residual = (sent_ef - dequant(payload)) * mask
      recv = dequant(exchange(payload))         # landed at the receiver

    Quantization happens BEFORE the collective, so the all_to_all and the
    chunked ppermute ring move bit-identical payload buffers — compression
    and schedule compose freely.  The int8 per-row scales travel as a
    second (tiny) collective over the same schedule.
    """
    sent_ef = (sent + residual.astype(sent.dtype)) * mask3
    payload, scale = quantize_rows(sent_ef, mode)
    deq = dequantize_rows(payload, scale, mode, sent.dtype)
    new_residual = ((sent_ef - deq) * mask3).astype(residual.dtype)
    recv_p = _exchange(payload, axis_name, ring_chunks)
    recv_s = (None if scale is None
              else _exchange(scale, axis_name, ring_chunks))
    return dequantize_rows(recv_p, recv_s, mode, out_dtype), new_residual


def halo_refresh_plan(age: int, refresh_every: int, cv: bool,
                      max_send: int) -> tuple[int, int]:
    """Static send-slot range ``[lo, hi)`` the next cached forward refreshes.

    ``age`` counts distributed eval forwards since the cache was created
    (host-side, so the choice is a Python constant baked into the trace —
    the cached-epoch executable contains NO collective at all).

      age % K == 0        full refresh: (0, max_send) — bit-for-bit the
                          synchronous exchange, which is what makes the
                          staleness-0 (K == 1) path bitwise-identical to
                          :func:`make_distributed_forward`.
      otherwise, cv off   (0, 0): aggregate purely against the cache.
      otherwise, cv on    the VR-GCN-style partial refresh: the slot space
                          is cut into K-1 contiguous chunks and cached
                          epoch c refreshes chunk c, so every halo row is
                          re-exchanged within K epochs (staleness bound)
                          and each cached epoch pays ~1/(K-1) of the full
                          payload — the "cached h plus the delta of the
                          refreshed rows" estimator.
    """
    K = max(1, int(refresh_every))
    if K == 1 or age % K == 0:
        return 0, max_send
    if not cv:
        return 0, 0
    c = (age % K) - 1
    nc = K - 1
    return (c * max_send) // nc, ((c + 1) * max_send) // nc


# ---------------------------------------------------------------------------
# aggregation backends
# ---------------------------------------------------------------------------

def make_ref_mean_agg(max_nodes: int):
    """jnp segment-op mean aggregation over a shard's local edge list — the
    reference backend (same math as kernels/ref.py, specialised to the
    padded shard layout)."""

    def mean_agg(h, shard):
        msg = h[shard["edge_src"]] * shard["edge_mask"][:, None].astype(h.dtype)
        s = jax.ops.segment_sum(msg, shard["edge_dst"], num_segments=max_nodes)
        deg = jax.ops.segment_sum(shard["edge_mask"].astype(h.dtype),
                                  shard["edge_dst"], num_segments=max_nodes)
        return s / jnp.maximum(deg, 1.0)[:, None]

    return mean_agg


def make_ref_split_agg(own_cap: int):
    """jnp segment-op interior/boundary aggregation pair for the overlapped
    forward.  Returns ``(agg_interior, agg_boundary)``; each maps
    ``(h, shard) -> (own_cap, D)`` and is only meaningful on its own row
    range (rows < n_int for interior, [n_int, n_own) for boundary) — the
    caller selects per row with a bitwise-safe ``jnp.where``.

    No mask multiply and no runtime degree pass: padding edges read the
    guaranteed-zero trash row and land in the sacrificial segment row
    ``own_cap`` (sliced off), and the static in-degree ships precomputed in
    ``shard["deg"]`` — two of the wins the split layout buys even before
    any exchange is overlapped.
    """

    def agg_interior(h, shard):
        s = jax.ops.segment_sum(h[shard["int_src"]], shard["int_dst"],
                                num_segments=own_cap + 1)[:own_cap]
        return s / shard["deg"][:, None].astype(h.dtype)

    def agg_boundary(h, shard):
        s = jax.ops.segment_sum(h[shard["bnd_src"]], shard["bnd_dst"],
                                num_segments=own_cap + 1)[:own_cap]
        return s / shard["deg"][:, None].astype(h.dtype)

    return agg_interior, agg_boundary


def make_pallas_mean_agg(max_nodes: int):
    """Pallas-kernel mean aggregation: the GNN hot-spot on the MXU.

    Reads the paired forward/transpose blocked-CSR structure
    (``shard["blk"]``, built by ``engine.stacking.build_stacked_vjp_blocks``)
    and routes through the ONE differentiable op
    ``kernels.ops.segment_mean_op`` — ``jax.grad`` through this forward
    stages the transpose aggregation kernel (full-graph training,
    DESIGN.md §6) instead of falling back to jnp scatter ops.
    """
    from ..kernels.ops import segment_mean_op

    def mean_agg(h, shard):
        return segment_mean_op(h, shard["blk"],
                               num_rows=max_nodes).astype(h.dtype)

    return mean_agg


def make_pallas_split_agg(own_cap: int):
    """Pallas interior/boundary aggregation pair for the overlapped forward.

    Each half's blocked structure covers only its own row range — interior
    rows [0, n_int), boundary rows REBASED to [0, n_own - n_int) — and is
    placed into the (own_cap, D) output by the unified op's ``row_base``
    (the row-range variant of ``segment_mean_op``), so each pass pays for
    ceil(range / BN) node blocks instead of the whole local space and stays
    differentiable: the boundary half's backward routes gradient into owned
    AND halo source rows, from where the halo exchange's own VJP carries it
    back to the owning partition.
    """
    from ..kernels.ops import segment_mean_op

    def agg_interior(h, shard):
        return segment_mean_op(h, shard["blk_int"], num_rows=own_cap,
                               row_base=0).astype(h.dtype)

    def agg_boundary(h, shard):
        return segment_mean_op(h, shard["blk_bnd"], num_rows=own_cap,
                               row_base=shard["n_int"]).astype(h.dtype)

    return agg_interior, agg_boundary


# ---------------------------------------------------------------------------
# SPMD forwards
# ---------------------------------------------------------------------------

def make_distributed_forward(model: GraphSAGE, pg_meta: dict,
                             axis_name: str = "data", agg=None,
                             compress: str = "none", ring_chunks: int = 0):
    """Build the per-shard n-layer SYNCHRONOUS forward with halo exchange.

    Returns ``fwd(params, shard) -> logits`` where ``shard`` is the
    per-partition slice of the stacked PartitionedGraph arrays; call it
    inside ``shard_map`` over a partition mesh, or under
    ``vmap(..., axis_name=...)`` for the single-device stacked fallback
    (jax batches ``all_to_all`` across the vmapped axis with the same
    transpose semantics — see DESIGN.md §3).

    ``agg(h, shard) -> (max_nodes, D)`` selects the aggregation backend;
    default is the jnp segment-op reference, the SPMD engine passes
    :func:`make_pallas_mean_agg` to put the Pallas kernel on the hot path.

    ``compress`` (DESIGN.md §11): ``"none"`` returns EXACTLY the forward
    above — the same closure, no extra arguments, so compression off is
    bit-for-bit today's trace by construction.  ``"fp16"``/``"int8"``
    return the error-compensated quantized variant
    ``fwd(params, shard, residual) -> (logits, new_residual)`` where
    ``residual["r{i}"]`` is layer i's carried send-side quantization error
    (same (P, maxS, D_i) geometry as the send lists); ``ring_chunks``
    selects the exchange schedule for the quantized payloads (the
    uncompressed forward keeps its all_to_all spelling untouched).

    Every layer's exchange fully serialises before any aggregation — the
    baseline :func:`make_overlap_forward` is benchmarked against.
    """
    max_nodes = pg_meta["max_nodes"]
    mean_agg = agg if agg is not None else make_ref_mean_agg(max_nodes)

    if compress == "none":
        def fwd(params: SAGEParams, shard: dict) -> jnp.ndarray:
            h = shard["features"]
            last = len(params.layers) - 1
            for i, lp in enumerate(params.layers):
                h = _halo_exchange(h, shard["send_idx"], shard["send_mask"],
                                   shard["recv_pos"], axis_name)
                a = mean_agg(h, shard)
                h = h @ lp.w_self + a @ lp.w_neigh + lp.b
                if i < last:
                    h = jax.nn.relu(h)
            return h

        return fwd

    def fwd_c(params: SAGEParams, shard: dict, residual: dict):
        h = shard["features"]
        mask3 = shard["send_mask"][..., None]
        last = len(params.layers) - 1
        new_res = {}
        for i, lp in enumerate(params.layers):
            sent = h[shard["send_idx"]] * mask3
            recv, new_res[f"r{i}"] = _ef_quantized_exchange(
                sent, mask3, residual[f"r{i}"], compress, axis_name,
                ring_chunks, h.dtype)
            h = h.at[shard["recv_pos"].reshape(-1)].set(
                recv.reshape(-1, h.shape[-1]).astype(h.dtype))
            a = mean_agg(h, shard)
            h = h @ lp.w_self + a @ lp.w_neigh + lp.b
            if i < last:
                h = jax.nn.relu(h)
        return h, new_res

    return fwd_c


def make_cached_forward(model: GraphSAGE, pg_meta: dict,
                        axis_name: str = "data", agg=None,
                        refresh_lo: int = 0, refresh_hi: int | None = None,
                        ring_chunks: int = 0, compress: str = "none"):
    """Build the per-shard n-layer forward against a HISTORICAL halo cache.

    Returns ``fwd(params, shard, cache) -> (logits, new_cache)`` where
    ``cache`` holds each layer's last-received exchange buffers in recv
    layout: ``{"h0": (P, maxS, D), "h1": (P, maxS, H), ...}`` per partition
    (``cache["hl"][q]`` = the rows partition q last sent here for layer l).
    Pad slots are zero at init and the refresh writes sender-masked zeros
    into them, so landing the cache never dirties the trash row.

    ``[refresh_lo, refresh_hi)`` is the STATIC send-slot range this call
    re-exchanges (from :func:`halo_refresh_plan`); everything outside it
    aggregates against the cached rows:

      full range    skip the cache landing entirely — gather/exchange/
                    scatter is then exactly :func:`_halo_exchange`, so a
                    refresh step is bit-for-bit the synchronous forward
                    while ALSO snapshotting the recv buffers into the cache.
      empty range   land cached rows only; the trace contains no collective.
      partial       land the cache, then exchange just the slot slice and
                    overwrite those rows fresh (the control-variate delta).

    Cached halo rows enter aggregation as constants (no VJP through past
    epochs), which is the VR-GCN historical-activation semantics.

    ``compress != "none"`` quantizes the REFRESH payload (the ``[lo, hi)``
    slice) with error feedback on the matching residual slot slice; the
    cache stores the DEQUANTIZED rows, so cached aggregation math is
    untouched.  The signature gains the residual:
    ``fwd(params, shard, cache, residual) -> (logits, new_cache,
    new_residual)``.  ``compress == "none"`` keeps today's closure and
    signature bit-for-bit.
    """
    max_nodes = pg_meta["max_nodes"]
    mean_agg = agg if agg is not None else make_ref_mean_agg(max_nodes)
    lo = int(refresh_lo)

    def land_and_refresh(h, shard, cached, res=None):
        hi = shard["send_idx"].shape[-1] if refresh_hi is None else refresh_hi
        full = lo == 0 and hi == shard["send_idx"].shape[-1]
        if hi > lo:
            # gather (and, compressed, quantize) BEFORE any cache landing:
            # send_idx only ever points at owned rows, and keeping the order
            # is what preserves today's trace for compress == "none"
            mask3 = shard["send_mask"][:, lo:hi][..., None]
            sent = h[shard["send_idx"][:, lo:hi]] * mask3
        if not full:
            h = h.at[shard["recv_pos"].reshape(-1)].set(
                cached.reshape(-1, h.shape[-1]).astype(h.dtype))
        if hi > lo:
            if res is None:
                recv = _exchange(sent, axis_name, ring_chunks)
            else:
                recv, new_r = _ef_quantized_exchange(
                    sent, mask3, res[:, lo:hi], compress, axis_name,
                    ring_chunks, h.dtype)
                res = res.at[:, lo:hi].set(new_r)
            h = h.at[shard["recv_pos"][:, lo:hi].reshape(-1)].set(
                recv.reshape(-1, h.shape[-1]).astype(h.dtype))
            cached = cached.at[:, lo:hi].set(recv.astype(cached.dtype))
        return h, cached, res

    def fwd(params: SAGEParams, shard: dict, cache: dict):
        h = shard["features"]
        last = len(params.layers) - 1
        new_cache = {}
        for i, lp in enumerate(params.layers):
            h, new_cache[f"h{i}"], _ = land_and_refresh(h, shard,
                                                        cache[f"h{i}"])
            a = mean_agg(h, shard)
            h = h @ lp.w_self + a @ lp.w_neigh + lp.b
            if i < last:
                h = jax.nn.relu(h)
        return h, new_cache

    def fwd_c(params: SAGEParams, shard: dict, cache: dict, residual: dict):
        h = shard["features"]
        last = len(params.layers) - 1
        new_cache, new_res = {}, {}
        for i, lp in enumerate(params.layers):
            h, new_cache[f"h{i}"], new_res[f"r{i}"] = land_and_refresh(
                h, shard, cache[f"h{i}"], residual[f"r{i}"])
            a = mean_agg(h, shard)
            h = h @ lp.w_self + a @ lp.w_neigh + lp.b
            if i < last:
                h = jax.nn.relu(h)
        return h, new_cache, new_res

    return fwd if compress == "none" else fwd_c


def make_overlap_forward(model: GraphSAGE, pg_meta: dict,
                         axis_name: str = "data", agg_interior=None,
                         agg_boundary=None, ring_chunks: int = 0):
    """Build the per-shard n-layer OVERLAPPED forward (DESIGN.md §5).

    Per layer the program is issued in an order XLA's async collective
    scheduler can overlap on a real mesh:

      1. gather the send rows and START the exchange (all_to_all, or a
         ``ring_chunks``-chunked ppermute ring),
      2. interior aggregation + the self-term matmul — neither reads a halo
         row, so both run while the exchange is in flight,
      3. land the received rows in the halo slots,
      4. boundary aggregation (the only halo-dependent compute), then the
         bitwise-safe per-row select between the two halves.

    Beyond the overlap, the split layout does strictly less work than the
    synchronous forward: dense transforms and aggregation outputs cover the
    ``own_cap`` owned rows instead of the full padded local space (halo
    rows are recomputed by their OWNING partition and exchanged, never
    transformed locally), degrees are static host constants, and padding
    edges read the guaranteed-zero trash row so no edge mask multiply runs.
    On owned rows the result is bit-for-bit identical to
    :func:`make_distributed_forward` (tests/test_engine_parity.py); halo
    and pad logit rows are NOT meaningful in either forward and differ
    between the two.

    Overlap is a no-op when P == 1 or every halo is empty: the exchange
    carries nothing, the boundary ranges are empty, and the per-row select
    resolves entirely to the interior half.
    """
    max_nodes = pg_meta["max_nodes"]
    own_cap = pg_meta["own_cap"]
    if agg_interior is None or agg_boundary is None:
        agg_interior, agg_boundary = make_ref_split_agg(own_cap)
    rows = np.arange(own_cap)[:, None]

    def split_layer(h, shard, layer, activate: bool):
        # (1) start the exchange first so everything until (3) overlaps it
        sent = h[shard["send_idx"]] * shard["send_mask"][..., None]
        recv = _exchange(sent, axis_name, ring_chunks)
        # (2) halo-independent compute
        agg_i = agg_interior(h, shard)
        self_t = h[:own_cap] @ layer.w_self
        # (3) land the halo rows
        flat_pos = shard["recv_pos"].reshape(-1)
        h = h.at[flat_pos].set(recv.reshape(-1, h.shape[-1]).astype(h.dtype))
        # (4) boundary aggregation + bitwise-safe per-row select
        agg_b = agg_boundary(h, shard)
        agg = jnp.where(rows < shard["n_int"], agg_i, agg_b)
        out = self_t + agg @ layer.w_neigh + layer.b
        if activate:
            out = jax.nn.relu(out)
        return out

    def embed(out):
        # re-embed owned rows into the padded local space: halo slots are
        # refreshed by the NEXT layer's exchange before anything reads them,
        # and the trash row (maxN - 1 > own_cap - 1) stays zero
        return jnp.zeros((max_nodes, out.shape[-1]), out.dtype).at[:own_cap].set(out)

    def fwd(params: SAGEParams, shard: dict) -> jnp.ndarray:
        h = shard["features"]
        last = len(params.layers) - 1
        for i, lp in enumerate(params.layers):
            h = embed(split_layer(h, shard, lp, activate=i < last))
        return h

    return fwd


def make_export_forward(model: GraphSAGE, pg_meta: dict,
                        axis_name: str = "data", agg=None):
    """Synchronous forward that ALSO materializes the serving handoff.

    Returns ``fwd(params, shard) -> {"layers", "logits", "cache"}`` where
    ``layers[i]`` is layer i's POST-exchange input embedding over the full
    padded local space (owned rows + freshly landed halo rows), ``logits``
    is bit-for-bit :func:`make_distributed_forward`'s output (same gather/
    exchange/scatter spelling, same contraction order), and ``cache`` is
    the recv-layout halo buffer snapshot ``{"h{i}": (P, maxS, D_i)}`` — the
    exact arrays a full-refresh :func:`make_cached_forward` step would have
    written, so the serving engine lands its halo rows from the same PR-6
    cache geometry (``recv_pos`` slots) the training eval path uses.
    """
    max_nodes = pg_meta["max_nodes"]
    mean_agg = agg if agg is not None else make_ref_mean_agg(max_nodes)

    def fwd(params: SAGEParams, shard: dict) -> dict:
        h = shard["features"]
        last = len(params.layers) - 1
        layers, cache = [], {}
        for i, lp in enumerate(params.layers):
            sent = h[shard["send_idx"]] * shard["send_mask"][..., None]
            recv = _exchange(sent, axis_name)
            h = h.at[shard["recv_pos"].reshape(-1)].set(
                recv.reshape(-1, h.shape[-1]).astype(h.dtype))
            cache[f"h{i}"] = recv
            layers.append(h)
            a = mean_agg(h, shard)
            h = h @ lp.w_self + a @ lp.w_neigh + lp.b
            if i < last:
                h = jax.nn.relu(h)
        return {"layers": tuple(layers), "logits": h, "cache": cache}

    return fwd


class RecomputePlanner:
    """Dirty-set propagation over the partitioned CSR shards (serving).

    Built once from a :class:`PartitionedGraph`; answers "after these rows'
    layer-(l-1) embeddings changed, which OWNED rows must recompute layer
    l?" per partition, including the replica mirroring between layers that
    keeps halo copies consistent with their owners.

    The rule per layer (DESIGN.md §9): a row recomputes iff its own input
    changed (self term) or a local in-neighbour's input changed (edges are
    stored dst-major per partition; the planner holds the src-major CSC
    mirror of the same local edge lists).  Rows whose IN-EDGES changed are
    seeded at layer 1 and carried forward by the self term.  Edge removals
    are only RECORDED at first: stale out-edges can only over-propagate
    (recompute a clean row to the same value), never under-propagate, so
    correctness needs no eager CSC deletion.  Once a partition accumulates
    ``compact_after`` recorded removals the planner compacts — rebuilds
    that shard's CSC from (static minus removed) plus the dynamically
    added edges — so long-running serving with heavy churn stops paying
    for dirty cones through edges that no longer exist.  :meth:`compact`
    forces the rebuild on demand.

    The replica map comes from the send/recv lists: owner p's local row
    ``send_idx[p, q, s]`` has a halo copy at q's ``recv_pos[q, p, s]``.
    Serving-time halo growth registers new replicas / out-edges through
    :meth:`add_replica` / :meth:`add_out_edge`.
    """

    def __init__(self, pg: PartitionedGraph, *, compact_after: int = 64):
        P = pg.num_parts
        self.num_parts = P
        self.compact_after = int(compact_after)
        self.compactions = 0
        self.n_own = np.asarray(pg.n_own).copy()
        self._csc = []
        for p in range(P):
            real = np.asarray(pg.edge_mask[p]) > 0
            src = np.asarray(pg.edge_src[p])[real].astype(np.int64)
            dst = np.asarray(pg.edge_dst[p])[real].astype(np.int64)
            order = np.argsort(src, kind="stable")
            n_rows = int(pg.max_nodes)
            counts = np.bincount(src, minlength=n_rows)
            ptr = np.zeros(n_rows + 1, np.int64)
            np.cumsum(counts, out=ptr[1:])
            self._csc.append((ptr, dst[order]))
        # dynamically added out-edges (src_local -> [dst_local]) per part
        self._extra_out: list[dict[int, list[int]]] = [{} for _ in range(P)]
        # removals recorded against the static CSC, pending compaction
        self._removed: list[set[tuple[int, int]]] = [set() for _ in range(P)]
        # replica lists: owner p's local row -> [(peer q, q's halo row)]
        self._rep: list[dict[int, list[tuple[int, int]]]] = [{} for _ in range(P)]
        send_idx = np.asarray(pg.send_idx)
        send_mask = np.asarray(pg.send_mask)
        recv_pos = np.asarray(pg.recv_pos)
        for p in range(P):
            for q in range(P):
                m = send_mask[p, q] > 0
                for s_loc, r_loc in zip(send_idx[p, q][m], recv_pos[q, p][m]):
                    self._rep[p].setdefault(int(s_loc), []).append((q, int(r_loc)))

    # ------------------------------------------------------------- mutation
    def add_out_edge(self, p: int, src_local: int, dst_local: int) -> None:
        self._extra_out[p].setdefault(int(src_local), []).append(int(dst_local))

    def add_replica(self, owner: int, row: int, peer: int, peer_row: int) -> None:
        self._rep[owner].setdefault(int(row), []).append((peer, int(peer_row)))

    def remove_out_edge(self, p: int, src_local: int, dst_local: int) -> None:
        """Record the removal of local edge src -> dst on partition p.

        A dynamically added edge is deleted in place; a static-CSC edge is
        only logged (stale until the next compaction, which is safe — it
        over-propagates).  Hitting ``compact_after`` pending removals
        triggers an automatic compaction of that partition's shard.
        """
        src_local, dst_local = int(src_local), int(dst_local)
        extra = self._extra_out[p].get(src_local)
        if extra is not None and dst_local in extra:
            extra.remove(dst_local)
            if not extra:
                del self._extra_out[p][src_local]
            return
        self._removed[p].add((src_local, dst_local))
        if len(self._removed[p]) >= self.compact_after:
            self._compact(p)

    def compact(self, p: int | None = None) -> None:
        """Force-rebuild the CSC shard(s) so every recorded removal and
        dynamic addition is folded into the static adjacency."""
        for q in ([p] if p is not None else range(self.num_parts)):
            if self._removed[q] or self._extra_out[q]:
                self._compact(int(q))

    def _compact(self, p: int) -> None:
        ptr, dst = self._csc[p]
        n_static = len(ptr) - 1
        src = np.repeat(np.arange(n_static, dtype=np.int64), np.diff(ptr))
        removed = self._removed[p]
        if removed:
            keep = np.fromiter(((int(s), int(d)) not in removed
                                for s, d in zip(src, dst)), bool, src.size)
            src, dst = src[keep], dst[keep]
        ex_src: list[int] = []
        ex_dst: list[int] = []
        for s, lst in self._extra_out[p].items():
            ex_src.extend([int(s)] * len(lst))
            ex_dst.extend(int(d) for d in lst)
        if ex_src:
            src = np.concatenate([src, np.asarray(ex_src, np.int64)])
            dst = np.concatenate([dst, np.asarray(ex_dst, np.int64)])
        n_rows = max(n_static, int(src.max()) + 1 if src.size else 0)
        counts = np.bincount(src, minlength=n_rows)
        new_ptr = np.zeros(n_rows + 1, np.int64)
        np.cumsum(counts, out=new_ptr[1:])
        order = np.argsort(src, kind="stable")
        self._csc[p] = (new_ptr, dst[order])
        self._extra_out[p] = {}
        self._removed[p].clear()
        self.compactions += 1

    # -------------------------------------------------------------- queries
    def replicas(self, p: int, rows: np.ndarray):
        """(peer, peer_row, owner_row) triples for every replica of ``rows``."""
        rep = self._rep[p]
        for r in np.asarray(rows):
            for q, qrow in rep.get(int(r), ()):
                yield q, qrow, int(r)

    def out_rows(self, p: int, rows: np.ndarray) -> np.ndarray:
        """Local out-neighbours (always owned rows: edges target dst-owned)."""
        ptr, dst = self._csc[p]
        extra = self._extra_out[p]
        segs = []
        n_static = len(ptr) - 1
        for r in np.asarray(rows):
            r = int(r)
            if r < n_static:
                segs.append(dst[ptr[r]:ptr[r + 1]])
            if r in extra:
                segs.append(np.asarray(extra[r], np.int64))
        if not segs:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(segs))

    def propagate(self, dirty_h0: dict[int, np.ndarray],
                  edge_seeds: dict[int, np.ndarray],
                  num_layers: int) -> list[dict[int, np.ndarray]]:
        """``plans[l-1][p]`` = sorted owned rows partition p recomputes at
        layer l (1-based), given local rows (owned or halo) whose input
        features changed and owned rows whose in-edge lists changed."""
        P = self.num_parts
        empty = np.empty(0, np.int64)
        cur = {p: np.unique(np.asarray(dirty_h0.get(p, empty), np.int64))
               for p in range(P)}
        plans: list[dict[int, np.ndarray]] = []
        for l in range(1, num_layers + 1):
            rec = {}
            for p in range(P):
                parts = [self.out_rows(p, cur[p]),
                         cur[p][cur[p] < self.n_own[p]]]
                if l == 1:
                    parts.append(np.asarray(
                        sorted(edge_seeds.get(p, ())), np.int64))
                rec[p] = np.unique(np.concatenate(parts)) if parts else empty
            plans.append(rec)
            if l < num_layers:
                nxt = {p: [rec[p]] for p in range(P)}
                for p in range(P):
                    for q, qrow, _ in self.replicas(p, rec[p]):
                        nxt[q].append(np.asarray([qrow], np.int64))
                cur = {p: np.unique(np.concatenate(nxt[p])) for p in range(P)}
        return plans
