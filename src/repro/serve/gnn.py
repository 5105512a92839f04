"""Partitioned online GNN inference service (DESIGN.md §9).

DistDGL's serving shape — per-partition precomputed state, a hot-row
cache, cross-partition request batching — rendered over this repo's
partition layout:

  · **Embedding store.**  One host array per (layer, partition):
    ``h[l][p]`` holds layer l's POST-exchange input embedding for every
    local row (owned + halo), ``h[L][p]`` the final logits for owned
    rows.  Initialised from :meth:`SPMDEngine.export_serving_state`:
    owned rows from the exported layer embeddings, halo rows landed from
    the exported recv-layout cache buffers through ``pg.recv_pos`` — the
    same PR-6 cache geometry the training eval path refreshes through.

  · **Dirty-set incremental recompute.**  Feature and edge updates mark
    rows dirty; :class:`~repro.graph.distributed.RecomputePlanner`
    propagates the dirty set one hop per layer through the CSR shards
    (self term ∪ local out-neighbours, halo replicas mirrored between
    layers), and :meth:`flush` recomputes ONLY those rows — a gathered
    sub-edge-list aggregation through ``segment_mean_op`` (or the jnp
    segment-sum reference) plus a row-gathered dense transform.  On this
    backend a row-subset matmul is bitwise the corresponding rows of the
    full matmul for >= 2 rows (single-row falls onto a gemv kernel with
    different reduction order), so every batch is padded to at least two
    rows via the trash row; sub-edge segment sums keep each row's edges
    in the canonical ascending-global-id order the full aggregation
    uses.  Served logits after any update sequence therefore match a
    from-scratch forward bit-for-bit in fp64 (tests/test_serve_gnn.py).

  · **Query batching tick.**  Queries accumulate in :meth:`submit`;
    each :meth:`tick` flushes pending recomputes once, answers repeat
    queries from an LRU hot-row cache (flush invalidates exactly the
    recomputed final-layer rows, so hits are bitwise the store row; hit /
    miss counts land in ``stats``), then groups the remaining node ids by
    owning partition and serves each group with ONE fused device gather
    from that partition's logits store.

Staleness contract: reads between ``tick``/``flush`` calls serve the
last flushed state; a flush makes every preceding update visible
atomically (layer l+1 never reads a mix of old and new layer-l rows,
because replicas are pushed before the next layer recomputes).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.distributed import PartitionedGraph, RecomputePlanner
from ..graph.csr import CSRGraph

__all__ = ["GNNServingEngine", "apply_updates_to_graph"]


def _bucket(n: int, lo: int = 2) -> int:
    """Next power of two >= max(n, lo) — bounds distinct jit shapes."""
    m = max(lo, int(n))
    return 1 << (m - 1).bit_length()


@partial(jax.jit, static_argnames=("activate",))
def _dense_recompute(h_prev, w_self, w_neigh, b, rows, src, dst, deg,
                     activate: bool):
    """Recompute ``rows``' next-layer embedding from the level-(l-1) store.

    Mirrors ``make_ref_mean_agg`` + the layer matmul spelling exactly:
    segment-sum over the (rebased) sub-edge list, divide by the clamped
    degree, then ``h @ w_self + agg @ w_neigh + b``.  Pad rows gather the
    all-zero trash row; pad edges land in the sacrificial segment M.
    """
    m = rows.shape[0]
    s = jax.ops.segment_sum(h_prev[src], dst, num_segments=m + 1)[:m]
    agg = s / jnp.maximum(deg, 1.0)[:, None]
    out = h_prev[rows] @ w_self + agg @ w_neigh + b
    return jax.nn.relu(out) if activate else out


@partial(jax.jit, static_argnames=("activate",))
def _pallas_recompute(h_prev, w_self, w_neigh, b, rows, blocks,
                      activate: bool):
    """The same recompute with the aggregation through ``segment_mean_op``
    (the blocked Pallas kernel every training forward uses)."""
    from ..kernels.ops import segment_mean_op

    agg = segment_mean_op(h_prev, blocks,
                          num_rows=int(rows.shape[0])).astype(h_prev.dtype)
    out = h_prev[rows] @ w_self + agg @ w_neigh + b
    return jax.nn.relu(out) if activate else out


_gather = jax.jit(lambda table, rows: table[rows])


class GNNServingEngine:
    """Online inference over a trained partitioned GraphSAGE.

    ``export`` is :meth:`SPMDEngine.export_serving_state`'s dict; the
    engine serves from host-resident growable per-partition stores and
    runs all numeric work (recompute, gather) as jitted device calls, so
    incremental results are bitwise the from-scratch forward.
    """

    def __init__(self, model, params, pg: PartitionedGraph, export: dict, *,
                 use_pallas_agg: bool = False,
                 hot_cache_rows: int = 256, planner_compact_after: int = 64):
        if model.aggregation != "mean":
            raise ValueError(
                f"GNNServingEngine recomputes GraphSAGE's mean layer per "
                f"row; it cannot serve {type(model).__name__}")
        if len(params.layers) != model.num_layers:
            raise ValueError("params depth != model.num_layers")
        self.model = model
        self.params = params
        self.L = model.num_layers
        self.use_pallas_agg = bool(use_pallas_agg)
        P = pg.num_parts
        self.num_parts = P
        self.n_own = np.asarray(pg.n_own).astype(np.int64)
        self.trash_row = int(pg.trash_row)

        # ---- ownership + local<->global maps -----------------------------
        gids_all = np.asarray(pg.global_ids)
        self.num_nodes = int(gids_all.max()) + 1
        self.owner_part = np.full(self.num_nodes, -1, np.int32)
        self.owner_row = np.full(self.num_nodes, -1, np.int64)
        for p in range(P):
            own = gids_all[p][: self.n_own[p]]
            self.owner_part[own] = p
            self.owner_row[own] = np.arange(self.n_own[p])
        self.l2g = [gids_all[p].copy() for p in range(P)]
        self.g2l = [{int(g): i for i, g in enumerate(self.l2g[p]) if g >= 0}
                    for p in range(P)]

        # ---- per-owned-row in-neighbour lists (ascending global id, the
        # order build_partitioned_graph emits and scipy-canonical CSR uses)
        self.nbr_loc: list[list[np.ndarray]] = []
        self.nbr_gid: list[list[np.ndarray]] = []
        for p in range(P):
            real = np.asarray(pg.edge_mask[p]) > 0
            src = np.asarray(pg.edge_src[p])[real].astype(np.int64)
            dst = np.asarray(pg.edge_dst[p])[real].astype(np.int64)
            counts = np.bincount(dst, minlength=int(self.n_own[p]))
            bounds = np.zeros(int(self.n_own[p]) + 1, np.int64)
            np.cumsum(counts[: self.n_own[p]], out=bounds[1:])
            # dst-major emitted order: row v's edges are contiguous
            self.nbr_loc.append([src[bounds[v]:bounds[v + 1]].copy()
                                 for v in range(int(self.n_own[p]))])
            self.nbr_gid.append([self.l2g[p][s] for s in self.nbr_loc[p]])

        # ---- embedding store: land halo rows from the exported recv-layout
        # cache buffers through recv_pos (the PR-6 cache geometry)
        recv_pos = np.asarray(pg.recv_pos)
        self.h: list[list[np.ndarray]] = []
        for l in range(self.L):
            per_part = []
            for p in range(P):
                arr = np.array(export["layers"][l][p], copy=True)
                arr[self.n_own[p]:] = 0          # halo re-landed, pads zeroed
                buf = np.asarray(export["cache"][f"h{l}"][p])
                arr[recv_pos[p].reshape(-1)] = buf.reshape(-1, arr.shape[-1])
                per_part.append(arr)
            self.h.append(per_part)
        self.h.append([np.array(export["logits"][p][: self.n_own[p]],
                                copy=True) for p in range(P)])
        self.dtype = self.h[0][0].dtype

        self.planner = RecomputePlanner(pg,
                                        compact_after=planner_compact_after)
        self._dirty0: list[set[int]] = [set() for _ in range(P)]
        self._edge_seeds: list[set[int]] = [set() for _ in range(P)]
        self._pending: list[int] = []
        # hot-row query cache: gid -> last served logit row, LRU up to
        # hot_cache_rows entries.  Entries are invalidated whenever a flush
        # recomputes that row's final-layer store, so a hit is always
        # bitwise the store row the gather path would have returned.
        self.hot_cache_rows = int(hot_cache_rows)
        self._hot: dict[int, np.ndarray] = {}
        self.stats = {"ticks": 0, "flushes": 0, "rows_recomputed": 0,
                      "gather_calls": 0, "queries": 0, "halo_rows_grown": 0,
                      "updates_queued": 0, "replay_attempts": 0,
                      "replayed": 0, "degraded_queries": 0,
                      "failovers": 0, "recoveries": 0,
                      "cache_hits": 0, "cache_misses": 0,
                      "planner_compactions": 0}

        # ---- per-partition health state machine (DESIGN.md §10) ----------
        # healthy -> failed (fail_partition / an injected serve fault) ->
        # healthy (recover_partition).  While a partition is failed its
        # stored embeddings stay FROZEN-CONSISTENT: any update whose
        # propagation cone would touch it is queued in arrival order and
        # applied NOWHERE, so reads of the failed store remain exactly the
        # last flushed state; queries it owns are answered from that state
        # with a per-answer staleness tag.  Queue replay is retried with
        # bounded exponential backoff and drains FIFO on recovery.
        self.health: list[str] = ["healthy"] * P
        self._failed_since: list[int] = [0] * P
        self._tick_no = 0
        self._queue: list[tuple] = []
        self._queued_feat: set[int] = set()
        self._queued_edges: set[tuple[int, int]] = set()
        self.max_backoff = 8          # backoff cap, in ticks
        self._backoff = 1
        self._retry_next = 0
        self.fault_plan = None

    # ------------------------------------------------------------- updates
    def _local(self, p: int, gid: int) -> int:
        """Local row of ``gid`` on partition p, growing a halo row (seeded
        with the owner's current per-layer embeddings, registered as a
        replica so future flushes keep it in sync) if p has never seen it."""
        row = self.g2l[p].get(gid)
        if row is not None:
            return row
        q = int(self.owner_part[gid])
        qrow = int(self.owner_row[gid])
        row = self.h[0][p].shape[0]
        for l in range(self.L):
            self.h[l][p] = np.concatenate(
                [self.h[l][p], self.h[l][q][qrow][None]], axis=0)
        self.l2g[p] = np.append(self.l2g[p], gid)
        self.g2l[p][gid] = row
        self.planner.add_replica(q, qrow, p, row)
        if qrow in self._dirty0[q]:
            self._dirty0[p].add(row)
        self.stats["halo_rows_grown"] += 1
        return row

    def update_features(self, gid: int, vec: np.ndarray) -> None:
        """Overwrite one node's input features (owner + every halo copy).
        While any partition in the update's propagation cone is failed the
        update is queued whole (applied nowhere) and replays on recovery."""
        gid = int(gid)
        if self._should_queue_feat(gid):
            self._queue.append(("feat", gid,
                                np.array(vec, self.dtype, copy=True)))
            self._queued_feat.add(gid)
            self.stats["updates_queued"] += 1
            return
        p = int(self.owner_part[gid])
        row = int(self.owner_row[gid])
        vec = np.asarray(vec, self.dtype)
        self.h[0][p][row] = vec
        self._dirty0[p].add(row)
        for q, qrow, _ in self.planner.replicas(p, np.asarray([row])):
            self.h[0][q][qrow] = vec
            self._dirty0[q].add(qrow)

    def add_edge(self, u: int, v: int) -> bool:
        """Add directed edge u -> v (u becomes an in-neighbour of v).
        Returns False if it already exists.  Growing a previously unseen
        cross-partition source appends a halo row on v's partition."""
        u, v = int(u), int(v)
        if self._should_queue_edge(u, v, adding=True):
            self._queue.append(("add", u, v))
            self._queued_edges.add((u, v))
            self.stats["updates_queued"] += 1
            return True
        p = int(self.owner_part[v])
        vrow = int(self.owner_row[v])
        pos = int(np.searchsorted(self.nbr_gid[p][vrow], u))
        if (pos < len(self.nbr_gid[p][vrow])
                and self.nbr_gid[p][vrow][pos] == u):
            return False
        urow = self._local(p, u)
        self.nbr_gid[p][vrow] = np.insert(self.nbr_gid[p][vrow], pos, u)
        self.nbr_loc[p][vrow] = np.insert(self.nbr_loc[p][vrow], pos, urow)
        self.planner.add_out_edge(p, urow, vrow)
        self._edge_seeds[p].add(vrow)
        return True

    def remove_edge(self, u: int, v: int) -> bool:
        """Remove directed edge u -> v; returns False if absent.  The
        removal is recorded with the planner, which keeps the stale
        out-edge until its per-partition compaction threshold (stale
        over-propagation is always safe; compaction stops paying for it)."""
        u, v = int(u), int(v)
        if self._should_queue_edge(u, v, adding=False):
            self._queue.append(("remove", u, v))
            self._queued_edges.add((u, v))
            self.stats["updates_queued"] += 1
            return True
        p = int(self.owner_part[v])
        vrow = int(self.owner_row[v])
        pos = int(np.searchsorted(self.nbr_gid[p][vrow], u))
        if (pos >= len(self.nbr_gid[p][vrow])
                or self.nbr_gid[p][vrow][pos] != u):
            return False
        urow = int(self.nbr_loc[p][vrow][pos])
        self.nbr_gid[p][vrow] = np.delete(self.nbr_gid[p][vrow], pos)
        self.nbr_loc[p][vrow] = np.delete(self.nbr_loc[p][vrow], pos)
        self.planner.remove_out_edge(p, urow, vrow)
        self._edge_seeds[p].add(vrow)
        return True

    # --------------------------------------------------------------- flush
    def _recompute_rows(self, l: int, p: int, rows: np.ndarray) -> None:
        h_prev = self.h[l - 1][p]
        lp = self.params.layers[l - 1]
        activate = l < self.L
        m = int(rows.size)
        # full-partition refresh keeps its exact (stable) shape; partial
        # batches pad to a power-of-two bucket, never below two rows
        mp = m if (m == self.n_own[p] and m >= 2) else _bucket(m)
        rp = np.full(mp, self.trash_row, np.int64)
        rp[:m] = rows
        srcs = [self.nbr_loc[p][r] for r in rows]
        counts = np.fromiter((s.size for s in srcs), np.int64, m)
        src = (np.concatenate(srcs) if m else np.empty(0, np.int64))
        dst = np.repeat(np.arange(m), counts)
        if self.use_pallas_agg:
            from ..kernels.ops import build_vjp_blocks
            blocks = build_vjp_blocks(src, dst, num_rows=mp,
                                      num_src_rows=h_prev.shape[0])
            out = _pallas_recompute(
                jnp.asarray(h_prev), lp.w_self, lp.w_neigh, lp.b,
                jnp.asarray(rp), jax.tree.map(jnp.asarray, blocks),
                activate=activate)
        else:
            e = int(src.size)
            ep = _bucket(e, lo=1)
            src_p = np.full(ep, self.trash_row, np.int64)
            dst_p = np.full(ep, mp, np.int64)   # sacrificial segment
            src_p[:e] = src
            dst_p[:e] = dst
            deg = np.ones(mp, self.dtype)
            deg[:m] = counts
            out = _dense_recompute(
                jnp.asarray(h_prev), lp.w_self, lp.w_neigh, lp.b,
                jnp.asarray(rp), jnp.asarray(src_p), jnp.asarray(dst_p),
                jnp.asarray(deg), activate=activate)
        self.h[l][p][rows] = np.asarray(out)[:m]

    def flush(self) -> dict:
        """Apply every pending update to the embedding store: propagate the
        dirty set one hop per layer, recompute exactly those owned rows,
        and mirror refreshed rows to their halo replicas between layers."""
        if (not any(self._dirty0) and not any(self._edge_seeds)):
            self.stats["planner_compactions"] = self.planner.compactions
            return {"rows_recomputed": 0, "per_layer": [0] * self.L}
        P = self.num_parts
        plans = self.planner.propagate(
            {p: np.fromiter(self._dirty0[p], np.int64, len(self._dirty0[p]))
             for p in range(P)},
            {p: np.fromiter(self._edge_seeds[p], np.int64,
                            len(self._edge_seeds[p])) for p in range(P)},
            self.L)
        per_layer, total = [], 0
        for l, rec in enumerate(plans, start=1):
            cnt = 0
            for p in range(P):
                if rec[p].size:
                    self._recompute_rows(l, p, rec[p])
                    cnt += int(rec[p].size)
            if l < self.L:
                for p in range(P):
                    for q, qrow, r in self.planner.replicas(p, rec[p]):
                        self.h[l][q][qrow] = self.h[l][p][r]
            else:
                # final-layer rows changed: their hot-cache entries are stale
                if self._hot:
                    for p in range(P):
                        for r in rec[p]:
                            self._hot.pop(int(self.l2g[p][r]), None)
            per_layer.append(cnt)
            total += cnt
        self._dirty0 = [set() for _ in range(P)]
        self._edge_seeds = [set() for _ in range(P)]
        self.stats["flushes"] += 1
        self.stats["rows_recomputed"] += total
        self.stats["planner_compactions"] = self.planner.compactions
        return {"rows_recomputed": total, "per_layer": per_layer}

    def refresh_full(self) -> dict:
        """From-scratch rematerialization through the same flush machinery
        (every owned row dirty) — the baseline :meth:`flush` must beat."""
        if self._any_failed():
            raise RuntimeError(
                "refresh_full requires every partition healthy; failed: "
                f"{[p for p, h in enumerate(self.health) if h != 'healthy']}")
        for p in range(self.num_parts):
            self._dirty0[p].update(range(int(self.n_own[p])))
        return self.flush()

    # ------------------------------------- health machine / degraded mode
    def _any_failed(self) -> bool:
        return any(h != "healthy" for h in self.health)

    def set_fault_plan(self, plan) -> None:
        """Attach a :class:`~repro.robustness.FaultPlan`; its serve fail /
        recover events are applied at the start of each :meth:`tick`."""
        self.fault_plan = plan

    def fail_partition(self, p: int) -> None:
        """Mark partition ``p`` failed at the current tick boundary.

        Pending dirty work is flushed FIRST (the failure lands on a flush
        boundary), so the failed store freezes in a fully consistent
        state; from here on any update whose cone touches ``p`` queues."""
        p = int(p)
        if self.health[p] != "healthy":
            return
        self.flush()
        self.health[p] = "failed"
        self._failed_since[p] = self._tick_no
        self.stats["failovers"] += 1

    def recover_partition(self, p: int) -> None:
        """Mark partition ``p`` healthy again; the queued updates replay
        (FIFO, all-or-nothing) at the next :meth:`tick`'s drain."""
        p = int(p)
        if self.health[p] != "failed":
            return
        self.health[p] = "healthy"
        self._backoff = 1
        self._retry_next = self._tick_no
        self.stats["recoveries"] += 1

    def _probe_touches_failed(self, seeds_h0: dict, seeds_edge: dict) -> bool:
        """Would an update with these dirty seeds propagate into a failed
        partition?  Runs the planner's cone (the exact sets flush would
        recompute + the replica pushes between layers) over the probe."""
        failed = {p for p, h in enumerate(self.health) if h != "healthy"}
        if not failed:
            return False
        P = self.num_parts
        for p in failed:
            if seeds_h0.get(p) or seeds_edge.get(p):
                return True
        plans = self.planner.propagate(
            {p: np.fromiter(sorted(seeds_h0.get(p, ())), np.int64,
                            len(seeds_h0.get(p, ()))) for p in range(P)},
            {p: np.fromiter(sorted(seeds_edge.get(p, ())), np.int64,
                            len(seeds_edge.get(p, ()))) for p in range(P)},
            self.L)
        for l, rec in enumerate(plans, start=1):
            for p in range(P):
                if p in failed and rec[p].size:
                    return True
                if l < self.L and rec[p].size:
                    for q, _qrow, _r in self.planner.replicas(p, rec[p]):
                        if q in failed:
                            return True
        return False

    def _should_queue_feat(self, gid: int) -> bool:
        if not self._queue and not self._any_failed():
            return False
        if gid in self._queued_feat:
            return True            # FIFO order behind the queued write
        if not self._any_failed():
            return False
        p = int(self.owner_part[gid])
        row = int(self.owner_row[gid])
        if self.health[p] != "healthy":
            return True
        seeds = {p: {row}}
        for q, qrow, _ in self.planner.replicas(p, np.asarray([row])):
            if self.health[q] != "healthy":
                return True        # h0 mirror would write into q
            seeds.setdefault(q, set()).add(qrow)
        return self._probe_touches_failed(seeds, {})

    def _should_queue_edge(self, u: int, v: int, *, adding: bool) -> bool:
        if not self._queue and not self._any_failed():
            return False
        if (u, v) in self._queued_edges:
            return True            # FIFO order behind the queued edge op
        if not self._any_failed():
            return False
        p = int(self.owner_part[v])
        if self.health[p] != "healthy":
            return True
        if adding and self.health[int(self.owner_part[u])] != "healthy":
            return True            # halo grow would subscribe to a dead host
        return self._probe_touches_failed({}, {p: {int(self.owner_row[v])}})

    def _drain_queue(self) -> None:
        """Replay the queued updates FIFO once every partition is healthy;
        while one is still failed, retry with bounded exponential backoff
        (1, 2, 4, ... capped at ``max_backoff`` ticks)."""
        if not self._queue:
            self._backoff = 1
            self._retry_next = 0
            return
        if self._tick_no < self._retry_next:
            return
        self.stats["replay_attempts"] += 1
        if self._any_failed():
            self._backoff = min(self._backoff * 2, self.max_backoff)
            self._retry_next = self._tick_no + self._backoff
            return
        ops, self._queue = self._queue, []
        self._queued_feat.clear()
        self._queued_edges.clear()
        for op in ops:
            if op[0] == "feat":
                self.update_features(op[1], op[2])
            elif op[0] == "add":
                self.add_edge(op[1], op[2])
            else:
                self.remove_edge(op[1], op[2])
        self.stats["replayed"] += len(ops)
        self._backoff = 1
        self._retry_next = 0

    # ------------------------------------------------------------- queries
    def submit(self, gids) -> None:
        self._pending.extend(int(g) for g in np.atleast_1d(np.asarray(gids)))

    def tick(self) -> tuple[dict, dict]:
        """One serving tick: apply scheduled fault events, attempt a queue
        drain, flush pending updates, then answer every queued query with
        one fused gather per owning partition.  Queries owned by a failed
        partition are answered from its frozen (last-flushed) logits and
        tagged in ``flush_stats['staleness']`` with the number of ticks
        since that partition failed."""
        self._tick_no += 1
        if self.fault_plan is not None:
            for kind, p in self.fault_plan.serve_events(self._tick_no):
                if kind == "fail":
                    self.fail_partition(p)
                else:
                    self.recover_partition(p)
        self._drain_queue()
        flush_stats = self.flush()
        results: dict[int, np.ndarray] = {}
        staleness: dict[int, int] = {}
        by_part: dict[int, list[int]] = {}
        for gid in self._pending:
            p = int(self.owner_part[gid])
            hot = self._hot.get(gid) if self.health[p] == "healthy" else None
            if hot is not None:
                self._hot[gid] = self._hot.pop(gid)    # LRU touch
                results[gid] = hot
                self.stats["cache_hits"] += 1
                continue
            by_part.setdefault(p, []).append(gid)
        for p, gids in by_part.items():
            rows = self.owner_row[np.asarray(gids, np.int64)]
            mp = _bucket(len(rows), lo=1)
            rp = np.zeros(mp, np.int64)
            rp[: len(rows)] = rows
            out = np.asarray(_gather(jnp.asarray(self.h[self.L][p]),
                                     jnp.asarray(rp)))[: len(rows)]
            self.stats["gather_calls"] += 1
            self.stats["cache_misses"] += len(gids)
            degraded = self.health[p] != "healthy"
            age = self._tick_no - self._failed_since[p] if degraded else 0
            for g, logit_row in zip(gids, out):
                results[g] = logit_row
                if degraded:
                    staleness[g] = age
                elif self.hot_cache_rows > 0:
                    self._hot.pop(g, None)
                    self._hot[g] = logit_row
            if degraded:
                self.stats["degraded_queries"] += len(gids)
            while len(self._hot) > self.hot_cache_rows:
                self._hot.pop(next(iter(self._hot)))
        self.stats["queries"] += len(self._pending)
        self.stats["ticks"] += 1
        self._pending.clear()
        flush_stats["staleness"] = staleness
        flush_stats["queued_updates"] = len(self._queue)
        flush_stats["health"] = list(self.health)
        return results, flush_stats

    def query(self, gids) -> np.ndarray:
        """Submit + tick: logits (k, C) aligned with ``gids``."""
        gids = np.atleast_1d(np.asarray(gids, np.int64))
        self.submit(gids)
        results, _ = self.tick()
        return np.stack([results[int(g)] for g in gids])

    def predict(self, gids) -> np.ndarray:
        return np.argmax(self.query(gids), axis=-1)

    def export_logits(self) -> np.ndarray:
        """(num_nodes, C) logits in global id order (flush first)."""
        self.flush()
        out = np.zeros((self.num_nodes, self.h[self.L][0].shape[-1]),
                       self.dtype)
        for p in range(self.num_parts):
            own = self.l2g[p][: self.n_own[p]]
            out[own] = self.h[self.L][p]
        return out

    # --------------------------------------------------------- constructors
    @classmethod
    def from_engine(cls, engine, pg: PartitionedGraph, params, **kw):
        return cls(engine.model, params, pg,
                   engine.export_serving_state(params), **kw)

    @classmethod
    def from_checkpoint(cls, path: str, engine, pg: PartitionedGraph, **kw):
        """Serve a checkpoint saved with ``train.checkpoint.save_pytree``."""
        from ..train.checkpoint import load_pytree

        params = load_pytree(path, engine.model.init(0))
        return cls.from_engine(engine, pg, params, **kw)


def apply_updates_to_graph(graph: CSRGraph, feature_updates: dict | None = None,
                           add_edges=(), remove_edges=()) -> CSRGraph:
    """Oracle-side mirror of the serving update API: rebuild a CSRGraph
    with the given updates applied.  Per-row in-neighbour lists stay
    sorted by global id — the canonical order both build paths aggregate
    in — so a from-scratch forward over the result is the serving
    engine's bitwise reference."""
    rows = {}

    def row(v: int) -> list[int]:
        if v not in rows:
            rows[v] = list(graph.neighbors(v))
        return rows[v]

    for u, v in add_edges:
        r = row(int(v))
        pos = int(np.searchsorted(r, int(u)))
        if pos >= len(r) or r[pos] != int(u):
            r.insert(pos, int(u))
    for u, v in remove_edges:
        r = row(int(v))
        pos = int(np.searchsorted(r, int(u)))
        if pos < len(r) and r[pos] == int(u):
            r.pop(pos)

    n = graph.num_nodes
    counts = np.diff(graph.indptr).copy()
    for v, r in rows.items():
        counts[v] = len(r)
    indptr = np.zeros(n + 1, graph.indptr.dtype)
    np.cumsum(counts, out=indptr[1:])
    indices = np.empty(int(indptr[-1]), graph.indices.dtype)
    for v in range(n):
        seg = (rows[v] if v in rows
               else graph.indices[graph.indptr[v]:graph.indptr[v + 1]])
        indices[indptr[v]:indptr[v + 1]] = seg

    features = np.array(graph.features, copy=True)
    for gid, vec in (feature_updates or {}).items():
        features[int(gid)] = np.asarray(vec, features.dtype)
    return CSRGraph(indptr=indptr, indices=indices, features=features,
                    labels=graph.labels, train_idx=graph.train_idx,
                    val_idx=graph.val_idx, test_idx=graph.test_idx,
                    num_classes=graph.num_classes, name=graph.name)
