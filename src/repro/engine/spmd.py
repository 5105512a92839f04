"""SPMD execution engine for the EAT pipeline (DESIGN.md §3).

Fused epoch steps instead of a Python loop over partitions: every
partition's graph shard, blocked aggregation structure and minibatch stream
is stacked into ``(P, ...)`` arrays, and each epoch executes as two
compiled calls — one trace scanning ALL training iterations (with the
cross-partition gradient mean inside the scan), one trace for the
full-graph validation forward with its per-layer halo ``all_to_all``
(compiled separately so the pipeline can time training without eval cost;
see DESIGN.md §3).

Three execution modes share one per-shard program:

  spmd        ``shard_map`` over a 1-D partition mesh — one partition per
              device, real collectives.  Picked by ``auto`` when the host
              exposes >= P devices.
  stacked     single-device fallback: the SAME per-shard function under
              ``vmap(axis_name=...)``; jax batches ``lax.all_to_all`` /
              ``lax.pmean`` across the vmapped axis with identical
              semantics, so the program is bit-compatible with the mesh
              version while running on one chip.
  sequential  legible Python-loop reference (sequential.py) — the parity
              oracle for tests/test_engine_parity.py and the numerically
              faithful descendant of the original per-partition driver.

Every ``jax.shard_map`` runs with ``check_vma=False``: the engine's
phase-0 outputs are replicated *by construction* (identical reductions ->
identical updates), which the checker cannot prove through ``lax.scan``.

GraphSAGE's full-graph mean aggregation routes through the Pallas
``segment_agg`` kernel (``use_pallas_agg=True``, compiled on a TPU and
interpreted elsewhere) or, with ``use_pallas_agg=False``, through the jnp
segment-op reference.

Host spans.  The program marks its host-side layer boundaries with
``jax.profiler.TraceAnnotation``; they cost about a microsecond each with
the profiler off and land in its trace, on the device ops' clock, when it
is on.  These are all of them (``<program>`` is the name ``_compiled``
receives; the compiled module of that program reads ``jit_eat_<program>``
with ``-`` spelled ``_``):

  eat.draw               one whole host epoch draw (``_EpochPrefetcher``'s
                         worker); metadata ``batches`` (iterations x
                         partitions) and ``bytes`` (the stacked epoch)
  eat.draw.cbs           the samplers' ``batches()`` calls
  eat.draw.make_batch    one ``make_batch(nodes)`` call; its self time is the
                         padding, labels and the copies of those to the
                         device (no longer the rows' copy where the device
                         gathers them)
  eat.draw.neighbors     ``NeighborSampler.sample``
  eat.draw.gather        ``SampledBlocks.feature_views``: on the device
                         (enqueuing ``jit_eat_gather`` with the int32 ids)
                         from the sampler's staged copy of its own table,
                         in numpy for any other table; metadata ``rows``
                         (rows gathered) and ``device`` (1 on the device,
                         0 in numpy)
  eat.draw.stack         stacking a draw's batches, per iteration and for
                         the epoch
  eat.draw_wait          the main thread waiting for the worker's draw
  eat.dispatch/<program> enqueuing a compiled program
  eat.wait/<program>     blocking until that program's outputs are ready
  eat.compile/<program>  tracing, lowering and compiling it (a cache miss)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.gp.trainer import (GPHyperParams, GRAD_COMPRESS_MODES,
                               grad_topk_size, make_bucketed_reduce_shard,
                               make_bucketed_reduce_stacked,
                               make_fullgraph_loss_fn,
                               make_personalize_partition_step,
                               make_personalize_step,
                               make_topk_reduce_shard,
                               make_topk_reduce_stacked)
from ..graph.distributed import (HALO_COMPRESS_MODES, PartitionedGraph,
                                 halo_refresh_plan,
                                 make_cached_forward, make_distributed_forward,
                                 make_export_forward,
                                 make_overlap_forward, make_pallas_attn_agg,
                                 make_pallas_mean_agg, make_pallas_split_agg,
                                 make_pallas_trim_agg,
                                 make_ref_attn_agg, make_ref_mean_agg,
                                 make_ref_split_agg, wire_row_bytes)
from ..graph.featstore import (assemble_features, check_feat_budget,
                               feat_peak_bytes, reconstruct_features)
from ..train.metrics import f1_scores_jnp
from ..train.optim import apply_updates
from .stacking import (build_stacked_feat_store, build_stacked_halo_cache,
                       build_stacked_halo_residual,
                       build_stacked_gat_blocks,
                       build_stacked_split_vjp_blocks,
                       build_stacked_trim_blocks,
                       build_stacked_vjp_blocks, stack_pytrees)

__all__ = ["AXIS", "EngineConfig", "SPMDEngine", "stack_epoch_batches"]

AXIS = "parts"


@dataclass(frozen=True)
class EngineConfig:
    mode: str = "auto"              # auto | spmd | stacked | sequential
    use_pallas_agg: bool = True     # route eval aggregation through Pallas
    dtype: Any = jnp.float32        # float dtype of graph features
    # boundary/interior split forward: overlap the halo exchange with
    # interior aggregation + the self-term matmul, and restrict dense
    # compute to owned rows (DESIGN.md §5)
    overlap_halo: bool = False
    # 0 = one all_to_all; >= 1 = ppermute ring with that many chunks per
    # step (per-chunk sends interleave on a real mesh; bit-identical data)
    ring_chunks: int = 0
    # objective of the FULL-GRAPH phase-0 mode (the sampled path's loss is
    # the loss_fn the engine is constructed with): "ce" | "focal"
    fg_loss: str = "ce"
    # historical-embedding halo cache (DESIGN.md §8): eval forwards
    # aggregate against the last-received boundary embeddings and only pay
    # the exchange on the halo_refresh_every cadence; halo_cv refreshes a
    # rotating slot chunk on cached epochs (the VR-GCN control-variate
    # delta) instead of going fully stale between refreshes
    halo_cache: bool = False
    halo_refresh_every: int = 1
    halo_cv: bool = False
    # compressed communication (DESIGN.md §11): quantized halo exchange on
    # the eval forwards ("none" | "fp16" | "int8", error-compensated via a
    # carried send-side residual) and the phase-0 gradient all-reduce
    # spelling ("none" | "bucketed" | "topk"); compression off is bit-for-
    # bit today's traces by construction
    halo_compress: str = "none"
    grad_compress: str = "none"
    grad_topk_frac: float = 0.01    # fraction of entries top-k ships
    grad_bucket_kb: int = 512       # bucketed psum slice size
    # two-tier feature store (DESIGN.md §12): keep only the hot_frac
    # highest-scoring owned feature rows resident per partition; cold rows
    # live in host numpy and are staged as compiled-call arguments — every
    # trace reassembles the full plane bitwise before the forward runs
    feat_store: bool = False
    hot_frac: float = 0.5
    hot_policy: str = "degree"      # degree | freq (see graph/featstore.py)
    # partition-group streaming (0 = off): evaluate in groups of G <= P
    # partitions so no (P, maxN, D) feature stack ever materializes —
    # the bigger-than-device path; requires feat_store, stacked mode
    feat_groups: int = 0
    # feature-memory budget in MB (0 = unchecked): the engine refuses to
    # build a configuration whose closed-form peak device feature bytes
    # exceed it (FeatureBudgetError) instead of OOMing mid-epoch
    feat_budget_mb: float = 0.0


def _as_blk(d: dict, dtype) -> dict:
    """One nested pytree per aggregation call site: int arrays stay int32,
    float structure follows the feature dtype."""
    return {k: jnp.asarray(v, dtype) if v.dtype == np.float32
            else jnp.asarray(v) for k, v in d.items()}


def _resolve_mode(mode: str, num_parts: int) -> str:
    if mode != "auto":
        return mode
    if num_parts > 1 and len(jax.devices()) >= num_parts:
        return "spmd"
    return "stacked"


def stack_epoch_batches(samplers, make_batch: Callable, num_parts: int):
    """Draw one epoch of minibatches from every host's sampler and stack them
    into ``(iters, P, ...)`` arrays for the fused epoch step.

    Mirrors the original driver's schedule exactly: ``iters`` is the longest
    host's batch count and shorter hosts wrap around (``it % len``).  Returns
    ``(batches, host_seconds, iters)`` where ``host_seconds[p]`` is the
    host-side sampling/gather time attributed to partition p (the DistDGL
    CPU-worker cost the paper's epoch times include).
    """
    import time

    with TraceAnnotation("eat.draw.cbs"):
        host_batches = [s.batches() for s in samplers]
    iters = max(len(b) for b in host_batches)
    t_host = np.zeros(num_parts)
    rows = []
    for it in range(iters):
        per_p = []
        for p in range(num_parts):
            hb = host_batches[p]
            nodes = hb[it % len(hb)]
            t0 = time.perf_counter()
            with TraceAnnotation("eat.draw.make_batch"):
                per_p.append(make_batch(nodes))
            t_host[p] += time.perf_counter() - t0
        with TraceAnnotation("eat.draw.stack"):
            rows.append(stack_pytrees(per_p))      # (P, ...)
    with TraceAnnotation("eat.draw.stack"):
        batches = stack_pytrees(rows)              # (iters, P, ...)
    return batches, t_host, iters


class SPMDEngine:
    """Fused-epoch executor over a stacked :class:`PartitionedGraph`.

    Public surface (identical across modes; see sequential.py for the
    reference implementation):

      phase0_epoch(params, opt_state, batches) ->
          (params, opt_state, losses (I, P), val_micro (P,))
      phase0_epoch_async(params, opt_state, keys) ->
          (params, opt_state, losses (I, P), val_micro (P,))
      phase1_epoch(pparams, popt, batches, global_params, budgets) ->
          (pparams, popt, losses (I, P), val_micro (P,))
      phase1_epoch_async(pparams, popt, keys, budgets, global_params) ->
          (pparams, popt, losses (i_run, P), val_micro (P,))
      evaluate(params_or_pparams, split) -> (micro (P,), preds (P, maxN))

    ``budgets`` is a per-partition iteration budget (int32, (P,)); a bool
    ``active`` vector is accepted and promoted to full-epoch-or-zero.  The
    async variants need :meth:`set_device_sampler` and run the epoch draw +
    fanout sampling + feature gather on the epoch trace (DESIGN.md §4, §7);
    ``phase0_epoch_async`` additionally fuses the validation eval forward
    into the SAME compiled call, so a generalization epoch is one
    host→device round-trip.
    """

    def __init__(self, model, loss_fn, optimizer, pg: PartitionedGraph,
                 hp: GPHyperParams = GPHyperParams(),
                 config: EngineConfig = EngineConfig()):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.hp = hp
        self.config = config
        self.num_parts = pg.num_parts
        self.num_classes = model.num_classes
        self.max_nodes = pg.max_nodes
        self.mode = _resolve_mode(config.mode, pg.num_parts)
        if config.feat_groups:
            if not config.feat_store:
                raise ValueError(
                    "feat_groups streams the feat-store cold tier over "
                    "partition groups; enable feat_store to use it")
            if not 1 <= config.feat_groups <= pg.num_parts:
                raise ValueError(
                    f"feat_groups must be in [1, num_parts], got "
                    f"{config.feat_groups}")
            if config.mode == "spmd":
                raise ValueError(
                    "feat_groups is a host-orchestrated streaming eval over "
                    "partition groups; the one-partition-per-device mesh "
                    "needs all planes at once — use stacked mode")
            if (config.halo_cache or config.overlap_halo
                    or config.halo_compress != "none"):
                raise ValueError(
                    "feat_groups streams the eval through the plain "
                    "sequential exchange; it has no cached/compressed/"
                    "overlapped spelling — pick one")
            # "auto" must not pick spmd: the streamed eval is stacked-only
            self.mode = "stacked"

        if config.halo_compress not in HALO_COMPRESS_MODES:
            raise ValueError(f"unknown halo_compress {config.halo_compress!r} "
                             f"(expected one of {HALO_COMPRESS_MODES})")
        if config.grad_compress not in GRAD_COMPRESS_MODES:
            raise ValueError(f"unknown grad_compress {config.grad_compress!r} "
                             f"(expected one of {GRAD_COMPRESS_MODES})")
        if config.halo_compress != "none" and config.overlap_halo:
            raise ValueError(
                "halo_compress quantizes the gathered send buffer on the "
                "combined-edge eval forward; the overlap forward has no "
                "compressed spelling — pick one")
        self.halo_compress = config.halo_compress
        self.grad_compress = config.grad_compress
        # wire accounting basis: real halo rows per layer and the payload
        # dtype's itemsize (never a hardcoded 4)
        self._halo_rows_total = int(pg.n_halo.sum())
        self._halo_row_width = pg.features.shape[-1]
        self._halo_itemsize = pg.features.dtype.itemsize

        f = config.dtype
        self.feat_store = bool(config.feat_store)
        # host->device bytes spent staging cold feature rows (counted where
        # the numpy staging buffer is handed to a compiled call); stays 0
        # all-resident and at hot_frac=1.0 (zero-size cold tier)
        self.cold_h2d_bytes = 0
        self._fs = None
        self._cold_host = None
        self._streamer = None
        shards = {
            "send_idx": jnp.asarray(pg.send_idx),
            "send_mask": jnp.asarray(pg.send_mask, f),
            "recv_pos": jnp.asarray(pg.recv_pos),
        }
        if self.feat_store:
            entries, self._fs = build_stacked_feat_store(
                pg, config.hot_frac, config.hot_policy, f)
            shards.update(entries)
            self._cold_host = self._fs.cold
        else:
            shards["features"] = jnp.asarray(pg.features, f)
        check_feat_budget(config.feat_budget_mb, self._feat_peak_bytes(pg),
                          context=f"mode={self.mode}")
        # GAT's attention: the transpose reads alpha through a slot map
        attention = model.aggregation == "attention"
        if config.overlap_halo:
            # split forward state: the per-partition interior row count plus
            # ONE aggregation backend's structures (the other is never read)
            shards["n_int"] = jnp.asarray(pg.n_int, jnp.int32)
            if config.use_pallas_agg:
                bi, bb = build_stacked_split_vjp_blocks(pg)
                shards["blk_int"] = _as_blk(bi, f)
                shards["blk_bnd"] = _as_blk(bb, f)
            else:
                shards.update({
                    "int_src": jnp.asarray(pg.int_src),
                    "int_dst": jnp.asarray(pg.int_dst),
                    "bnd_src": jnp.asarray(pg.bnd_src),
                    "bnd_dst": jnp.asarray(pg.bnd_dst),
                    "deg": jnp.asarray(pg.deg, f),
                })
        else:
            shards.update({
                "edge_src": jnp.asarray(pg.edge_src),
                "edge_dst": jnp.asarray(pg.edge_dst),
                "edge_mask": jnp.asarray(pg.edge_mask, f),
            })
            if config.use_pallas_agg:
                shards["blk"] = _as_blk(
                    build_stacked_gat_blocks(pg) if attention
                    else build_stacked_vjp_blocks(pg), f)
        # edge slots of one whole-graph aggregation, every partition: the
        # chunk layouts' on the Pallas backend, the padded edge lists' on
        # the jnp one (the second number of last_eval_agg_slots)
        blk = [shards[k] for k in ("blk", "blk_int", "blk_bnd") if k in shards]
        self._agg_slots = (sum(int(b["src"].size) for b in blk) if blk else
                           sum(int(shards[k].size) for k in
                               ("edge_src", "int_src", "bnd_src")
                               if k in shards))
        self._mesh = None
        if self.mode == "spmd":
            from ..launch.mesh import make_partition_mesh
            self._mesh = make_partition_mesh(self.num_parts, AXIS)
        # the resident (P, ...) graph data; on the mesh each device holds
        # only its own partition's slice
        resident = {"shards": shards, "labels": pg.labels,
                    "masks": {"train": pg.train_mask, "val": pg.val_mask,
                              "test": pg.test_mask}}
        where = (NamedSharding(self._mesh, P(AXIS)) if self._mesh is not None
                 else None)
        self._where = where
        self._resident = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), where), resident)

        meta = {"max_nodes": pg.max_nodes, "own_cap": pg.own_cap}
        self._fwd_meta = meta
        if config.overlap_halo:
            if config.halo_cache:
                raise ValueError(
                    "halo_cache and overlap_halo are alternative exchange "
                    "optimisations: the cache removes the very exchange the "
                    "overlap would hide — pick one")
            aggs = (make_pallas_split_agg(pg.own_cap)
                    if config.use_pallas_agg else make_ref_split_agg(pg.own_cap))
            self.fwd = make_overlap_forward(
                model, meta, axis_name=AXIS, agg_interior=aggs[0],
                agg_boundary=aggs[1], ring_chunks=config.ring_chunks)
        else:
            if attention:
                agg = (make_pallas_attn_agg(pg.max_nodes)
                       if config.use_pallas_agg
                       else make_ref_attn_agg(pg.max_nodes))
            else:
                agg = (make_pallas_mean_agg(pg.max_nodes)
                       if config.use_pallas_agg
                       else make_ref_mean_agg(pg.max_nodes))
            self._mean_agg = agg
            self.fwd = make_distributed_forward(model, meta, axis_name=AXIS,
                                                agg=agg)
            if config.halo_compress != "none":
                # the compressed eval forward; self.fwd stays uncompressed
                # (full-graph training differentiates through the live
                # exchange, and the serving export needs exact embeddings)
                self._fwd_comp = make_distributed_forward(
                    model, meta, axis_name=AXIS, agg=agg,
                    compress=config.halo_compress,
                    ring_chunks=config.ring_chunks)
        # the evaluation's last mean layer over the evaluated split's
        # in-edges only (_eval_trim); the other spellings keep the full
        # layout, each with its own aggregation
        self._fwd_trim = None
        self._trim: dict = {}
        self.last_eval_agg_slots = (0, 0)   # (slots the latest evaluate's
                                            # last layer aggregated, slots
                                            # of the full layout)
        if (model.aggregation == "mean" and config.use_pallas_agg
                and not (config.overlap_halo or config.halo_cache
                         or config.halo_compress != "none"
                         or config.feat_groups)):
            self._fwd_trim = make_distributed_forward(
                model, meta, axis_name=AXIS, agg=self._mean_agg,
                last_agg=make_pallas_trim_agg(pg.max_nodes))
        if self.halo_compress != "none":
            self._halo_residual = jax.tree.map(
                lambda x: jnp.asarray(x, f),
                build_stacked_halo_residual(pg, model.layer_input_dims))
        self._grad_res = None   # lazy (P, N) top-k error-feedback state
        self.halo_cache = bool(config.halo_cache)
        self.last_halo_exchange_bytes = 0
        if self.halo_cache:
            self.max_send = pg.send_idx.shape[-1]
            # real (unpadded) rows per send-slot index, for the refreshed-
            # payload accounting; halo_slot_bytes(0, maxS) == the graph's
            # halo_bytes_per_layer
            self._halo_slot_counts = np.asarray(pg.send_mask).sum(axis=(0, 1))
            self._halo_byte_per_slot = wire_row_bytes(
                pg.features.shape[-1], config.halo_compress,
                pg.features.dtype.itemsize)
            self._halo_state = jax.tree.map(
                lambda x: jnp.asarray(x, f),
                build_stacked_halo_cache(pg, model.layer_input_dims))
            self._halo_age = 0
            self._cached_fwds: dict = {}
            # the full refresh every cache starts with; refuses a model the
            # cached spelling cannot run, here rather than at the first eval
            self._cached_fwd(0, self.max_send)
        # fault injection (DESIGN.md §10): when armed, the next eval
        # forward's freshly exchanged cache payload is "lost in transit" —
        # the stale cache is kept and ages on
        self._drop_next_refresh = False
        self.halo_refresh_drops = 0
        # full-graph phase-0: value_and_grad straight through self.fwd (the
        # halo-exchange forward whose aggregation op carries a custom VJP)
        self._fg_loss = make_fullgraph_loss_fn(self.fwd, loss=config.fg_loss)
        self._pstep = make_personalize_step(loss_fn, optimizer, hp)
        self._device_sampler = None
        self._sampler_gen = 0
        self.last_eval_seconds = 0.0   # execution time of the latest
                                       # separately-compiled evaluate() call
        self._cache: dict = {}
        self.compile_count = 0

    # ------------------------------------------------------------ plumbing
    @property
    def shards(self) -> dict:
        """Per-partition graph shards, every leaf stacked ``(P, ...)``."""
        return self._resident["shards"]

    @property
    def labels(self):
        return self._resident["labels"]

    @property
    def masks(self) -> dict:
        return self._resident["masks"]

    def _shape_key(self, name: str, args) -> tuple:
        # shardings are part of the key: an AOT executable is specialised to
        # its input shardings, and epoch 2's params arrive sharded over the
        # mesh while epoch 1's broadcast-fresh params were replicated.
        # weak_type too: jit specialises on it, and a python-scalar-built
        # array would otherwise collide with a strongly-typed one
        leaves = jax.tree_util.tree_leaves(args)
        return (name,) + tuple(
            (l.shape, str(l.dtype), bool(getattr(l, "weak_type", False)),
             str(getattr(l, "sharding", "")))
            for l in leaves)

    def _compiled(self, name: str, fn: Callable, *args):
        """AOT lower+compile once per input-shape signature, so epoch timing
        in the pipeline never includes XLA compilation.  ``compile_count``
        exposes the misses: identically shaped/sharded fresh inputs must
        reuse the executable (locked by a tier-1 regression test).

        The resident graph data enters every executable as its leading
        ARGUMENT, never as a closed-over constant, which would be baked into
        each program (one device copy per executable, and on a mesh a full
        copy on every device).  ``fn`` reads it through ``shards`` /
        ``labels`` / ``masks``, bound to that argument while it traces.

        The returned call opens ``eat.dispatch/<name>`` around the enqueue
        and carries ``name`` as ``.program`` for :meth:`_timed`."""
        key = self._shape_key(name, args)
        if key not in self._cache:
            import re

            self.compile_count += 1

            def with_resident(resident, *a):
                saved, self._resident = self._resident, resident
                try:
                    return fn(*a)
                finally:
                    self._resident = saved

            # the compiled module, and so the device trace, reads
            # jit_eat_<name>
            with_resident.__name__ = "eat_" + re.sub(r"\W", "_", name)
            with TraceAnnotation(f"eat.compile/{name}"):
                self._cache[key] = jax.jit(with_resident).lower(
                    self._resident, *args).compile()
        exe = self._cache[key]

        def run(*a):
            with TraceAnnotation(f"eat.dispatch/{name}"):
                return exe(self._resident, *a)

        run.program = name
        return run

    def _micro_of(self, preds, labels, mask):
        lab = jnp.where(mask, labels, -1)
        micro, _, _ = f1_scores_jnp(preds, lab, self.num_classes)
        return micro

    # ------------------------------------------- two-tier feature store
    def _feat_peak_bytes(self, pg) -> int:
        d = pg.features.shape[-1]
        b = np.dtype(self.config.dtype).itemsize
        if not self.feat_store:
            return feat_peak_bytes(self.num_parts, pg.max_nodes, d, b)
        return feat_peak_bytes(
            self.num_parts, pg.max_nodes, d, b,
            hot_rows=self._fs.hot.shape[1], cold_rows=self._fs.cold.shape[1],
            groups=self.config.feat_groups)

    def _featurize(self, shard, cold):
        """Reassemble one partition's full feature plane on-trace from the
        resident hot tier and the staged cold rows — bitwise equal to the
        all-resident ``shard["features"]`` (graph/featstore.py invariant),
        so every downstream forward (plain/cached/compressed/overlap) is
        untouched.  Passthrough when the store is off."""
        if not self.feat_store:
            return shard
        s = dict(shard)
        s["features"] = assemble_features(
            s.pop("fs_hot"), s.pop("fs_rows_hot"),
            cold, s.pop("fs_rows_cold"), self.max_nodes)
        return s

    def _stage_cold(self):
        """The (P, C, D) cold staging buffer for ONE compiled call.  Numpy
        on purpose: handing a host array to the executable is the actual
        host->device transfer the store trades residency for, counted here."""
        self.cold_h2d_bytes += self._cold_host.nbytes
        return self._cold_host

    def _fs_args(self) -> tuple:
        """Trailing compiled-call args of any trace that reassembles the
        shard feature plane: ``(cold,)`` under the store, ``()`` otherwise
        (keeping the all-resident call signatures byte-identical)."""
        return (self._stage_cold(),) if self.feat_store else ()

    def _stage_sampler_cold(self):
        """The device sampler's (Nc, D) cold rows for one epoch call."""
        ch = self._device_sampler.cold_host
        self.cold_h2d_bytes += ch.nbytes
        return ch

    @property
    def resident_feature_bytes(self) -> int:
        """Device-resident feature bytes: the engine's stacked plane (or
        hot tier) plus the attached device sampler's gather table (or its
        hot tier) — the footprint the feature store shrinks."""
        arr = self.shards["fs_hot"] if self.feat_store \
            else self.shards["features"]
        total = int(arr.size) * arr.dtype.itemsize
        ds = self._device_sampler
        if ds is not None:
            t = ds.features if ds.features is not None else ds.hot_feats
            total += int(t.size) * t.dtype.itemsize
        return total

    # ------------------------------------------ historical halo cache state
    # The cache ages once per distributed eval forward (standalone evaluate
    # OR the fused async epoch's eval); the refresh slot range is a host-side
    # constant from halo_refresh_plan, so each plan compiles its own
    # executable and the pure-cached one contains no collective at all.

    def _halo_plan(self) -> tuple[int, int]:
        if self._drop_next_refresh:
            self._drop_next_refresh = False
            self.halo_refresh_drops += 1
            return (0, 0)
        return halo_refresh_plan(self._halo_age, self.config.halo_refresh_every,
                                 self.config.halo_cv, self.max_send)

    def _halo_slot_bytes(self, lo: int, hi: int) -> int:
        return int(self._halo_slot_counts[lo:hi].sum()) * self._halo_byte_per_slot

    def _halo_tick(self, plan: tuple[int, int], new_state) -> None:
        self._halo_state = new_state
        # one exchange per SAGE layer, each shipping only the refreshed slots
        self.last_halo_exchange_bytes = (self.model.num_layers
                                         * self._halo_slot_bytes(*plan))
        self._halo_age += 1

    def drop_next_halo_refresh(self) -> None:
        """Arm the dropped-payload fault: the next eval forward runs the
        pure-cached plan (0, 0) — it aggregates fully against the stale
        cache and ships no refresh bytes, exactly as if the scheduled
        payload was lost in transit — while the cache still ages."""
        self._drop_next_refresh = True

    # ---- checkpoint/resume surface (RunCheckpointer) ---------------------
    def halo_cache_state(self):
        """(cache pytree, age) for checkpointing; None without the cache."""
        if not self.halo_cache:
            return None
        return self._halo_state, self._halo_age

    def restore_halo_cache_state(self, state, age: int) -> None:
        if not self.halo_cache:
            raise ValueError("engine built without halo_cache")
        f = self.config.dtype
        self._halo_state = jax.tree.map(lambda x: jnp.asarray(x, f), state)
        self._halo_age = int(age)

    # -------------------------------------- compressed communication state
    @property
    def halo_wire_bytes_per_layer(self) -> int:
        """Real payload bytes ONE layer's halo exchange puts on the wire
        under the configured compression — the dtype-truthful replacement
        for assuming 4-byte rows.  Equals ``pg.halo_bytes_per_layer`` when
        ``halo_compress == "none"``."""
        return self._halo_rows_total * wire_row_bytes(
            self._halo_row_width, self.halo_compress, self._halo_itemsize)

    def _grad_residual(self, params):
        """Lazily-built (P, N) top-k error-feedback state (flat per-partition
        gradient space), zero before the first compressed sync."""
        if self._grad_res is None:
            from jax.flatten_util import ravel_pytree

            flat, _ = ravel_pytree(params)
            self._grad_res = jnp.zeros((self.num_parts, flat.shape[0]),
                                       flat.dtype)
        return self._grad_res

    def comm_residual_state(self):
        """Error-feedback residual pytrees for checkpointing:
        ``(halo_residual, grad_residual)``; each entry is None when the
        matching compression is off (or, for top-k, before the first
        phase-0 step).  None when neither exists."""
        h = self._halo_residual if self.halo_compress != "none" else None
        g = self._grad_res if self.grad_compress == "topk" else None
        if h is None and g is None:
            return None
        return h, g

    def restore_comm_residual_state(self, state) -> None:
        h, g = state
        if h is not None:
            f = self.config.dtype
            self._halo_residual = jax.tree.map(
                lambda x: jnp.asarray(x, f), h)
        if g is not None:
            self._grad_res = jnp.asarray(g)

    def _cached_fwd(self, lo: int, hi: int):
        key = (lo, hi)
        if key not in self._cached_fwds:
            self._cached_fwds[key] = make_cached_forward(
                self.model, self._fwd_meta, axis_name=AXIS,
                agg=self._mean_agg, refresh_lo=lo, refresh_hi=hi,
                ring_chunks=self.config.ring_chunks,
                compress=self.halo_compress)
        return self._cached_fwds[key]

    def _eval_stacked_cached(self, params, cache, split: str,
                             per_partition_params: bool, plan, residual=None,
                             fs=()):
        fwd_c = self._cached_fwd(*plan)

        if residual is not None:
            def one_c(prm, shard, c, r, labels, mask, *cold):
                logits, nc, nr = fwd_c(prm, self._featurize(shard, *cold)
                                       if cold else shard, c, r)
                preds = jnp.argmax(logits, axis=-1)
                return self._micro_of(preds, labels, mask), preds, nc, nr

            return jax.vmap(one_c, axis_name=AXIS,
                            in_axes=(0 if per_partition_params else None,
                                     0, 0, 0, 0, 0) + (0,) * len(fs))(
                params, self.shards, cache, residual, self.labels,
                self.masks[split], *fs)

        def one(prm, shard, c, labels, mask, *cold):
            logits, nc = fwd_c(prm, self._featurize(shard, *cold)
                               if cold else shard, c)
            preds = jnp.argmax(logits, axis=-1)
            return self._micro_of(preds, labels, mask), preds, nc

        return jax.vmap(one, axis_name=AXIS,
                        in_axes=(0 if per_partition_params else None,
                                 0, 0, 0, 0) + (0,) * len(fs))(
            params, self.shards, cache, self.labels, self.masks[split], *fs)

    def _eval_spmd_cached(self, params, cache, split: str,
                          per_partition_params: bool, plan, residual=None,
                          fs=()):
        fwd_c = self._cached_fwd(*plan)
        comp = residual is not None

        def shard_fn(prm, cache_s, shard_s, labels_s, mask_s, *rest_s):
            rest = list(rest_s)
            p = jax.tree.map(lambda x: x[0], prm) if per_partition_params else prm
            sh = jax.tree.map(lambda x: x[0], shard_s)
            c = jax.tree.map(lambda x: x[0], cache_s)
            res_s = rest.pop(0) if comp else None
            if rest:                                  # staged cold rows
                sh = self._featurize(sh, rest[0][0])
            if comp:
                r = jax.tree.map(lambda x: x[0], res_s)
                logits, nc, nr = fwd_c(p, sh, c, r)
            else:
                logits, nc = fwd_c(p, sh, c)
            preds = jnp.argmax(logits, axis=-1)
            micro = self._micro_of(preds, labels_s[0], mask_s[0])
            head = (micro[None], preds[None],
                    jax.tree.map(lambda x: x[None], nc))
            return head + ((jax.tree.map(lambda x: x[None], nr),)
                           if comp else ())

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(AXIS) if per_partition_params else P(),
                      P(AXIS), P(AXIS), P(AXIS), P(AXIS))
                     + ((P(AXIS),) if comp else ())
                     + (P(AXIS),) * len(fs),
            out_specs=(P(AXIS), P(AXIS), P(AXIS))
                      + ((P(AXIS),) if comp else ()), check_vma=False)
        args = (params, cache, self.shards, self.labels, self.masks[split])
        if comp:
            args = args + (residual,)
        return fn(*(args + tuple(fs)))

    def _eval_stacked_comp(self, params, residual, split: str,
                           per_partition_params: bool, fs=()):
        def one(prm, shard, r, labels, mask, *cold):
            logits, nr = self._fwd_comp(prm, self._featurize(shard, *cold)
                                        if cold else shard, r)
            preds = jnp.argmax(logits, axis=-1)
            return self._micro_of(preds, labels, mask), preds, nr

        return jax.vmap(one, axis_name=AXIS,
                        in_axes=(0 if per_partition_params else None,
                                 0, 0, 0, 0) + (0,) * len(fs))(
            params, self.shards, residual, self.labels, self.masks[split],
            *fs)

    def _eval_spmd_comp(self, params, residual, split: str,
                        per_partition_params: bool, fs=()):
        def shard_fn(prm, res_s, shard_s, labels_s, mask_s, *cold_s):
            p = jax.tree.map(lambda x: x[0], prm) if per_partition_params else prm
            sh = jax.tree.map(lambda x: x[0], shard_s)
            if cold_s:
                sh = self._featurize(sh, cold_s[0][0])
            r = jax.tree.map(lambda x: x[0], res_s)
            logits, nr = self._fwd_comp(p, sh, r)
            preds = jnp.argmax(logits, axis=-1)
            micro = self._micro_of(preds, labels_s[0], mask_s[0])
            return micro[None], preds[None], jax.tree.map(lambda x: x[None], nr)

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(AXIS) if per_partition_params else P(),
                      P(AXIS), P(AXIS), P(AXIS), P(AXIS))
                     + (P(AXIS),) * len(fs),
            out_specs=(P(AXIS), P(AXIS), P(AXIS)), check_vma=False)
        return fn(params, residual, self.shards, self.labels,
                  self.masks[split], *fs)

    # ------------------------------------------------- stacked (vmap) mode
    def _eval_stacked(self, params, split: str, per_partition_params: bool,
                      fs=(), trim=None):
        fwd, shards = self._eval_fwd(trim)

        def one(prm, shard, *cold):
            return fwd(prm, self._featurize(shard, *cold) if cold else shard)

        in_axes = (0 if per_partition_params else None, 0) + (0,) * len(fs)
        logits = jax.vmap(one, axis_name=AXIS, in_axes=in_axes)(
            params, shards, *fs)                     # (P, maxN, C)
        preds = self._split_preds(logits, self.masks[split], trim)
        micro = jax.vmap(self._micro_of)(preds, self.labels, self.masks[split])
        return micro, preds

    def _eval_fwd(self, trim):
        """The eval's forward and the shards it reads: with a trimmed
        structure, the last layer aggregates over it (``blk_trim``)."""
        if trim is None:
            return self.fwd, self.shards
        return self._fwd_trim, {**self.shards, "blk_trim": trim}

    @staticmethod
    def _split_preds(logits, mask, trim):
        """Each row's class; -1 outside the split where the last layer ran
        over the split's in-edges only."""
        preds = jnp.argmax(logits, axis=-1)
        return preds if trim is None else jnp.where(mask, preds, -1)

    def _eval_trim(self, split: str):
        """``(structure, slots)`` of ``split``'s last eval layer: the
        resident (P, ...) structure over the split rows' in-edges
        (``build_stacked_trim_blocks``), built on the split's first
        evaluate, and the chunk slots it runs; ``(None, full slots)`` where
        the full layout is kept — a spelling with its own aggregation, or
        a split whose trimmed layout has no fewer chunks."""
        if self._fwd_trim is None:
            return None, self._agg_slots
        if split not in self._trim:
            sh = self.shards
            b = build_stacked_trim_blocks(
                np.asarray(sh["edge_src"]), np.asarray(sh["edge_dst"]),
                np.asarray(sh["edge_mask"]), np.asarray(self.masks[split]),
                np.asarray(sh["blk"]["deg"]))
            if b["src"].shape[1] < sh["blk"]["src"].shape[1]:
                self._trim[split] = (jax.device_put(
                    _as_blk(b, self.config.dtype), self._where),
                    int(b["src"].size))
            else:
                self._trim[split] = (None, self._agg_slots)
        return self._trim[split]

    def _grad_reduce_stacked(self):
        """Stacked-mode gradient reducer for the configured grad_compress
        mode: ``reduce(grads_stacked) -> grads`` (none / bucketed) or
        ``reduce(grads_stacked, residual) -> (grads, residual)`` (topk)."""
        num_parts = self.num_parts
        if self.grad_compress == "bucketed":
            return make_bucketed_reduce_stacked(
                num_parts, self.config.grad_bucket_kb * 1024)
        if self.grad_compress == "topk":
            return make_topk_reduce_stacked(num_parts,
                                            self.config.grad_topk_frac)
        # the all-reduce: the same stacked-axis mean the mesh computes
        # after its all_gather
        return lambda grads: jax.tree.map(
            lambda g: jnp.sum(g, axis=0) / num_parts, grads)

    def _grad_reduce_shard(self):
        """Per-shard (collective) reducer for grad_compress.  The plain mean
        is spelled ``all_gather`` + a local stack-axis sum: pure data
        movement followed by the stacked mode's own deterministic
        reduction, so a mesh run is bitwise the stacked one (a ``pmean``'s
        reduction order is the collective implementation's choice)."""
        num_parts = self.num_parts
        if self.grad_compress == "bucketed":
            return make_bucketed_reduce_shard(
                num_parts, AXIS, self.config.grad_bucket_kb * 1024)
        if self.grad_compress == "topk":
            return make_topk_reduce_shard(num_parts, AXIS,
                                          self.config.grad_topk_frac)

        def mean(grads):
            g_all = jax.lax.all_gather(grads, AXIS)          # (P, ...)
            return jax.tree.map(lambda g: jnp.sum(g, axis=0) / num_parts,
                                g_all)

        return mean

    def _phase0_stacked(self, params, opt_state, batches, grad_res=None):
        reduce = self._grad_reduce_stacked()

        if self.grad_compress == "topk":
            def one_iter_t(carry, b_it):
                params, opt_state, res = carry
                losses, grads = jax.vmap(
                    jax.value_and_grad(self.loss_fn),
                    in_axes=(None, 0))(params, b_it)
                grads, res = reduce(grads, res)
                updates, opt_state = self.optimizer.update(grads, opt_state,
                                                           params)
                return (apply_updates(params, updates), opt_state, res), losses

            (params, opt_state, grad_res), losses = jax.lax.scan(
                one_iter_t, (params, opt_state, grad_res), batches)
            return params, opt_state, losses, grad_res

        def one_iter(carry, b_it):
            params, opt_state = carry
            losses, grads = jax.vmap(
                jax.value_and_grad(self.loss_fn), in_axes=(None, 0))(params, b_it)
            grads = reduce(grads)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return (params, opt_state), losses

        (params, opt_state), losses = jax.lax.scan(
            one_iter, (params, opt_state), batches)
        return params, opt_state, losses

    def _fg_batch(self):
        """The full-graph 'batch': every partition's graph shard + labels +
        train mask, (P, ...)-stacked like any minibatch pytree."""
        return {"shard": self.shards, "labels": self.labels,
                "train_mask": self.masks["train"]}

    def _phase0_fullgraph_stacked(self, params, opt_state, iters: int):
        batch = self._fg_batch()
        reduce = self._grad_reduce_stacked()

        def one_iter(carry, _):
            params, opt_state = carry
            # vmap with the collective axis bound: each partition's loss
            # differentiates THROUGH the halo exchange, so grads[p] includes
            # the paths via embeddings p shipped to other partitions
            losses, grads = jax.vmap(
                jax.value_and_grad(self._fg_loss), in_axes=(None, 0),
                axis_name=AXIS)(params, batch)
            grads = reduce(grads)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return (params, opt_state), losses

        (params, opt_state), losses = jax.lax.scan(
            one_iter, (params, opt_state), None, length=iters)
        return params, opt_state, losses

    def _phase0_fullgraph_spmd(self, params, opt_state, iters: int):
        g_reduce = self._grad_reduce_shard()

        def shard_fn(params, opt_state, shard_s, labels_s, mask_s):
            batch = {"shard": jax.tree.map(lambda x: x[0], shard_s),
                     "labels": labels_s[0], "train_mask": mask_s[0]}

            def one(carry, _):
                p, o = carry
                loss, grads = jax.value_and_grad(self._fg_loss)(p, batch)
                grads = g_reduce(grads)
                updates, o = self.optimizer.update(grads, o, p)
                return (apply_updates(p, updates), o), loss

            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), None, length=iters)
            return params, opt_state, losses[:, None]

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P(None, AXIS)), check_vma=False)
        return fn(params, opt_state, self.shards, self.labels,
                  self.masks["train"])

    def _phase0_async_partition_program(self, plan=None):
        """ONE partition's fused generalization epoch: epoch draw (uniform
        shuffle, or the CBS-weighted Eq. 3 mini-epoch when the sampler is
        class-balanced), per-iteration batch materialisation, the train scan
        with the cross-partition gradient mean, and the validation eval
        forward — all on a single trace (DESIGN.md §7).  The SINGLE body both
        modes execute, so PRNG consumption order cannot drift between them.

        The default gradient all-reduce is spelled ``all_gather`` + a local
        stack-axis sum: pure data movement followed by the SAME deterministic
        reduction the sequential oracle performs, which is what makes the
        spmd mesh mode bit-for-bit with the reference (a ``pmean``'s
        reduction order is the collective implementation's choice).
        ``grad_compress`` swaps in the bucketed-psum or top-k spelling.

        ``*state`` carries the eval/EF pytrees in a fixed order — halo
        cache (when ``plan`` is set), halo residual (``halo_compress``),
        flat gradient residual (``grad_compress == "topk"``) — and the
        return tuple appends their updated values in the same order after
        ``(params, opt_state, losses, micro)``.  Under the feature store
        two staged cold buffers follow the state (the sampler's (Nc, D)
        rows for the batch gathers, this partition's (C, D) rows for the
        fused eval's plane); they are inputs only, never returned.
        """
        ds = self._device_sampler
        comp = self.halo_compress != "none"
        topk = self.grad_compress == "topk"
        fs_on = self.feat_store
        fwd_c = self._cached_fwd(*plan) if plan is not None else None
        g_reduce = self._grad_reduce_shard()

        def per_part(params, opt_state, key, logp_row, train_row, k_row,
                     shard, labels, val_mask, *state):
            st = list(state)
            cache = st.pop(0) if fwd_c is not None else None
            h_res = st.pop(0) if comp else None
            g_res = st.pop(0) if topk else None
            ck = {"cold": st.pop(0)} if fs_on else {}
            sh_cold = st.pop(0) if fs_on else None
            kd, ke = jax.random.split(key)
            nodes, valid = ds.draw_epoch(kd, logp_row, train_row, k_row)
            iter_keys = jax.random.split(ke, ds.num_batches)

            if topk:
                def one_t(carry, xs):
                    n_i, v_i, k_i = xs
                    p, o, r = carry
                    batch = ds.make_batch(k_i, n_i, v_i, **ck)
                    loss, grads = jax.value_and_grad(self.loss_fn)(p, batch)
                    grads, r = g_reduce(grads, r)
                    updates, o = self.optimizer.update(grads, o, p)
                    return (apply_updates(p, updates), o, r), loss

                (params, opt_state, g_res), losses = jax.lax.scan(
                    one_t, (params, opt_state, g_res),
                    (nodes, valid, iter_keys))
            else:
                def one(carry, xs):
                    n_i, v_i, k_i = xs
                    p, o = carry
                    batch = ds.make_batch(k_i, n_i, v_i, **ck)
                    loss, grads = jax.value_and_grad(self.loss_fn)(p, batch)
                    grads = g_reduce(grads)
                    updates, o = self.optimizer.update(grads, o, p)
                    return (apply_updates(p, updates), o), loss

                (params, opt_state), losses = jax.lax.scan(
                    one, (params, opt_state), (nodes, valid, iter_keys))
            if fs_on:
                # reassemble the shard plane only now, after the (feature-
                # free) train scan, so the assembled array's live range is
                # just the fused eval
                shard = self._featurize(shard, sh_cold)
            # fused eval: the validation forward (halo exchange + blocked
            # aggregation + on-device F1) on the epoch's final params, in
            # the SAME device program as the train scan
            extras = []
            if fwd_c is not None:
                if comp:
                    logits, new_cache, new_hres = fwd_c(params, shard,
                                                        cache, h_res)
                    extras += [new_cache, new_hres]
                else:
                    logits, new_cache = fwd_c(params, shard, cache)
                    extras += [new_cache]
            elif comp:
                logits, new_hres = self._fwd_comp(params, shard, h_res)
                extras += [new_hres]
            else:
                logits = self.fwd(params, shard)
            preds = jnp.argmax(logits, axis=-1)
            micro = self._micro_of(preds, labels, val_mask)
            if topk:
                extras += [g_res]
            return (params, opt_state, losses, micro) + tuple(extras)

        return per_part

    def _phase0_async_stacked(self, params, opt_state, keys, state=(),
                              plan=None, fs=()):
        ds = self._device_sampler
        per_part = self._phase0_async_partition_program(plan)
        # fs = (sampler cold (Nc, D) — replicated, shard cold (P, C, D))
        extra_axes = (0,) * len(state) + ((None, 0) if fs else ())
        out = jax.vmap(
            per_part, axis_name=AXIS,
            in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0) + extra_axes)(
                params, opt_state, keys, ds.logp, ds.train_idx, ds.k,
                self.shards, self.labels, self.masks["val"], *state, *fs)
        params, opt_state, losses, micro = out[:4]
        # every partition applies the identical mean update to the identical
        # replica: return one copy (bitwise equal across the stacked axis)
        head = (jax.tree.map(lambda x: x[0], params),
                jax.tree.map(lambda x: x[0], opt_state),
                losses.T, micro)                    # (I, P), (P,)
        return head + tuple(out[4:])

    def _phase0_async_spmd(self, params, opt_state, keys, state=(),
                           plan=None, fs=()):
        ds = self._device_sampler
        n_st = len(state)

        def shard_fn(params, opt_state, key_s, logp_s, train_s, k_s,
                     shard_s, labels_s, mask_s, *rest_s):
            per_part = self._phase0_async_partition_program(plan)
            sh = jax.tree.map(lambda x: x[0], shard_s)
            extra = tuple(jax.tree.map(lambda x: x[0], c)
                          for c in rest_s[:n_st])
            if fs:
                # sampler cold is replicated (P() spec — arrives whole);
                # the per-partition shard cold is sharded like the shards
                extra += (rest_s[n_st], rest_s[n_st + 1][0])
            out = per_part(
                params, opt_state, key_s[0], logp_s[0], train_s[0], k_s[0],
                sh, labels_s[0], mask_s[0], *extra)
            params, opt_state, losses, micro = out[:4]
            head = (params, opt_state, losses[:, None], micro[None])
            return head + tuple(jax.tree.map(lambda x: x[None], c)
                                for c in out[4:])

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS),
                      P(AXIS), P(AXIS), P(AXIS)) + (P(AXIS),) * n_st
                     + ((P(), P(AXIS)) if fs else ()),
            out_specs=(P(), P(), P(None, AXIS), P(AXIS)) + (P(AXIS),) * n_st,
            check_vma=False)
        args = (params, opt_state, keys, ds.logp, ds.train_idx, ds.k,
                self.shards, self.labels, self.masks["val"]) \
            + tuple(state) + tuple(fs)
        return fn(*args)

    def _phase1_stacked(self, pparams, popt, batches, global_params, budgets):
        def one_iter(carry, xs):
            i, b_it = xs
            pp, po = carry
            # masked variable-length scan: partition p trains while i < its
            # budget, rides through bitwise-frozen afterwards
            pp, po, losses = self._pstep(pp, po, b_it, global_params,
                                         i < budgets)
            return (pp, po), losses

        iters = jax.tree_util.tree_leaves(batches)[0].shape[0]
        (pparams, popt), losses = jax.lax.scan(
            one_iter, (pparams, popt), (jnp.arange(iters), batches))
        return pparams, popt, losses

    def _async_partition_program(self, global_params, i_run: int):
        """ONE partition's async epoch: mini-epoch draw, per-iteration batch
        materialisation, masked training scan.  The SINGLE body both modes
        execute — stacked vmaps it, spmd runs it per shard — so the PRNG
        consumption order (and with it stacked/spmd bit-parity) cannot
        drift between them."""
        ds = self._device_sampler
        pstep1 = make_personalize_partition_step(self.loss_fn, self.optimizer,
                                                 self.hp)

        def per_part(pp, po, key, budget, logp_row, train_row, k_row, *fs):
            ck = {"cold": fs[0]} if fs else {}
            kd, ke = jax.random.split(key)
            nodes, valid = ds.draw_epoch(kd, logp_row, train_row, k_row)
            iter_keys = jax.random.split(ke, ds.num_batches)

            def one(carry, xs):
                i, n_i, v_i, k_i = xs
                p, o = carry
                batch = ds.make_batch(k_i, n_i, v_i, **ck)
                p, o, l = pstep1(p, o, batch, global_params, i < budget)
                return (p, o), l

            (pp, po), losses = jax.lax.scan(
                one, (pp, po),
                (jnp.arange(i_run), nodes[:i_run], valid[:i_run],
                 iter_keys[:i_run]))
            return pp, po, losses

        return per_part

    def _phase1_async_stacked(self, pparams, popt, keys, budgets,
                              global_params, i_run: int, fs=()):
        ds = self._device_sampler
        per_part = self._async_partition_program(global_params, i_run)
        pparams, popt, losses = jax.vmap(
            per_part, in_axes=(0, 0, 0, 0, 0, 0, 0)
            + (None,) * len(fs))(
                pparams, popt, keys, budgets,
                ds.logp, ds.train_idx, ds.k, *fs)
        return pparams, popt, losses.T              # (i_run, P)

    # --------------------------------------------------- spmd (mesh) mode
    def _phase0_spmd(self, params, opt_state, batches, grad_res=None):
        g_reduce = self._grad_reduce_shard()

        if self.grad_compress == "topk":
            def shard_fn_t(params, opt_state, b_s, res_s):
                b = jax.tree.map(lambda x: x[:, 0], b_s)   # (I, ...)

                def one(carry, bi):
                    p, o, r = carry
                    loss, grads = jax.value_and_grad(self.loss_fn)(p, bi)
                    grads, r = g_reduce(grads, r)
                    updates, o = self.optimizer.update(grads, o, p)
                    return (apply_updates(p, updates), o, r), loss

                (params, opt_state, res), losses = jax.lax.scan(
                    one, (params, opt_state, res_s[0]), b)
                return params, opt_state, losses[:, None], res[None]

            fn = jax.shard_map(
                shard_fn_t, mesh=self._mesh,
                in_specs=(P(), P(), P(None, AXIS), P(AXIS)),
                out_specs=(P(), P(), P(None, AXIS), P(AXIS)), check_vma=False)
            return fn(params, opt_state, batches, grad_res)

        # like make_generalize_step(axis_names=(AXIS,)) but reporting the
        # LOCAL loss: the stacked/sequential paths record per-host losses, so
        # the engine's (I, P) loss matrix must stay per-host for parity
        def gen_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
            grads = g_reduce(grads)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, loss

        def shard_fn(params, opt_state, b_s):
            b = jax.tree.map(lambda x: x[:, 0], b_s)       # (I, ...)

            def one(carry, bi):
                p, o = carry
                p, o, l = gen_step(p, o, bi)
                return (p, o), l

            (params, opt_state), losses = jax.lax.scan(one, (params, opt_state), b)
            return params, opt_state, losses[:, None]

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(), P(), P(None, AXIS)),
            out_specs=(P(), P(), P(None, AXIS)), check_vma=False)
        return fn(params, opt_state, batches)

    def _phase1_spmd(self, pparams, popt, batches, global_params, budgets):
        pstep1 = make_personalize_partition_step(self.loss_fn, self.optimizer,
                                                 self.hp)

        def shard_fn(pp_s, po_s, b_s, gp, bud_s):
            pp = jax.tree.map(lambda x: x[0], pp_s)
            po = jax.tree.map(lambda x: x[0], po_s)
            b = jax.tree.map(lambda x: x[:, 0], b_s)
            bud = bud_s[0]
            iters = jax.tree_util.tree_leaves(b)[0].shape[0]

            def one(carry, xs):
                i, bi = xs
                p, o = carry
                p, o, l = pstep1(p, o, bi, gp, i < bud)
                return (p, o), l

            (pp, po), losses = jax.lax.scan(one, (pp, po),
                                            (jnp.arange(iters), b))
            return (jax.tree.map(lambda x: x[None], pp),
                    jax.tree.map(lambda x: x[None], po),
                    losses[:, None])

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(AXIS), P(AXIS), P(None, AXIS), P(), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS), P(None, AXIS)), check_vma=False)
        return fn(pparams, popt, batches, global_params, budgets)

    def _phase1_async_spmd(self, pparams, popt, keys, budgets, global_params,
                           i_run: int, fs=()):
        ds = self._device_sampler

        def shard_fn(pp_s, po_s, key_s, bud_s, gp, logp_s, train_s, k_s,
                     *fs_s):
            per_part = self._async_partition_program(gp, i_run)
            pp = jax.tree.map(lambda x: x[0], pp_s)
            po = jax.tree.map(lambda x: x[0], po_s)
            pp, po, losses = per_part(pp, po, key_s[0], bud_s[0],
                                      logp_s[0], train_s[0], k_s[0], *fs_s)
            return (jax.tree.map(lambda x: x[None], pp),
                    jax.tree.map(lambda x: x[None], po),
                    losses[:, None])

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(),
                      P(AXIS), P(AXIS), P(AXIS)) + (P(),) * len(fs),
            out_specs=(P(AXIS), P(AXIS), P(None, AXIS)), check_vma=False)
        return fn(pparams, popt, keys, budgets, global_params,
                  ds.logp, ds.train_idx, ds.k, *fs)

    def _eval_spmd(self, params, split: str, per_partition_params: bool,
                   fs=(), trim=None):
        fwd, shards = self._eval_fwd(trim)

        def shard_fn(prm, shard_s, labels_s, mask_s, *cold_s):
            p = jax.tree.map(lambda x: x[0], prm) if per_partition_params else prm
            sh = jax.tree.map(lambda x: x[0], shard_s)
            if cold_s:
                sh = self._featurize(sh, cold_s[0][0])
            preds = self._split_preds(fwd(p, sh), mask_s[0], trim)
            micro = self._micro_of(preds, labels_s[0], mask_s[0])
            return micro[None], preds[None]

        fn = jax.shard_map(
            shard_fn, mesh=self._mesh,
            in_specs=(P(AXIS) if per_partition_params else P(),
                      P(AXIS), P(AXIS), P(AXIS)) + (P(AXIS),) * len(fs),
            out_specs=(P(AXIS), P(AXIS)), check_vma=False)
        return fn(params, shards, self.labels, self.masks[split], *fs)

    # ------------------------------------------------------- public surface
    # Epoch methods return a trailing ``device_seconds``: wall time of the
    # compiled TRAIN scan only.  The validation forward is a separately
    # compiled (still internally fused: halo all_to_all + aggregation +
    # on-device F1) call whose cost is identical across sampler/partition
    # ablations, so excluding it — like the original per-batch driver did —
    # keeps epoch-time comparisons about training.  AOT compilation happens
    # outside every timed window.

    def _timed(self, fn, *args):
        """Call a :meth:`_compiled` program and wait for its outputs
        (``eat.wait/<program>``); returns them and the seconds taken."""
        import time

        t0 = time.perf_counter()
        out = fn(*args)
        with TraceAnnotation(f"eat.wait/{fn.program}"):
            jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    def _needs_sampled_loss(self, spelling: str) -> None:
        """Refuse a sampled-batch program where the model has no sampled
        loss (GAT: the engine is built with ``loss_fn=None``)."""
        if self.loss_fn is None:
            raise ValueError(
                f"{spelling} trains on sampled mini-batches; "
                f"{type(self.model).__name__} has no sampled loss and trains "
                f"through phase0_fullgraph_epoch only")

    def phase0_epoch(self, params, opt_state, batches):
        self._needs_sampled_loss("phase0_epoch")
        impl = self._phase0_spmd if self.mode == "spmd" else self._phase0_stacked
        if self.grad_compress == "topk":
            res = self._grad_residual(params)
            fn = self._compiled("phase0", impl, params, opt_state, batches,
                                res)
            (params, opt_state, losses, new_res), dt = self._timed(
                fn, params, opt_state, batches, res)
            self._grad_res = new_res
        else:
            fn = self._compiled("phase0", impl, params, opt_state, batches)
            (params, opt_state, losses), dt = self._timed(
                fn, params, opt_state, batches)
        val_micro, _ = self.evaluate(params, "val", per_partition_params=False)
        return params, opt_state, losses, val_micro, dt

    def phase0_epoch_async(self, params, opt_state, keys):
        """One fused generalization epoch: the on-device epoch draw (uniform
        shuffle of the local train set, or the CBS mini-epoch when the
        attached sampler is class-balanced), batch materialisation, the
        synchronous train scan with the cross-partition gradient mean, AND
        the validation eval forward — all in ONE compiled device program, so
        an epoch costs one host→device round-trip instead of shipping
        ``iters`` host-built batches plus a separate eval call.

        ``keys`` is (P, 2) uint32 per-partition PRNG state (fold the epoch
        index into a per-partition base key).  Unlike phase-1 there are no
        budgets: generalization is synchronous data-parallel SGD, every
        partition scans all ``num_batches`` iterations.  Returns
        ``(params, opt_state, losses (I, P), val_micro (P,), device_seconds)``
        where the timing, unlike :meth:`phase0_epoch`, INCLUDES the fused
        eval (it is part of the one device call; the pipeline's epoch-time
        attribution accounts for that).
        """
        self._needs_sampled_loss("phase0_epoch_async")
        if self._device_sampler is None:
            raise ValueError("phase0_epoch_async needs set_device_sampler()")
        if self.config.feat_groups:
            raise ValueError(
                "feat_groups streams the eval forward on the host; the "
                "fused async epoch is one device program — run the host-"
                "batch phase-0 path (async_generalize=False) when streaming")
        base = (self._phase0_async_spmd if self.mode == "spmd"
                else self._phase0_async_stacked)
        comp = self.halo_compress != "none"
        topk = self.grad_compress == "topk"
        plan = self._halo_plan() if self.halo_cache else None
        # carried state, in the partition program's fixed order
        state = ()
        if plan is not None:
            state += (self._halo_state,)
        if comp:
            state += (self._halo_residual,)
        if topk:
            state += (self._grad_residual(params),)
        # staged cold rows (feature store): the sampler's global cold tier
        # feeds the batch gathers, the shard cold tier feeds the fused eval
        fs = ((self._stage_sampler_cold(), self._stage_cold())
              if self.feat_store else ())
        if state or fs:
            n_st = len(state)
            impl = lambda p, o, k, *st: base(p, o, k, st[:n_st], plan,
                                             st[n_st:])
            name = f"phase0_async-g{self._sampler_gen}"
            if plan is not None:
                name += f"-c{plan[0]}-{plan[1]}"
            fn = self._compiled(name, impl, params, opt_state, keys,
                                *state, *fs)
            out, dt = self._timed(fn, params, opt_state, keys, *state, *fs)
            params, opt_state, losses, val_micro = out[:4]
            rest = list(out[4:])
            if plan is not None:
                self._halo_tick(plan, rest.pop(0))
            if comp:
                self._halo_residual = rest.pop(0)
                if plan is None:
                    self.last_halo_exchange_bytes = (
                        self.model.num_layers * self.halo_wire_bytes_per_layer)
            if topk:
                self._grad_res = rest.pop(0)
        else:
            fn = self._compiled(f"phase0_async-g{self._sampler_gen}", base,
                                params, opt_state, keys)
            (params, opt_state, losses, val_micro), dt = self._timed(
                fn, params, opt_state, keys)
        self.last_eval_seconds = 0.0    # eval is inside dt on this path
        return params, opt_state, losses, val_micro, dt

    def phase0_fullgraph_epoch(self, params, opt_state, iters: int = 1):
        """Full-graph phase-0 epoch: ``iters`` full-batch steps whose
        ``value_and_grad`` runs straight through the distributed forward —
        per-layer halo exchange, the differentiable Pallas aggregation op
        (forward AND transpose kernels on the traced path when
        ``use_pallas_agg=True``) and the cross-partition gradient mean.  The
        centralized (P=1) configuration is the paper's Table IV baseline at
        full-graph scale; P>1 is per-partition full-graph training."""
        if self.feat_store:
            raise ValueError(
                "full-graph training differentiates through the resident "
                "feature stack on every iteration; the feature store "
                "serves features per compiled call — run full_graph_train "
                "all-resident")
        if self.halo_cache:
            raise ValueError(
                "halo_cache is an eval-forward optimisation; full-graph "
                "training differentiates through the live halo exchange "
                "and cannot train against stale cached embeddings")
        if self.grad_compress == "topk":
            raise ValueError(
                "top-k gradient sparsification is a sampled phase-0 feature; "
                "full-graph training keeps the exact (or bucketed) all-reduce")
        impl = (self._phase0_fullgraph_spmd if self.mode == "spmd"
                else self._phase0_fullgraph_stacked)
        fn = self._compiled(f"phase0_fg-{iters}",
                            lambda p, o: impl(p, o, iters), params, opt_state)
        (params, opt_state, losses), dt = self._timed(fn, params, opt_state)
        val_micro, _ = self.evaluate(params, "val", per_partition_params=False)
        return params, opt_state, losses, val_micro, dt

    @staticmethod
    def _as_budgets(active_or_budgets, iters: int):
        """Phase-1 gating is expressed as per-partition iteration BUDGETS;
        a bool `active` vector (the pre-async API) means full-epoch-or-zero."""
        b = jnp.asarray(active_or_budgets)
        if b.dtype == jnp.bool_:
            b = jnp.where(b, iters, 0)
        return b.astype(jnp.int32)

    def phase1_epoch(self, pparams, popt, batches, global_params, budgets):
        self._needs_sampled_loss("phase1_epoch")
        iters = jax.tree_util.tree_leaves(batches)[0].shape[0]
        budgets = self._as_budgets(budgets, iters)
        impl = self._phase1_spmd if self.mode == "spmd" else self._phase1_stacked
        fn = self._compiled("phase1", impl, pparams, popt, batches,
                            global_params, budgets)
        (pparams, popt, losses), dt = self._timed(
            fn, pparams, popt, batches, global_params, budgets)
        val_micro, _ = self.evaluate(pparams, "val", per_partition_params=True)
        return pparams, popt, losses, val_micro, dt

    # ----------------------------------------------- async personalization
    def set_device_sampler(self, sampler) -> None:
        """Attach a :class:`DeviceEpochSampler`; required by
        :meth:`phase0_epoch_async` and :meth:`phase1_epoch_async` (the
        fully-on-device epoch paths)."""
        if self.feat_store != (getattr(sampler, "cold_host", None)
                               is not None):
            raise ValueError(
                "feat-store mismatch: the engine and its device sampler "
                "must agree — build the sampler with feat_store matching "
                "EngineConfig.feat_store")
        self._device_sampler = sampler
        # the sampler's arrays are baked into the async trace as constants,
        # so a new sampler must never hit an old executable (shapes alone
        # can't distinguish two same-sized graphs) — and the superseded
        # executables pin those arrays in device memory, so evict them
        self._sampler_gen += 1
        self._cache = {k: v for k, v in self._cache.items()
                       if not str(k[0]).startswith(("phase0_async-",
                                                    "phase1_async-"))}

    def phase1_epoch_async(self, pparams, popt, keys, budgets, global_params):
        """One asynchronous personalization step: mini-epoch resample, batch
        shuffle, fanout sampling, feature gather AND the masked training scan
        all inside ONE device program — no host NumPy on the mini-epoch path.

        ``keys`` is (P, 2) uint32 per-partition PRNG state; ``budgets`` (P,)
        int32 from :meth:`GPController.phase1_budgets`.  The scan's static
        trip count is max(budgets) rounded up to a power of two (bounding
        recompiles to log2(I) shapes), so converged partitions stop paying
        for the stragglers' full epochs.
        """
        self._needs_sampled_loss("phase1_epoch_async")
        if self._device_sampler is None:
            raise ValueError("phase1_epoch_async needs set_device_sampler()")
        budgets = self._as_budgets(budgets, self._device_sampler.num_batches)
        cap = self._device_sampler.num_batches
        need = int(np.asarray(budgets).max())
        i_run = 1
        while i_run < min(need, cap):
            i_run *= 2
        i_run = min(i_run, cap)
        impl = (self._phase1_async_spmd if self.mode == "spmd"
                else self._phase1_async_stacked)
        # the phase-1 scan only gathers batch features (no fused eval), so
        # the feature store stages just the sampler's cold tier here
        fs = (self._stage_sampler_cold(),) if self.feat_store else ()
        fn = self._compiled(
            f"phase1_async-{i_run}-g{self._sampler_gen}",
            lambda pp, po, k, b, gp, *c: impl(pp, po, k, b, gp, i_run, c),
            pparams, popt, keys, budgets, global_params, *fs)
        (pparams, popt, losses), dt = self._timed(
            fn, pparams, popt, keys, budgets, global_params, *fs)
        val_micro, _ = self.evaluate(pparams, "val", per_partition_params=True)
        return pparams, popt, losses, val_micro, dt

    def evaluate(self, params, split: str = "test",
                 per_partition_params: bool = True):
        """``(micro (P,), preds (P, maxN))`` of ``split``: each partition's
        micro-F1 over its ``split`` rows, and each row's class.  Read
        ``preds`` on the split's rows only: where the last layer runs over
        the split's in-edges alone (GraphSAGE's mean on the plain Pallas
        forward, :meth:`_eval_trim`), every other row reads -1.  Sets
        ``last_eval_agg_slots``."""
        trim, slots = self._eval_trim(split)
        self.last_eval_agg_slots = (slots, self._agg_slots)
        if self.config.feat_groups:
            return self._evaluate_streamed(params, split,
                                           per_partition_params)
        comp = self.halo_compress != "none"
        fs = self._fs_args()
        if self.halo_cache:
            # the refresh slot range is a static host-side plan, so every
            # plan gets its own executable (the pure-cached one has no
            # collective at all); the cache rides through as carried state,
            # and under halo_compress so does the quantization residual
            plan = self._halo_plan()
            res = (self._halo_residual,) if comp else ()
            if self.mode == "spmd":
                impl = lambda prm, c, *r: self._eval_spmd_cached(
                    prm, c, split, per_partition_params, plan,
                    *r[:len(res)], fs=r[len(res):])
            else:
                impl = lambda prm, c, *r: self._eval_stacked_cached(
                    prm, c, split, per_partition_params, plan,
                    *r[:len(res)], fs=r[len(res):])
            fn = self._compiled(
                f"eval-{split}-{per_partition_params}-c{plan[0]}-{plan[1]}",
                impl, params, self._halo_state, *res, *fs)
            out, self.last_eval_seconds = self._timed(
                fn, params, self._halo_state, *res, *fs)
            if comp:
                micro, preds, new_state, new_res = out
                self._halo_residual = new_res
            else:
                micro, preds, new_state = out
            self._halo_tick(plan, new_state)
            return micro, preds
        if comp:
            if self.mode == "spmd":
                impl = lambda prm, r, *c: self._eval_spmd_comp(
                    prm, r, split, per_partition_params, fs=c)
            else:
                impl = lambda prm, r, *c: self._eval_stacked_comp(
                    prm, r, split, per_partition_params, fs=c)
            fn = self._compiled(f"eval-{split}-{per_partition_params}",
                                impl, params, self._halo_residual, *fs)
            (micro, preds, new_res), self.last_eval_seconds = self._timed(
                fn, params, self._halo_residual, *fs)
            self._halo_residual = new_res
            self.last_halo_exchange_bytes = (self.model.num_layers
                                             * self.halo_wire_bytes_per_layer)
            return micro, preds
        impl_of = (self._eval_spmd if self.mode == "spmd"
                   else self._eval_stacked)
        if trim is None:
            impl = lambda prm, *c: impl_of(prm, split, per_partition_params,
                                           fs=c)
            args = (params, *fs)
        else:
            impl = lambda prm, t, *c: impl_of(prm, split, per_partition_params,
                                              fs=c, trim=t)
            args = (params, trim, *fs)
        fn = self._compiled(f"eval-{split}-{per_partition_params}", impl,
                            *args)
        # execution time of the compiled eval (AOT compile excluded), so the
        # pipeline can compare host-path epochs, whose eval is a separate
        # call, against the fused async epoch whose timing includes eval
        out, self.last_eval_seconds = self._timed(fn, *args)
        return out

    def _evaluate_streamed(self, params, split: str,
                           per_partition_params: bool):
        """Partition-group streaming eval (DESIGN.md §12): host-orchestrated
        eager forward over groups of ``feat_groups`` partitions, so at most
        G assembled feature planes exist at once — the bigger-than-device
        path.  Op-for-op the sequential reference forward, hence bitwise
        locked against it in tests/test_engine_parity.py."""
        import time

        from .streaming import StreamedEvaluator

        if self._streamer is None:
            self._streamer = StreamedEvaluator(self)
        t0 = time.perf_counter()
        micro, preds, cold_bytes = self._streamer.evaluate(
            params, split, per_partition_params)
        jax.block_until_ready((micro, preds))
        self.cold_h2d_bytes += cold_bytes
        self.last_eval_seconds = time.perf_counter() - t0
        return micro, preds

    def export_serving_state(self, params) -> dict:
        """One full-refresh forward materializing the serving handoff
        (DESIGN.md §9): ``{"layers": [(P, maxN, D_i) per layer],
        "logits": (P, maxN, C), "cache": {"h{i}": (P, P, maxS, D_i)}}``
        as host numpy arrays.  The logits are bit-for-bit ``evaluate()``'s
        forward (same spelling), the cache is the recv-layout snapshot a
        full-refresh cached forward would have written — when the engine
        runs with ``halo_cache`` the freshly exchanged buffers are handed
        back to it, so the export doubles as a cache refresh.

        Global (replicated) params only; the overlap forward never
        materializes post-exchange layer inputs, so build the engine
        without ``overlap_halo`` to serve from it.
        """
        if self.config.overlap_halo:
            raise ValueError(
                "export_serving_state needs the combined-edge forward; "
                "build the engine without overlap_halo")
        shards = self.shards
        if self.feat_store:
            # the export forward wants the resident plane; reconstruct it
            # host-side (bitwise the all-resident stack) and hand it in as
            # the call argument — a one-shot transfer for the serving
            # handoff, not part of the per-epoch cold-row accounting
            shards = {k: v for k, v in self.shards.items()
                      if not k.startswith("fs_")}
            shards["features"] = jnp.asarray(
                reconstruct_features(self._fs, self.max_nodes),
                self.config.dtype)
        fwd_e = make_export_forward(self.model, self._fwd_meta,
                                    axis_name=AXIS, agg=self._mean_agg)
        if self.mode == "spmd":
            def shard_fn(prm, shard_s):
                sh = jax.tree.map(lambda x: x[0], shard_s)
                return jax.tree.map(lambda x: x[None], fwd_e(prm, sh))
            L = self.model.num_layers
            out_specs = {"layers": tuple(P(AXIS) for _ in range(L)),
                         "logits": P(AXIS),
                         "cache": {f"h{i}": P(AXIS) for i in range(L)}}
            impl = jax.shard_map(shard_fn, mesh=self._mesh,
                                 in_specs=(P(), P(AXIS)),
                                 out_specs=out_specs, check_vma=False)
        else:
            impl = jax.vmap(fwd_e, axis_name=AXIS, in_axes=(None, 0))
        fn = self._compiled("export_serving", impl, params, shards)
        out = fn(params, shards)
        if self.halo_cache:
            # the snapshot is exactly a full refresh: hand it to the cache
            self._halo_state = jax.tree.map(
                lambda x: x.astype(self.config.dtype), out["cache"])
        return jax.tree.map(np.asarray, out)
