"""EAT-DistGNN pipeline: EW partitioning → CBS sampling → GP training.

This is the paper's full experimental loop (the engine behind Tables II–V
and Fig. 3) over N logical compute hosts.  Since PR 1 the per-partition
Python loop is gone: every epoch executes as two fused steps through
``repro.engine.SPMDEngine`` (DESIGN.md §3) — one jitted trace scans all
training iterations with the cross-partition gradient mean, a second runs
the full-graph validation forward with its per-layer halo ``all_to_all``
and the Pallas ``segment_agg`` aggregation.  On a multi-device host the
same per-shard program runs under ``shard_map`` over a partition mesh; on
one CPU it runs under ``vmap`` with identical collective semantics;
``engine_mode="sequential"`` keeps the legible Python-loop reference (the
parity oracle of tests/test_engine_parity.py).

Faithfulness notes:

  · Phase-0 is synchronous data-parallel SGD: per host gradients on its own
    batch, averaged each iteration (the all-reduce), identical updates.
  · The personalization trigger is loss-curve flattening (Fig. 3 magenta).
  · Phase-1 stops aggregating; each host descends its local loss + the
    Eq. 4 prox term, with per-host early stopping and per-host best models.
  · Evaluation (phase-1 validation and the final test) runs through the
    DISTRIBUTED forward: boundary nodes aggregate halo embeddings computed
    under the OWNING partition's personalized model — the semantics a real
    deployment has, and a deliberate change from the pre-engine driver,
    which evaluated each host's model solo over the whole graph.
  · CBS mini-epochs resample 25% of the host's training nodes by Eq. 3.
  · ``full_graph_train=True`` replaces phase-0's sampled minibatches with
    full-batch ``value_and_grad`` straight through the distributed forward
    (halo exchange + the differentiable blocked aggregation op, DESIGN.md
    §6); with ``centralized=True`` this is the Table IV baseline trained at
    full-graph scale on the kernel path.
  · ``async_personalize=True`` makes phase-1 genuinely asynchronous: each
    partition gets its own iteration budget from GPController (masked
    variable-length scan), and the mini-epoch draw itself moves on-device
    (core/sampler/cbs_device.py) so no host NumPy runs on that path;
    DESIGN.md §4 defines what "epoch" means when budgets differ.
  · ``async_generalize=True`` moves phase-0's epoch draw on-device too
    (the same DeviceEpochSampler: CBS-weighted mini-epochs, or a uniform
    shuffle of the local train set without CBS) and fuses the train scan
    WITH the validation eval forward into one compiled call, so a
    generalization epoch is one host→device round-trip — no host NumPy
    draw and no ``_EpochPrefetcher`` on that path (DESIGN.md §7).
    ``full_graph_train`` supersedes it (full-graph phase-0 has no sampling).
  · Host-side sampling (where it remains) is double-buffered: epoch t+1's
    draw overlaps epoch t's fused device step.  The prefetcher is created
    lazily, on the first epoch that actually samples on the host.
  · Sampling may cross partition boundaries exactly like DistDGL's remote
    neighbour fetch; comm_halo_bytes accounts BOTH that sampled remote-fetch
    volume (cut_fraction-scaled, per training epoch) and the eval forward's
    per-layer halo all_to_all volume (PartitionedGraph.halo_bytes_per_layer).
  · "Distributed" timing on one CPU is reported as the paper measures it:
    per-epoch time = max over hosts of (host-side sampling time + an equal
    1/N share of the fused TRAIN scan), synchronous phases waiting for the
    slowest host; phase-1 accumulates per-host time only while that host is
    active.  Validation-forward time is excluded, as in the original
    per-batch driver, so epoch-time ablations compare training work.  Communication is additionally reported in bytes (gradient +
    halo traffic), since wall-clock network time cannot be measured honestly
    in a single-process simulation.  XLA compilation is excluded (the engine
    AOT-compiles each epoch shape before the timed call).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .core import (GPController, GPHyperParams, GPScheduleConfig,
                   broadcast_to_partitions, partition_graph)
from .core.gp.trainer import grad_sync_wire_bytes
from .core.sampler import (CBSampler, build_device_epoch_sampler,
                           host_draw_count)
from .engine import (EngineConfig, make_engine, stack_epoch_batches,
                     stack_pytrees)
from .graph import (BENCHMARKS, GAT, GraphSAGE, NeighborSampler,
                    build_partitioned_graph, make_benchmark)
from .robustness import FaultPlan, InjectedCrash, RunCheckpointer
from .train.metrics import F1Report, f1_scores
from .train.optim import AdamW

__all__ = ["EATConfig", "EATResult", "run_eat_distgnn"]


@dataclass(frozen=True)
class EATConfig:
    dataset: str = "products-s"
    num_parts: int = 4
    partition_method: str = "ew"          # random | metis | ew | ew_balanced
    use_cbs: bool = True
    use_gp: bool = True
    use_focal: bool = False
    max_epochs: int = 40
    # the model: "sage" (GraphSAGE, mean aggregator) or "gat" (GAT with the
    # paper's PPI heads (4, 4, 6)), which trains on the full-graph path only
    # (full_graph_train) and runs no GP phase 1: personalization trains on
    # sampled mini-batches, so use_gp is taken as False
    arch: str = "sage"
    # GraphSAGE's hidden width; GAT's per-head width (PPI: 256)
    hidden_dim: int = 128
    batch_size: int = 256
    fanouts: tuple[int, int] = (10, 10)
    lr: float = 1e-3
    lambda_prox: float = 0.01
    subset_fraction: float = 0.25
    flatten_tol: float = 0.02
    # hard phase split: fraction of max_epochs spent generalizing (the
    # paper's "parameter controls the proportion"); None = loss-driven
    # trigger, except async runs default to 0.4 so personalization — the
    # phase async exists for — is reached even under tiny epoch budgets
    phase0_fraction: float | None = None
    seed: int = 0
    centralized: bool = False             # 1 host, no partitioning (Table IV)
    engine_mode: str = "auto"             # auto | spmd | stacked | sequential
    use_pallas_agg: bool = True           # Pallas segment_agg on the eval path
    # boundary/interior split forward: overlap each layer's halo exchange
    # with interior aggregation + the self-term matmul (DESIGN.md §5)
    overlap_halo: bool = False
    ring_chunks: int = 0                  # chunked ppermute ring (0 = all_to_all)
    # historical-embedding halo cache (DESIGN.md §8): eval forwards aggregate
    # against the last-received boundary embeddings; only every
    # halo_refresh_every-th forward pays the full exchange, and halo_cv
    # refreshes a rotating slot chunk in between (VR-GCN control variate)
    halo_cache: bool = False
    halo_refresh_every: int = 4
    halo_cv: bool = False
    # compressed communication (DESIGN.md §11): quantized halo exchange on
    # the eval forwards (error-compensated; composes with the halo cache and
    # either exchange schedule) and the phase-0 gradient all-reduce spelling
    halo_compress: str = "none"           # none | fp16 | int8
    grad_compress: str = "none"           # none | bucketed | topk
    grad_topk_frac: float = 0.01          # fraction of entries top-k ships
    grad_bucket_kb: int = 512             # bucketed psum slice size
    # phase-0 trains FULL-GRAPH instead of sampled minibatches: one (or
    # ``full_graph_iters``) full-batch value_and_grad step(s) per epoch
    # straight through the distributed forward — halo exchange and the
    # differentiable blocked aggregation op (custom VJP; DESIGN.md §6).
    # With ``centralized=True`` this is the paper's Table IV baseline
    # trained at full-graph scale on the MXU path.
    full_graph_train: bool = False
    full_graph_iters: int = 1             # full-batch steps per phase-0 epoch
    # phase-1 runs fully on device: per-partition iteration budgets + the CBS
    # mini-epoch draw / fanout sampling / feature gather on the epoch trace
    # (no host NumPy on the mini-epoch path; DESIGN.md §4)
    async_personalize: bool = False
    # phase-0 runs fully on device too: the epoch draw (CBS mini-epoch, or a
    # uniform train-set shuffle without CBS) plus the train scan plus the
    # fused validation eval, all in ONE device program per epoch — no host
    # prefetcher on this path (DESIGN.md §7; superseded by full_graph_train)
    async_generalize: bool = False
    # overlap host-side sampling of epoch t+1 with the device step of epoch t
    double_buffer: bool = True
    # fault tolerance (DESIGN.md §10): checkpoint_dir arms epoch-granular
    # checkpointing through RunCheckpointer (atomic archives + checksummed
    # manifest, last keep_checkpoints retained); resume=True restores the
    # newest valid checkpoint and continues such that final params and val
    # micro-F1 are bit-for-bit the uninterrupted run's
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    keep_checkpoints: int = 3
    resume: bool = False
    # two-tier feature store (DESIGN.md §12): keep the top hot_frac of each
    # partition's feature rows (by hot_policy score) resident on device and
    # stage the cold remainder from host numpy per compiled call; the device
    # sampler's gather table splits the same way.  feat_groups > 0 streams
    # the eval over G-partition groups (stacked mode only) so a feature
    # matrix bigger than the stacked plane still evaluates; feat_budget_mb
    # makes the engine refuse to build when peak device feature bytes
    # exceed the budget (<= 0 disables)
    feat_store: bool = False
    hot_frac: float = 0.5
    hot_policy: str = "degree"            # degree | freq
    feat_groups: int = 0
    feat_budget_mb: float = 0.0
    # float dtype of the feature/mask path ("float32" | "float64"); float64
    # needs jax_enable_x64 and is what the fp64 resume-parity oracles run
    dtype: str = "float32"


@dataclass
class EATResult:
    config: EATConfig
    f1: F1Report                       # pooled test predictions
    per_partition_micro: np.ndarray
    partition_entropies: np.ndarray
    partition_time_s: float
    weight_time_s: float
    train_time_s: float                # simulated distributed wall time
    epoch_time_s: float                # mean per-epoch (phase-0), eval excluded
                                       # where eval is a separate call
    epochs_run: int
    personalize_start_epoch: int
    loss_history: list[float] = field(default_factory=list)
    val_history: list[float] = field(default_factory=list)
    comm_grad_bytes: int = 0
    comm_halo_bytes: int = 0
    # per-phase communication volume (bytes moved, not just seconds):
    # gradient all-reduce traffic is phase-0 only; halo/remote-fetch
    # traffic is attributed to the phase whose epochs incurred it
    comm_halo_bytes_phase0: int = 0
    comm_halo_bytes_phase1: int = 0
    halo_bytes_per_layer: int = 0      # eval-forward exchange payload/layer
    # eval-forward exchange volume actually paid (sum and per-epoch trace):
    # equals 2 * halo_bytes_per_layer per epoch without the cache, only the
    # refreshed-row payload per epoch with --halo-cache
    comm_halo_exchange_bytes: int = 0
    halo_exchange_history: list[int] = field(default_factory=list)
    engine_mode: str = "stacked"
    phase1_time_s: float = 0.0         # slowest host's cumulative phase-1 time
    phase1_epochs: int = 0
    host_draws_phase1: int = 0         # host NumPy mini-epoch draws in phase-1
                                       # (0 under async_personalize)
    host_draws_phase0: int = 0         # host NumPy epoch draws in phase-0
                                       # (0 under async_generalize)
    # per-epoch TRAIN iteration counts in phase-0 — the deterministic
    # work-based witness that CBS mini-epochs shorten the epoch (the
    # wall-clock claim's machine-load-independent proxy)
    phase0_iter_history: list[int] = field(default_factory=list)
    # TOTAL host→device payload across all phase-0 epochs: stacked batch
    # arrays on the host-sampled path (the batch's bytes, also where its
    # rows were gathered on the device), just the (P, 2) PRNG keys per
    # epoch on the async path (divide by epochs for the per-epoch payload) —
    # plus, under the feature store, the cold rows staged for phase-0's
    # compiled calls (train gathers and the per-epoch validation eval)
    host_to_device_bytes_phase0: int = 0
    # phase-1's cold-row staging traffic (async epoch gathers, per-epoch
    # val evals AND the final test eval); 0 without the feature store
    host_to_device_bytes_phase1: int = 0
    # device-resident feature bytes (engine plane/hot tier + attached
    # sampler table) — the footprint the feature store shrinks
    resident_feature_bytes: int = 0
    # total cold-row host->device staging bytes (both phases)
    cold_h2d_bytes: int = 0
    # mean phase-0 epoch period INCLUDING the validation eval's 1/N share —
    # the apples-to-apples number against the fused async epoch, whose one
    # device call is inseparable from its eval (epoch_time_s excludes eval
    # wherever eval is a separately-compiled call)
    epoch_time_with_eval_s: float = 0.0
    # the stacked per-partition params the final test eval ran with — the
    # bit-for-bit witness the kill-and-resume parity tests compare
    final_params: Any = None
    # epoch the run resumed from (-1 = fresh start)
    resumed_from_epoch: int = -1
    # total injected straggler delay (max over hosts per epoch, summed)
    straggler_delay_s: float = 0.0
    # per-epoch device seconds of the epoch's compiled train call (both
    # phases, block_until_ready-timed, compilation excluded)
    epoch_device_s: list[float] = field(default_factory=list)

    def summary(self) -> dict:
        return {
            "dataset": self.config.dataset,
            "arch": self.config.arch,
            "method": self._label(),
            "parts": self.config.num_parts,
            "engine": self.engine_mode,
            "micro_f1": round(self.f1.micro * 100, 2),
            "macro_f1": round(self.f1.macro * 100, 2),
            "weighted_f1": round(self.f1.weighted * 100, 2),
            "train_time_s": round(self.train_time_s, 2),
            "epoch_time_s": round(self.epoch_time_s, 3),
            "epoch_time_with_eval_s": round(self.epoch_time_with_eval_s, 4),
            "epochs": self.epochs_run,
            "personalize_start": self.personalize_start_epoch,
            "avg_entropy": round(float(self.partition_entropies.mean()), 4),
            "partition_time_s": round(self.partition_time_s, 2),
            "comm_grad_mb": round(self.comm_grad_bytes / 1e6, 1),
            "comm_halo_mb": round(self.comm_halo_bytes / 1e6, 1),
            "comm_halo_phase0_mb": round(self.comm_halo_bytes_phase0 / 1e6, 1),
            "comm_halo_phase1_mb": round(self.comm_halo_bytes_phase1 / 1e6, 1),
            "halo_bytes_per_layer": self.halo_bytes_per_layer,
            "halo_cache": self.config.halo_cache,
            "halo_refresh_every": self.config.halo_refresh_every,
            "halo_cv": self.config.halo_cv,
            "halo_compress": self.config.halo_compress,
            "grad_compress": self.config.grad_compress,
            "comm_halo_exchange_mb": round(
                self.comm_halo_exchange_bytes / 1e6, 3),
            "phase1_time_s": round(self.phase1_time_s, 3),
            "phase1_epochs": self.phase1_epochs,
            "async_personalize": self.config.async_personalize,
            "async_generalize": self.config.async_generalize,
            "overlap_halo": self.config.overlap_halo,
            "full_graph_train": self.config.full_graph_train,
            "phase0_iters_per_epoch": (
                round(float(np.mean(self.phase0_iter_history)), 2)
                if self.phase0_iter_history else 0.0),
            "host_to_device_mb_phase0": round(
                self.host_to_device_bytes_phase0 / 1e6, 3),
            "host_to_device_mb_phase1": round(
                self.host_to_device_bytes_phase1 / 1e6, 3),
            "feat_store": self.config.feat_store,
            "hot_frac": self.config.hot_frac,
            "resident_feature_mb": round(
                self.resident_feature_bytes / 1e6, 3),
            "cold_h2d_mb": round(self.cold_h2d_bytes / 1e6, 3),
            "resumed_from_epoch": self.resumed_from_epoch,
            "straggler_delay_s": round(self.straggler_delay_s, 3),
        }

    def _label(self) -> str:
        c = self.config
        if c.centralized:
            return "Centralized"
        parts = {"random": "RAND", "metis": "METIS", "ew": "EW",
                 "ew_balanced": "EW-BAL"}[c.partition_method]
        mods = [parts]
        if c.use_gp:
            mods.append("GP")
        if c.use_cbs:
            mods.append("CBS")
        return "+".join(mods)


class _EpochPrefetcher:
    """Double-buffered host sampling: draw epoch t+1's batches in a background
    thread while the device executes epoch t's fused step.

    One worker thread at a time, so the samplers' NumPy RNG streams advance
    in exactly the sequential order — results are identical to the
    unbuffered pipeline, only the wall-clock overlaps.

    ``snapshot`` (optional) is called on the MAIN thread immediately before
    each speculative draw starts, so ``last_snapshot`` always holds a
    race-free capture of the sampler RNG states with every draw through the
    last handed-out epoch consumed — the stream position an epoch-boundary
    checkpoint must store for a resumed run to re-draw the next epoch
    identically (DESIGN.md §10).

    ``draw`` returns a tuple whose first item is the epoch's stacked
    ``(iters, P, ...)`` batch pytree, as :func:`stack_epoch_batches` does;
    the worker's ``eat.draw`` span carries its ``batches`` and ``bytes``.
    """

    def __init__(self, draw, snapshot=None):
        self._draw = draw
        self._snapshot = snapshot
        self._pending = None
        self.last_snapshot = None

    def _spawn(self) -> None:
        import threading

        if self._snapshot is not None:
            self.last_snapshot = self._snapshot()
        box = {}

        def work():
            try:
                with TraceAnnotation("eat.draw") as span:
                    box["out"] = self._draw()
                    leaves = jax.tree_util.tree_leaves(box["out"][0])
                    span.set_metadata(
                        batches=leaves[0].shape[0] * leaves[0].shape[1],
                        bytes=sum(x.nbytes for x in leaves))
            except BaseException as e:   # surfaces in next(), not swallowed
                box["err"] = e

        th = threading.Thread(target=work, daemon=True)
        th.start()
        self._pending = (th, box)

    def next(self):
        """Epoch t's batches (waits if still sampling), then immediately
        kicks off epoch t+1's draw so it overlaps the caller's device step."""
        if self._pending is None:
            self._spawn()
        th, box = self._pending
        with TraceAnnotation("eat.draw_wait"):
            th.join()
        if "err" in box:
            raise box["err"]
        self._spawn()
        return box["out"]

    def settle(self) -> None:
        """Wait for any in-flight draw WITHOUT discarding it — quiesces the
        worker so host_draw_count() snapshots are race-free."""
        if self._pending is not None:
            self._pending[0].join()

    def close(self) -> None:
        """Join and discard any in-flight draw (phase transition / shutdown)."""
        if self._pending is not None:
            self._pending[0].join()
            self._pending = None


def run_eat_distgnn(cfg: EATConfig, verbose: bool = False,
                    fault_plan: FaultPlan | None = None) -> EATResult:
    if cfg.arch not in ("sage", "gat"):
        raise ValueError(f"unknown arch {cfg.arch!r} (expected 'sage' or "
                         f"'gat')")
    if cfg.arch == "gat" and not cfg.full_graph_train:
        raise ValueError(
            "arch='gat' has no sampled path: it trains on the full-graph "
            "path only — set full_graph_train")
    if cfg.arch == "gat" and cfg.use_gp:
        # GP's phase 1 personalizes on sampled mini-batches: GAT trains
        # phase 0 alone, to its own early stop
        cfg = replace(cfg, use_gp=False)
        if verbose:
            print("[arch=gat] no GP phase 1 (sampled mini-batches): use_gp "
                  "taken as False")
    if cfg.halo_cache and cfg.full_graph_train:
        raise ValueError(
            "halo_cache is an eval-forward optimisation; full_graph_train "
            "differentiates through the live halo exchange and cannot train "
            "against stale cached embeddings")
    if cfg.feat_store and cfg.full_graph_train:
        raise ValueError(
            "full_graph_train differentiates through the resident feature "
            "stack; the feature store's staged cold tier has no training "
            "spelling — run full-graph training all-resident")
    if cfg.feat_groups and cfg.async_generalize:
        raise ValueError(
            "feat_groups streams the eval host-side, which cannot live "
            "inside the fused async phase-0 program — run the host-batch "
            "phase-0 path (async_generalize=False) when streaming")
    fdt = np.dtype(cfg.dtype)
    graph = make_benchmark(BENCHMARKS[cfg.dataset])
    n_parts = 1 if cfg.centralized else cfg.num_parts

    # ---------------- partitioning (host-side preprocessing, timed) -------
    if cfg.centralized:
        parts = np.zeros(graph.num_nodes, dtype=np.int64)
        p_time = w_time = 0.0
        ents = np.array([0.0])
    else:
        pres = partition_graph(graph.indptr, graph.indices, graph.features,
                               graph.labels, n_parts,
                               method=cfg.partition_method, seed=cfg.seed,
                               fanout_k=cfg.fanouts[0])
        parts = pres.parts
        p_time, w_time = pres.partition_time_s, pres.weight_time_s
        ents = pres.stats.entropies
        if verbose:
            print(f"partition[{cfg.partition_method}] {pres.stats.row()}")

    # ---------------- stacked shards + engine ------------------------------
    pg = build_partitioned_graph(graph, parts, n_parts)
    if cfg.arch == "gat":
        model = GAT(feature_dim=graph.feature_dim,
                    num_classes=graph.num_classes, head_dim=cfg.hidden_dim)
        loss_fn = None      # no sampled loss: the full-graph loss only
    else:
        model = GraphSAGE(feature_dim=graph.feature_dim,
                          hidden_dim=cfg.hidden_dim,
                          num_classes=graph.num_classes)
        loss_fn = model.make_loss_fn(loss="focal" if cfg.use_focal else "ce")
    opt = AdamW(lr=cfg.lr, grad_clip=5.0)
    engine = make_engine(
        model, loss_fn, opt, pg,
        hp=GPHyperParams(lambda_prox=cfg.lambda_prox),
        config=EngineConfig(mode=cfg.engine_mode,
                            use_pallas_agg=cfg.use_pallas_agg,
                            dtype=fdt,
                            overlap_halo=cfg.overlap_halo,
                            ring_chunks=cfg.ring_chunks,
                            fg_loss="focal" if cfg.use_focal else "ce",
                            halo_cache=cfg.halo_cache,
                            halo_refresh_every=cfg.halo_refresh_every,
                            halo_cv=cfg.halo_cv,
                            halo_compress=cfg.halo_compress,
                            grad_compress=cfg.grad_compress,
                            grad_topk_frac=cfg.grad_topk_frac,
                            grad_bucket_kb=cfg.grad_bucket_kb,
                            feat_store=cfg.feat_store,
                            hot_frac=cfg.hot_frac,
                            hot_policy=cfg.hot_policy,
                            feat_groups=cfg.feat_groups,
                            feat_budget_mb=cfg.feat_budget_mb))
    if verbose:
        dev = jax.devices()
        print(f"engine[{engine.mode}] platform={dev[0].platform} "
              f"device_kind={dev[0].device_kind} devices={len(dev)} "
              f"{pg.summary()}")

    # ---------------- per-host samplers -----------------------------------
    # the feature store keeps the whole table off the device: its batches
    # gather on the host; otherwise make_batch gathers on the device
    neigh = NeighborSampler(graph, fanouts=cfg.fanouts, seed=cfg.seed,
                            stage_features=not cfg.feat_store)
    host_train = [graph.train_idx[parts[graph.train_idx] == p]
                  for p in range(n_parts)]
    samplers = [
        CBSampler(graph.indptr, graph.indices, graph.labels, host_train[p],
                  batch_size=cfg.batch_size,
                  subset_fraction=cfg.subset_fraction if cfg.use_cbs else 1.0,
                  class_balanced=cfg.use_cbs, seed=cfg.seed + p)
        for p in range(n_parts)
    ]

    params = model.init(cfg.seed)
    opt_state = opt.init(params)
    # per-sync gradient wire volume, truthful to the sync SPELLING: the
    # plain all_gather ships P*(P-1) full copies, the bucketed ring 2*(P-1),
    # top-k only the (value, index) pairs each partition keeps
    p_leaves = jax.tree_util.tree_leaves(params)
    grad_bytes_per_sync = grad_sync_wire_bytes(
        cfg.grad_compress, n_parts, sum(l.size for l in p_leaves),
        itemsize=p_leaves[0].dtype.itemsize, topk_frac=cfg.grad_topk_frac)
    # cross-partition edges = remote fetch volume per epoch (DistDGL analog)
    src_all = graph.indices
    dst_all = np.repeat(np.arange(graph.num_nodes), np.diff(graph.indptr))
    cut_frac = float((parts[src_all] != parts[dst_all]).mean())
    # effective per-epoch visit fraction: CBS mini-epochs touch subset_fraction
    # of the train nodes, the plain sampler touches all of them
    eff_fraction = cfg.subset_fraction if cfg.use_cbs else 1.0
    fetch_bytes_per_epoch = int(cut_frac * graph.num_edges * graph.feature_dim
                                * fdt.itemsize * eff_fraction)
    def eval_exchange_bytes() -> int:
        # the exchange volume THIS epoch's eval forward actually paid: only
        # the refreshed-row payload under the historical halo cache (the
        # engine reports it after each cached forward), the full per-layer
        # WIRE payload (dtype- and compression-truthful) otherwise
        if cfg.halo_cache:
            return int(engine.last_halo_exchange_bytes)
        return model.num_layers * int(getattr(
            engine, "halo_wire_bytes_per_layer", pg.halo_bytes_per_layer))

    # graph.features itself where fdt is its dtype: feature_views then
    # gathers on the device from the sampler's staged copy
    batch_feats = np.asarray(graph.features, fdt)

    def make_batch(nodes: np.ndarray) -> dict:
        # fixed shapes (pad + mask) so batches stack across hosts and the
        # jitted step compiles once — mirrors the static-shape TPU contract
        k = len(nodes)
        if k < cfg.batch_size:
            nodes = np.concatenate(
                [nodes, np.zeros(cfg.batch_size - k, dtype=nodes.dtype)])
        mask = np.zeros(cfg.batch_size, fdt)
        mask[:k] = 1.0
        blocks = neigh.sample(nodes)
        x_t, x_1, x_2 = blocks.feature_views(batch_feats)
        return {"x_t": jnp.asarray(x_t), "x_1": jnp.asarray(x_1),
                "x_2": jnp.asarray(x_2),
                "labels": jnp.asarray(graph.labels[nodes]),
                "mask": jnp.asarray(mask)}

    # ---------------- phase 0: generalization -----------------------------
    p0frac = cfg.phase0_fraction
    if p0frac is None and cfg.async_personalize:
        p0frac = 0.4
    sched = GPScheduleConfig(
        max_epochs=cfg.max_epochs,
        flatten_tol=cfg.flatten_tol,
        phase0_fraction=p0frac,
        # a hard split must fit the epoch budget (e.g. --epochs 3)
        min_phase0_epochs=(min(3, max(1, cfg.max_epochs // 3))
                           if p0frac is not None else 3))
    ctrl = GPController(num_partitions=n_parts, config=sched)
    sim_time = 0.0
    epoch_times: list[float] = []
    epoch_times_with_eval: list[float] = []
    epoch_dev: list[float] = []
    comm_grad = 0
    comm_halo_p0 = 0
    comm_halo_p1 = 0
    halo_exchange_hist: list[int] = []   # per-epoch eval-exchange payload
    best_global = params
    loss_hist: list[float] = []
    val_hist: list[float] = []

    # host sampler RNG discipline for checkpointing: `rng_snapshot` always
    # holds the generator states with every draw through the last
    # handed-out epoch consumed — captured on the main thread BEFORE any
    # speculative prefetch draw, so the double-buffered path checkpoints
    # the same stream position the unbuffered path would (DESIGN.md §10)
    def capture_rng() -> dict:
        return {"cbs": [s._rng.bit_generator.state for s in samplers],
                "neigh": neigh._rng.bit_generator.state}

    def restore_rng(snap: dict) -> None:
        for s, st in zip(samplers, snap["cbs"]):
            s._rng.bit_generator.state = st
        neigh._rng.bit_generator.state = snap["neigh"]

    rng_snapshot = capture_rng()

    # the prefetcher exists only where host sampling does: it is created
    # lazily by the first epoch that draws on the host, so fully-async runs
    # never construct it (the phase-0 host-isolation contract)
    prefetch = None

    def next_epoch_batches():
        nonlocal prefetch, rng_snapshot
        if cfg.double_buffer:
            if prefetch is None:
                prefetch = _EpochPrefetcher(
                    lambda: stack_epoch_batches(samplers, make_batch, n_parts),
                    snapshot=capture_rng)
            out = prefetch.next()
            rng_snapshot = prefetch.last_snapshot
            return out
        out = stack_epoch_batches(samplers, make_batch, n_parts)
        rng_snapshot = capture_rng()
        return out

    # ONE device sampler serves both async phases (Eq. 3 / uniform logp +
    # fanout structure + features); staged lazily by the first phase that
    # needs it, so it never pins a replicated feature copy it won't use
    async_phase0 = cfg.async_generalize and not cfg.full_graph_train
    dev_sampler = None

    def stage_device_sampler():
        nonlocal dev_sampler
        if dev_sampler is None:
            dev_sampler = build_device_epoch_sampler(
                graph, host_train, n_parts, batch_size=cfg.batch_size,
                subset_fraction=cfg.subset_fraction if cfg.use_cbs else 1.0,
                class_balanced=cfg.use_cbs, fanouts=cfg.fanouts,
                feat_store=cfg.feat_store, hot_frac=cfg.hot_frac,
                hot_policy=cfg.hot_policy)
        return dev_sampler

    if async_phase0:
        engine.set_device_sampler(stage_device_sampler())
        p0_base_keys = jax.random.split(
            jax.random.PRNGKey(cfg.seed ^ 0x6E02), n_parts)

    def epoch_host_times(t_host, t_dev):
        # synchronous epoch: everyone waits for the slowest host; the fused
        # device step is attributed in equal 1/N shares.  Double-buffered,
        # the next epoch's sampling overlaps this epoch's device step, so
        # the steady-state epoch period is the max of the two, not the sum.
        if cfg.double_buffer:
            return np.maximum(t_host, t_dev / n_parts)
        return t_host + t_dev / n_parts

    # full-graph epochs exchange halos in BOTH directions of each train
    # step (the backward's transpose aggregation routes gradient through
    # the same send/recv lists), plus the per-epoch validation forward's
    # per-layer exchange — which the sampled path's accounting also counts
    # — and fetch no sampled neighbours
    # (training exchanges stay uncompressed — only the eval forward's
    # exchange is quantized, so only its term uses the wire-byte rate)
    fg_halo_bytes_per_epoch = (2 * model.num_layers * pg.halo_bytes_per_layer
                               * cfg.full_graph_iters
                               + model.num_layers * int(getattr(
                                   engine, "halo_wire_bytes_per_layer",
                                   pg.halo_bytes_per_layer)))

    host_to_device_p0 = 0
    host_to_device_p1 = 0
    p0_iter_hist: list[int] = []
    straggler_total = 0.0

    # cold-row staging is counted inside the engine (where the numpy buffer
    # is handed to a compiled call); the pipeline reads per-epoch DELTAS to
    # attribute the traffic to the phase that paid it
    cold_mark = int(getattr(engine, "cold_h2d_bytes", 0))

    def cold_delta() -> int:
        nonlocal cold_mark
        now = int(getattr(engine, "cold_h2d_bytes", 0))
        d, cold_mark = now - cold_mark, now
        return d

    # ---------------- checkpoint/resume (DESIGN.md §10) --------------------
    ckpt = (RunCheckpointer(cfg.checkpoint_dir,
                            keep_last=cfg.keep_checkpoints)
            if cfg.checkpoint_dir else None)
    fingerprint = {"dataset": cfg.dataset, "num_parts": n_parts,
                   "method": cfg.partition_method, "seed": cfg.seed,
                   "dtype": cfg.dtype, "engine": engine.mode,
                   "halo_cache": cfg.halo_cache,
                   "halo_compress": cfg.halo_compress,
                   "grad_compress": cfg.grad_compress,
                   "feat_store": cfg.feat_store,
                   "hot_frac": cfg.hot_frac if cfg.feat_store else 0.0,
                   "hot_policy": cfg.hot_policy if cfg.feat_store else ""}

    def halo_ckpt_state():
        if cfg.halo_cache and hasattr(engine, "halo_cache_state"):
            return engine.halo_cache_state()
        return None

    def comm_res_state():
        # error-feedback residuals are part of the resumable state: dropping
        # them on resume would re-inject the already-compensated error
        if hasattr(engine, "comm_residual_state"):
            return engine.comm_residual_state()
        return None

    def make_like(host: dict) -> dict:
        # reject a foreign checkpoint BEFORE any array I/O: a different
        # seed/partitioning would otherwise surface as a shape mismatch
        fp = host.get("fingerprint", {})
        if fp != fingerprint:
            raise ValueError(
                f"checkpoint fingerprint {fp} does not match this run "
                f"{fingerprint} — refusing to resume")
        # the arrays template is phase-dependent: personal params exist
        # only once the phase-1 loop has run at least one epoch
        like = {"params": params, "opt": opt_state, "best_global": params}
        if host.get("has_phase1"):
            pp = broadcast_to_partitions(params, n_parts)
            like.update(global_params=params, pparams=pp,
                        popt=jax.vmap(opt.init)(pp), best_personal=pp)
        st = halo_ckpt_state()
        if st is not None:
            like["halo"] = st[0]
        if host.get("has_halo_res"):
            like["halo_res"] = engine._halo_residual
        if host.get("has_grad_res"):
            like["grad_res"] = engine._grad_residual(params)
        return like

    restore_phase1 = None
    resumed_from = -1
    if ckpt is not None and cfg.resume:
        loaded = ckpt.load_latest(make_like)
        if loaded is not None:
            arrays, host, resumed_from = loaded
            params, opt_state = arrays["params"], arrays["opt"]
            best_global = arrays["best_global"]
            ctrl.load_state_dict(host["controller"])
            rng_snapshot = host["rng"]
            restore_rng(rng_snapshot)
            loss_hist = [float(x) for x in host["loss_hist"]]
            val_hist = [float(x) for x in host["val_hist"]]
            sim_time = float(host["sim_time"])
            epoch_times = [float(x) for x in host["epoch_times"]]
            epoch_times_with_eval = [float(x)
                                     for x in host["epoch_times_with_eval"]]
            epoch_dev = [float(x) for x in host.get("epoch_device_s", ())]
            comm_grad, comm_halo_p0, comm_halo_p1 = (
                int(x) for x in host["comm"])
            halo_exchange_hist = [int(x) for x in host["halo_exchange_hist"]]
            p0_iter_hist = [int(x) for x in host["p0_iter_hist"]]
            host_to_device_p0 = int(host["host_to_device_p0"])
            host_to_device_p1 = int(host.get("host_to_device_p1", 0))
            straggler_total = float(host.get("straggler_s", 0.0))
            if "halo" in arrays:
                engine.restore_halo_cache_state(arrays["halo"],
                                                host["halo_age"])
            if "halo_res" in arrays or "grad_res" in arrays:
                engine.restore_comm_residual_state(
                    (arrays.get("halo_res"), arrays.get("grad_res")))
            if host.get("has_phase1"):
                restore_phase1 = (arrays, host)
            if verbose:
                print(f"[resume] epoch {resumed_from} phase {ctrl.phase} "
                      f"from {cfg.checkpoint_dir}")

    phase1_state: dict = {}   # live phase-1 state, for checkpoint capture

    def save_checkpoint() -> None:
        arrays = {"params": params, "opt": opt_state,
                  "best_global": best_global}
        host = {
            "has_phase1": bool(phase1_state),
            "controller": ctrl.state_dict(),
            "rng": rng_snapshot,
            "loss_hist": loss_hist, "val_hist": val_hist,
            "sim_time": sim_time,
            "epoch_times": epoch_times,
            "epoch_times_with_eval": epoch_times_with_eval,
            "epoch_device_s": epoch_dev,
            "comm": [int(comm_grad), int(comm_halo_p0), int(comm_halo_p1)],
            "halo_exchange_hist": [int(x) for x in halo_exchange_hist],
            "p0_iter_hist": [int(x) for x in p0_iter_hist],
            "host_to_device_p0": int(host_to_device_p0),
            "host_to_device_p1": int(host_to_device_p1),
            "straggler_s": straggler_total,
            "fingerprint": fingerprint,
        }
        st = halo_ckpt_state()
        if st is not None:
            arrays["halo"] = jax.tree.map(np.asarray, st[0])
            host["halo_age"] = int(st[1])
        cs = comm_res_state()
        if cs is not None:
            h_res, g_res = cs
            if h_res is not None:
                arrays["halo_res"] = jax.tree.map(np.asarray, h_res)
            if g_res is not None:
                arrays["grad_res"] = np.asarray(g_res)
            host["has_halo_res"] = h_res is not None
            host["has_grad_res"] = g_res is not None
        if phase1_state:
            arrays.update(
                global_params=phase1_state["global_params"],
                pparams=phase1_state["pparams"],
                popt=phase1_state["popt"],
                best_personal=stack_pytrees(phase1_state["best_personal"]))
            host["host_elapsed"] = [float(x)
                                    for x in phase1_state["host_elapsed"]]
            host["phase1_epochs"] = int(phase1_state["phase1_epochs"])
        ckpt.save(ctrl.epoch, arrays, host)

    def epoch_boundary() -> None:
        """End of one epoch (ctrl already advanced): persist the boundary,
        then let any injected crash fire AFTER the state is durable — the
        only crash point an epoch-granular checkpointer can replay."""
        if ckpt is not None and ctrl.epoch % max(1, cfg.checkpoint_every) == 0:
            save_checkpoint()
        if fault_plan is not None and fault_plan.crash_at(ctrl.epoch):
            raise InjectedCrash(ctrl.epoch)

    def epoch_faults() -> np.ndarray | None:
        """Start of one epoch (index ctrl.epoch): arm the dropped-refresh
        fault, return this epoch's straggler delays (None = none)."""
        if fault_plan is None:
            return None
        if (cfg.halo_cache and fault_plan.drop_halo_refresh(ctrl.epoch)
                and hasattr(engine, "drop_next_halo_refresh")):
            engine.drop_next_halo_refresh()
        d = fault_plan.straggler_delay(ctrl.epoch, n_parts)
        return d if d.any() else None

    draws_at_p0_start = host_draw_count()
    # the no-GP early stop lives in the loop CONDITION (not a body break) so
    # a run resumed from its stopping boundary also exits before training
    while (not ctrl.done and ctrl.phase == 0
           and not (not cfg.use_gp and ctrl.phase0_stopper.stopped)):
        delay = epoch_faults()
        if cfg.full_graph_train:
            params, opt_state, losses, val_micro, t_dev = (
                engine.phase0_fullgraph_epoch(params, opt_state,
                                              iters=cfg.full_graph_iters))
            iters = np.asarray(losses).shape[0]
            t_host = np.zeros(n_parts)      # no host sampling on this path
            comm_halo_p0 += fg_halo_bytes_per_epoch
            halo_exchange_hist.append(eval_exchange_bytes())
        elif async_phase0:
            # one device program per epoch: draw + train scan + fused eval.
            # The only host→device payload is the per-partition PRNG keys.
            keys = jax.vmap(jax.random.fold_in, (0, None))(
                p0_base_keys, ctrl.epoch)
            params, opt_state, losses, val_micro, t_dev = (
                engine.phase0_epoch_async(params, opt_state, keys))
            iters = np.asarray(losses).shape[0]
            t_host = np.zeros(n_parts)      # no host sampling on this path
            host_to_device_p0 += np.asarray(keys).nbytes
            ex = eval_exchange_bytes()
            halo_exchange_hist.append(ex)
            comm_halo_p0 += ex + fetch_bytes_per_epoch
        else:
            batches, t_host, iters = next_epoch_batches()
            host_to_device_p0 += sum(
                l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(batches))
            params, opt_state, losses, val_micro, t_dev = engine.phase0_epoch(
                params, opt_state, batches)
            ex = eval_exchange_bytes()
            halo_exchange_hist.append(ex)
            comm_halo_p0 += ex + fetch_bytes_per_epoch
        host_to_device_p0 += cold_delta()
        epoch_dev.append(float(t_dev))
        comm_grad += grad_bytes_per_sync * iters
        p0_iter_hist.append(int(iters))
        host_time = epoch_host_times(t_host, t_dev)
        if delay is not None:
            # injected straggler: the synchronous epoch waits for it
            host_time = host_time + delay
            straggler_total += float(delay.max())
        sim_time += float(host_time.max())
        epoch_times.append(float(host_time.max()))
        # eval-inclusive epoch period: a separately-compiled eval (host and
        # full-graph paths) adds its 1/N share; the fused async epoch's
        # t_dev already contains it (last_eval_seconds is 0 there)
        epoch_times_with_eval.append(
            float(host_time.max())
            + getattr(engine, "last_eval_seconds", 0.0) / n_parts)

        mean_loss = float(np.asarray(losses).mean())
        mean_val = float(np.asarray(val_micro).mean())
        loss_hist.append(mean_loss)
        val_hist.append(mean_val)
        if ctrl.record_phase0(mean_loss, mean_val):
            best_global = params
        if verbose:
            print(f"[phase-0] epoch {ctrl.epoch:3d} loss {mean_loss:.4f} "
                  f"val-micro {mean_val*100:.2f} device {t_dev:.4f}s")
        if cfg.use_gp and ctrl.should_personalize():
            ctrl.start_personalization()
        epoch_boundary()

    if prefetch is not None:
        prefetch.settle()       # quiesce the worker: race-free snapshot
    # sync note: with the prefetcher the tally includes the speculative
    # next-epoch draw that overlapped the last phase-0 device step
    host_draws_p0 = host_draw_count() - draws_at_p0_start

    personalize_start = ctrl.personalize_start_epoch

    # ---------------- phase 1: personalization ----------------------------
    phase1_time = 0.0
    phase1_epochs = 0
    host_draws_p1 = 0
    if cfg.use_gp and not cfg.centralized:
        if restore_phase1 is not None:
            # resumed mid-personalization: restore the phase-1 state the
            # checkpoint carried instead of re-deriving it from best_global
            arrays, rhost = restore_phase1
            global_params = arrays["global_params"]
            pparams, popt = arrays["pparams"], arrays["popt"]
            best_personal = [
                jax.tree.map(lambda x, p=p: x[p], arrays["best_personal"])
                for p in range(n_parts)]
            host_elapsed = np.asarray(rhost["host_elapsed"], float)
            phase1_epochs = int(rhost["phase1_epochs"])
        else:
            global_params = best_global
            pparams = broadcast_to_partitions(global_params, n_parts)
            popt = jax.vmap(opt.init)(pparams)
            best_personal = [jax.tree.map(lambda x: x[p], pparams)
                             for p in range(n_parts)]
            host_elapsed = np.zeros(n_parts)

        if cfg.async_personalize:
            # from here on the mini-epoch path is one device program: join
            # and discard any in-flight host draw, then attach the device
            # sampler staged before phase-0 (ONE sampler serves both phases;
            # already attached when phase-0 ran async)
            if prefetch is not None:
                prefetch.close()
            if not async_phase0:
                engine.set_device_sampler(stage_device_sampler())
            base_keys = jax.random.split(
                jax.random.PRNGKey(cfg.seed ^ 0xCB5D), n_parts)
        elif prefetch is not None:
            prefetch.settle()       # quiesce the worker: race-free snapshot
        # sync note: the count includes the final speculative (discarded)
        # prefetch epoch — those draws still run on the host during phase-1
        draws_at_p1_start = host_draw_count()

        while not ctrl.done:
            active_np = ctrl.active_partitions
            delay = epoch_faults()
            if delay is not None:
                host_elapsed += np.where(active_np, delay, 0.0)
                straggler_total += float(delay.max())
            if cfg.async_personalize:
                budgets = ctrl.phase1_budgets(dev_sampler.natural_iters)
                keys = jax.vmap(jax.random.fold_in, (0, None))(
                    base_keys, ctrl.epoch)
                pparams, popt, losses, val_micro, t_dev = (
                    engine.phase1_epoch_async(pparams, popt, keys,
                                              jnp.asarray(budgets),
                                              global_params))
                # each host pays for its own budgeted share of the fused
                # step; converged hosts (budget 0) pay nothing
                host_elapsed += t_dev * budgets / max(1, int(budgets.sum()))
            else:
                batches, t_host, iters = next_epoch_batches()
                budgets = ctrl.phase1_budgets(iters)
                pparams, popt, losses, val_micro, t_dev = engine.phase1_epoch(
                    pparams, popt, batches, global_params,
                    jnp.asarray(budgets))
                host_elapsed += np.where(
                    active_np, epoch_host_times(t_host, t_dev), 0.0)
            epoch_dev.append(float(t_dev))
            ex = eval_exchange_bytes()
            halo_exchange_hist.append(ex)
            comm_halo_p1 += ex + fetch_bytes_per_epoch
            host_to_device_p1 += cold_delta()
            scores = np.asarray(val_micro)
            is_best = ctrl.record_phase1(scores)
            phase1_epochs += 1
            for p in np.flatnonzero(is_best):
                best_personal[p] = jax.tree.map(lambda x: x[p], pparams)
            loss_hist.append(float(np.asarray(losses)[-1].mean()))
            val_hist.append(float(scores.mean()))
            if verbose:
                print(f"[phase-1] epoch {ctrl.epoch:3d} "
                      f"val-micro {scores.mean()*100:.2f} "
                      f"active {int(active_np.sum())}/{n_parts} "
                      f"budgets {np.asarray(budgets).tolist()} "
                      f"device {t_dev:.4f}s")
            phase1_state.update(
                global_params=global_params, pparams=pparams, popt=popt,
                best_personal=best_personal, host_elapsed=host_elapsed,
                phase1_epochs=phase1_epochs)
            epoch_boundary()
        # async phase: distributed time = slowest host's own cumulative time
        if prefetch is not None:
            prefetch.close()        # settle in-flight draws before counting
        host_draws_p1 = host_draw_count() - draws_at_p1_start
        phase1_time = float(host_elapsed.max())
        sim_time += phase1_time
        final_stacked = stack_pytrees(best_personal)
    else:
        final_stacked = broadcast_to_partitions(best_global, n_parts)
        if prefetch is not None:
            prefetch.close()

    # ---------------- final evaluation -------------------------------------
    _, preds = engine.evaluate(final_stacked, "test",
                               per_partition_params=True)
    host_to_device_p1 += cold_delta()    # the test eval's cold staging
    preds = np.asarray(preds)
    test_mask = np.asarray(pg.test_mask)
    labels = np.asarray(pg.labels)
    all_preds, all_labels, per_micro = [], [], np.zeros(n_parts)
    for p in range(n_parts):
        m = test_mask[p]
        pred, lab = preds[p][m], labels[p][m]
        all_preds.append(pred)
        all_labels.append(lab)
        per_micro[p] = f1_scores(pred, lab, graph.num_classes).micro
    f1 = f1_scores(np.concatenate(all_preds), np.concatenate(all_labels),
                   graph.num_classes)

    return EATResult(
        config=cfg, f1=f1, per_partition_micro=per_micro,
        partition_entropies=ents, partition_time_s=p_time, weight_time_s=w_time,
        train_time_s=sim_time,
        epoch_time_s=float(np.mean(epoch_times)) if epoch_times else 0.0,
        epoch_time_with_eval_s=(float(np.mean(epoch_times_with_eval))
                                if epoch_times_with_eval else 0.0),
        epochs_run=ctrl.epoch, personalize_start_epoch=personalize_start,
        loss_history=loss_hist, val_history=val_hist,
        comm_grad_bytes=comm_grad,
        comm_halo_bytes=comm_halo_p0 + comm_halo_p1,
        comm_halo_bytes_phase0=comm_halo_p0,
        comm_halo_bytes_phase1=comm_halo_p1,
        halo_bytes_per_layer=pg.halo_bytes_per_layer,
        comm_halo_exchange_bytes=sum(halo_exchange_hist),
        halo_exchange_history=halo_exchange_hist,
        engine_mode=engine.mode,
        phase1_time_s=phase1_time, phase1_epochs=phase1_epochs,
        host_draws_phase1=host_draws_p1,
        host_draws_phase0=host_draws_p0,
        phase0_iter_history=p0_iter_hist,
        host_to_device_bytes_phase0=host_to_device_p0,
        host_to_device_bytes_phase1=host_to_device_p1,
        resident_feature_bytes=int(getattr(engine,
                                           "resident_feature_bytes", 0)),
        cold_h2d_bytes=int(getattr(engine, "cold_h2d_bytes", 0)),
        final_params=final_stacked,
        resumed_from_epoch=resumed_from,
        straggler_delay_s=straggler_total,
        epoch_device_s=epoch_dev,
    )
