"""One cell's training job, built from its configuration and traffic files
and driven through the program's own per-epoch calls.

A cell is ``BENCHMARK.json``'s workload entry: a configuration file (graph
and model sizes, under ``configs/``), the model module its ``arch`` names
(``models/<arch>.py``: weights, the program's model, the plain forward,
FLOPs) and a traffic file (the job: sampled mini-batches or full-graph
steps, partitions, fanout and batch, under ``traffic/``).  Nothing here
names a cell or a model: a later cell adds files only.

``Cell`` holds what does not depend on ``--seed``: the generated graph, its
EW partition, the program's ``SPMDEngine`` and its compiled programs, on
the cell's ``chips`` devices (one: every partition vmapped on
``jax.devices()[0]``; more: one partition per chip under ``shard_map``).
``Trainer`` holds what does: the initial weights (made by the model
module, on the device, from the seed), the samplers and the optimizer
state.  One ``Trainer.epoch`` makes the same calls
``repro.pipeline.run_eat_distgnn`` makes for a phase-0 epoch:

  sampled     per-partition ``CBSampler`` + ``NeighborSampler`` draws,
              stacked by ``stack_epoch_batches`` behind the pipeline's
              double-buffered ``_EpochPrefetcher``, then
              ``SPMDEngine.phase0_epoch`` (train scan + validation eval);
  fullgraph   ``SPMDEngine.phase0_fullgraph_epoch`` (full-batch steps
              through the halo exchange and the aggregation kernel, then
              the validation eval).

The checked epochs (the first few, through the same calls) also keep the
validation forward's predictions of every validation node, which
``check.py`` holds against the plain reference's logits.

Faults (``FAULTS``) break the timed path underneath, for the tests and the
calibration that show the comparison catches them; a benchmark run never
sets one.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

import numpy as np

from . import byname, graphgen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MODELS = BENCH / "models"

# faults of the timed path a training cell can have (tests, calibration)
FAULTS = ("frozen_state", "half_batch", "no_exchange", "altered_rows")


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: ModuleType             # models/<arch>.py


def load_cell(workload: str, root: Path = ROOT,
              models: Path = MODELS) -> CellSpec:
    """Find a workload of ``<root>/BENCHMARK.json`` and read its
    configuration file, its traffic file (``perfbench/traffic/<name>``)
    and the model module its configuration's ``arch`` names
    (``<models>/<arch>.py``)."""
    spec = load_json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(Path(root) / cfg_entry["file"])
    traffic = load_json(traffic_file(w["traffic"], root))
    if "arch" not in config:
        raise KeyError(f"configuration {cfg_entry['file']} names no arch")
    return CellSpec(name=workload, chips=int(w["chips"]), config=config,
                    traffic=traffic,
                    model=byname.load("model", config["arch"], models))


def traffic_file(name: str, root: Path = ROOT) -> Path:
    hits = sorted((Path(root) / "perfbench" / "traffic").glob(f"{name}.json"))
    if not hits:
        raise FileNotFoundError(f"no traffic file perfbench/traffic/{name}.json")
    return hits[0]


# ---------------------------------------------------------------- seeds
def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, (seed >> 31) % (1 << 31))


# ---------------------------------------------------------------- the cell
class Cell:
    """Graph, partition and engine of one configuration under one traffic
    mix; independent of ``--seed``, so calibration reuses it across seeds.
    ``like``, a cell of the same spec, lends its graph and partition (the
    calibration's fault cells), so only the engine is built anew."""

    def __init__(self, spec: CellSpec, faults: tuple[str, ...] = (),
                 like: "Cell | None" = None):
        import jax

        from repro.core import GPHyperParams, partition_graph
        from repro.engine import EngineConfig, make_engine
        from repro.graph import CSRGraph, build_partitioned_graph
        from repro.train.optim import AdamW

        self.spec, self.faults = spec, tuple(faults)
        cfg, tr = spec.config, spec.traffic
        self.kind = tr["kind"]
        if self.kind not in ("sampled", "fullgraph"):
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        self.num_parts = int(tr["partition"]["num_parts"])
        if spec.chips not in (1, self.num_parts):
            raise ValueError(
                f"a cell runs its {self.num_parts} partitions on one chip or "
                f"one to a chip, not on {spec.chips}")
        # the chips the engine runs on: partition p on devices[p] under
        # shard_map, or every partition on devices[0], vmapped
        self.devices = jax.devices()[:spec.chips]
        mode = "stacked" if spec.chips == 1 else "spmd"
        t0 = time.perf_counter()
        if like is not None:
            self.graph, self.prog_graph = like.graph, like.prog_graph
            self.parts, self.pg = like.parts, like.pg
            t1 = t2 = t0
        else:
            g = self.graph = graphgen.generate(cfg)
            self.prog_graph = CSRGraph(
                indptr=g.indptr, indices=g.indices, features=g.features,
                labels=g.labels, train_idx=g.train_idx, val_idx=g.val_idx,
                test_idx=g.test_idx, num_classes=g.num_classes,
                name=cfg["name"])
            t1 = time.perf_counter()
            part = tr["partition"]
            pres = partition_graph(
                g.indptr, g.indices, g.features, g.labels, self.num_parts,
                method=part["method"], seed=int(part["seed"]),
                fanout_k=int(part["fanout_k"]))
            self.parts = np.asarray(pres.parts)
            t2 = time.perf_counter()
            self.pg = build_partitioned_graph(self.prog_graph, self.parts,
                                              self.num_parts)
        g = self.graph
        self.model, loss_fn = spec.model.build_program(cfg)
        self.opt = AdamW(lr=float(cfg["lr"]), b1=float(cfg["adam_b1"]),
                         b2=float(cfg["adam_b2"]), eps=float(cfg["adam_eps"]),
                         grad_clip=float(cfg["grad_clip"]))
        self._exchange_patch = self._patch_exchange()
        self.engine = make_engine(
            self.model, loss_fn, self.opt, self.pg, hp=GPHyperParams(),
            config=EngineConfig(mode=mode, use_pallas_agg=True))
        self._plant_engine_faults()
        self._span_evaluate()
        t3 = time.perf_counter()
        self.timings = {"generate_s": t1 - t0, "partition_s": t2 - t1,
                        "build_s": t3 - t2}
        # real work per step, for the FLOP functions
        self.train_mask_count = int(np.asarray(
            self.engine.masks["train"]).sum())
        self.host_train = [g.train_idx[self.parts[g.train_idx] == p]
                           for p in range(self.num_parts)]
        self.val_ids, self.val_rows = self._val_rows()
        self.last_eval = None

    def _val_rows(self):
        """The generator's validation nodes, sorted, and where the
        program's evaluation answers for each: (partition, local row) among
        the partition's owned rows."""
        pg, n = self.pg, self.graph.num_nodes
        part = np.full(n, -1, np.int64)
        row = np.full(n, -1, np.int64)
        for p in range(pg.num_parts):
            own = np.asarray(pg.global_ids[p, :int(pg.n_own[p])], np.int64)
            part[own], row[own] = p, np.arange(len(own))
        ids = np.sort(np.asarray(self.graph.val_idx, np.int64))
        if (part[ids] < 0).any():
            raise ValueError("a validation node is owned by no partition")
        return ids, (part[ids], row[ids])

    def val_preds(self) -> np.ndarray:
        """The latest validation forward's class for each of ``val_ids``."""
        preds = np.asarray(self.last_eval[1])
        return preds[self.val_rows[0], self.val_rows[1]]

    # -- faults planted in the program's step (never set by a run) --------
    def _patch_exchange(self):
        """``no_exchange``: the halo exchange leaves the halo rows as they
        were (zero), so every boundary row aggregates without its remote
        in-neighbours, in the full-graph step and in the validation
        forward.  Stays patched while the engine traces its programs, until
        :meth:`close`."""
        if "no_exchange" not in self.faults:
            return None
        from unittest import mock

        import repro.graph.distributed as dist

        patch = mock.patch.object(dist, "_halo_exchange",
                                  lambda h, *a, **k: h)
        patch.start()
        return patch

    def _plant_engine_faults(self):
        import jax

        eng = self.engine
        if "no_exchange" in self.faults and self.kind == "sampled":
            # the gradient mean across partitions is left out: partition 0's
            # gradient alone reaches the optimizer
            eng._grad_reduce_stacked = lambda: (
                lambda grads: jax.tree.map(lambda g: g[0], grads))
            eng._grad_reduce_shard = lambda: (lambda grads: grads)
        res = eng._resident
        if "half_batch" in self.faults and self.kind == "fullgraph":
            tm = np.asarray(res["masks"]["train"]).copy()
            for p in range(tm.shape[0]):
                idx = np.flatnonzero(tm[p])
                tm[p, idx[1::2]] = False
            res["masks"]["train"] = jax.device_put(
                tm, res["masks"]["train"].sharding)
        if "altered_rows" in self.faults:
            # the resident rows feed the full-graph step and, in every
            # cell, the validation forward
            f = res["shards"]["features"]
            res["shards"]["features"] = jax.device_put(
                np.roll(np.asarray(f), 1, axis=1), f.sharding)

    def _span_evaluate(self):
        """A ``bench.eval`` span around the engine's validation forward, so
        a trace tells it from the train call inside the same epoch call;
        its output (micro-F1 and per-row classes, on the device) is kept as
        ``last_eval`` for the checked epochs."""
        from jax.profiler import TraceAnnotation

        evaluate = self.engine.evaluate

        def spanned(*a, **k):
            with TraceAnnotation("bench.eval"):
                self.last_eval = evaluate(*a, **k)
                return self.last_eval

        self.engine.evaluate = spanned

    def close(self) -> None:
        self.last_eval = None
        patch = getattr(self, "_exchange_patch", None)
        if patch is not None:
            patch.stop()
            self._exchange_patch = None


# ---------------------------------------------------------------- training
@dataclass
class EpochOut:
    losses: np.ndarray            # (iters, P) per-partition step losses
    nodes: int                    # labelled training nodes the steps used
    steps: int                    # optimizer steps in the epoch
    batch_ids: list = field(default_factory=list)  # sampled: (it, p) ids
    val_preds: np.ndarray | None = None  # checked epochs: class per val node


class Trainer:
    """Seed-dependent state of one run on a :class:`Cell`."""

    def __init__(self, cell: Cell, seed: int, record_epochs: int = 0):
        from repro.core.sampler import CBSampler
        from repro.graph import NeighborSampler
        from repro.pipeline import _EpochPrefetcher

        self.cell, self.seed = cell, int(seed)
        model = cell.spec.model
        self.params0 = model.init_params(cell.spec.config,
                                         seed_key(self.seed))
        self.params = model.to_program_params(self.params0)
        self.opt_state = cell.opt.init(self.params)
        self._record_left = int(record_epochs)
        self._evals_left = int(record_epochs)
        self.prefetch = None
        if cell.kind == "sampled":
            tr = cell.spec.traffic
            self.batch_size = int(tr["batch_size"])
            fanouts = tuple(int(f) for f in tr["fanouts"])
            self.neigh = NeighborSampler(cell.prog_graph, fanouts=fanouts,
                                         seed=self.seed)
            g = cell.prog_graph
            self.samplers = [
                CBSampler(g.indptr, g.indices, g.labels, cell.host_train[p],
                          batch_size=self.batch_size,
                          subset_fraction=float(tr["subset_fraction"]),
                          class_balanced=bool(tr["class_balanced"]),
                          seed=self.seed + p)
                for p in range(cell.num_parts)]
            self._feats = np.asarray(g.features, np.float32)
            self.prefetch = _EpochPrefetcher(self._draw)

    # -- the pipeline's make_batch, with the ids kept for the reference ----
    def _make_batch(self, nodes: np.ndarray, rec: dict) -> dict:
        import jax.numpy as jnp

        B = self.batch_size
        k = len(nodes)
        if k < B:
            nodes = np.concatenate([nodes, np.zeros(B - k, dtype=nodes.dtype)])
        mask = np.zeros(B, np.float32)
        mask[:k] = 1.0
        blocks = self.neigh.sample(nodes)
        x_t, x_1, x_2 = blocks.feature_views(self._feats)
        rec["nodes"] += k
        if rec["keep"]:
            rec["ids"].append({"targets": blocks.targets, "nbrs1": blocks.nbrs1,
                               "nbrs2": blocks.nbrs2, "mask": mask.copy()})
        if "half_batch" in self.cell.faults:
            mask[k // 2:k] = 0.0
        if "altered_rows" in self.cell.faults:
            x_1 = np.roll(x_1, 1, axis=0)
        return {"x_t": jnp.asarray(x_t), "x_1": jnp.asarray(x_1),
                "x_2": jnp.asarray(x_2),
                "labels": jnp.asarray(self.cell.prog_graph.labels[nodes]),
                "mask": jnp.asarray(mask)}

    def _draw(self):
        from repro.engine import stack_epoch_batches

        rec = {"nodes": 0, "ids": [], "keep": self._record_left > 0}
        self._record_left -= 1
        batches, _, iters = stack_epoch_batches(
            self.samplers, lambda n: self._make_batch(n, rec),
            self.cell.num_parts)
        return batches, iters, rec

    def epoch(self) -> EpochOut:
        from jax.profiler import TraceAnnotation

        eng = self.cell.engine
        old = (self.params, self.opt_state)
        if self.cell.kind == "sampled":
            with TraceAnnotation("bench.draw_wait"):
                batches, iters, rec = self.prefetch.next()
            with TraceAnnotation("bench.phase0_epoch"):
                params, opt_state, losses, _, _ = eng.phase0_epoch(
                    self.params, self.opt_state, batches)
            nodes, ids = rec["nodes"], rec["ids"]
        else:
            iters = int(self.cell.spec.traffic["full_graph_iters"])
            with TraceAnnotation("bench.phase0_epoch"):
                params, opt_state, losses, _, _ = (
                    eng.phase0_fullgraph_epoch(self.params, self.opt_state,
                                               iters=iters))
            nodes, ids = self.cell.train_mask_count * iters, []
        losses = np.asarray(losses)
        preds = None
        if self._evals_left > 0:
            self._evals_left -= 1
            preds = self.cell.val_preds()
        if "frozen_state" in self.cell.faults:
            params, opt_state = old
        self.params, self.opt_state = params, opt_state
        return EpochOut(losses=losses.reshape(losses.shape[0], -1),
                        nodes=int(nodes),
                        steps=int(iters), batch_ids=ids, val_preds=preds)

    def close(self) -> None:
        if self.prefetch is not None:
            self.prefetch.close()
            self.prefetch = None
