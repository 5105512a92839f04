"""Fault-tolerance benchmark — degraded-mode serving + recovery (PR 8).

Builds the serving engine from an ``SPMDEngine`` export on `products-s`
(P=4, stacked), then drives the same synthetic request stream as
``bench_serving.py`` through a scripted partition outage:

  healthy phase   — baseline p50/p99 tick latency and queries/s;
  degraded phase  — one partition failed: its queries answer from the
      frozen store with staleness tags, every update whose propagation
      cone touches it queues; p50/p99/QPS again (the whole point: the
      service keeps answering);
  recovery        — the partition comes back, the queued updates replay
      FIFO and flush in one tick; ``recovery_s`` is that tick's wall
      time, and the reconverged logits are checked BITWISE against a
      ``refresh_full()`` pass over the same store (the full-vs-
      incremental oracle).

Also records kill-and-resume behaviour of the training checkpointer on
the tiny benchmark: checkpoint save cost per epoch and resume-restart
cost (load + re-reaching the crashed epoch's state).

Emits ``results/BENCH_faults.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_faults.json")


def build(args):
    from repro.core import GPHyperParams, partition_graph
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.serve import GNNServingEngine
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS[args.dataset])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels,
                        args.parts, method="ew", seed=args.seed)
    pg = build_partitioned_graph(g, r.parts, args.parts)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=64,
                      num_classes=g.num_classes)
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                     GPHyperParams(),
                     EngineConfig(mode="stacked", use_pallas_agg=False))
    srv = GNNServingEngine.from_engine(eng, pg, model.init(args.seed))
    return g, srv


def drive(srv, g, rng, ticks, updates, queries):
    """Run the stream; returns (lat list, stale answers, queries asked)."""
    lat, stale = [], 0
    for _ in range(ticks):
        for v in rng.choice(g.num_nodes, updates, replace=False):
            srv.update_features(int(v), rng.normal(
                0, 1, g.feature_dim).astype(np.float32))
        srv.submit(rng.choice(g.num_nodes, queries, replace=False))
        t0 = time.perf_counter()
        _, st = srv.tick()
        lat.append(time.perf_counter() - t0)
        stale += len(st.get("staleness", {}))
    return lat, stale


def pctl(lat):
    p50, p99 = np.percentile(lat, [50, 99])
    return round(float(p50) * 1e3, 2), round(float(p99) * 1e3, 2)


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products-s")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=12)
    ap.add_argument("--updates-per-tick", type=int, default=4)
    ap.add_argument("--queries-per-tick", type=int, default=32)
    ap.add_argument("--fail-partition", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    g, srv = build(args)
    rng = np.random.default_rng(args.seed)
    U, Q = args.updates_per_tick, args.queries_per_tick

    # warm the jitted recompute/gather kernels out of the timed region
    drive(srv, g, rng, 2, U, Q)

    # ---- healthy baseline ----------------------------------------------
    t0 = time.time()
    lat_h, _ = drive(srv, g, rng, args.ticks, U, Q)
    qps_h = args.ticks * Q / (time.time() - t0)
    p50_h, p99_h = pctl(lat_h)

    # ---- degraded phase: one partition down ----------------------------
    srv.fail_partition(args.fail_partition)
    t0 = time.time()
    lat_d, stale = drive(srv, g, rng, args.ticks, U, Q)
    qps_d = args.ticks * Q / (time.time() - t0)
    p50_d, p99_d = pctl(lat_d)
    queued = srv.stats["updates_queued"]

    # ---- recovery: replay + flush in one tick --------------------------
    srv.recover_partition(args.fail_partition)
    t0 = time.perf_counter()
    srv.tick()
    recovery_s = time.perf_counter() - t0
    assert not srv._queue, "queue did not drain on recovery"

    # full-vs-incremental oracle: the replayed store must be bitwise a
    # from-scratch rematerialization of the same state
    inc = srv.export_logits()
    srv.refresh_full()
    reconverged = bool((inc == srv.export_logits()).all())

    # ---- training-side checkpoint/resume cost (tiny, f32 stacked) ------
    from repro.pipeline import EATConfig, run_eat_distgnn
    from repro.robustness import FaultPlan, InjectedCrash

    KW = dict(dataset="tiny", num_parts=4, batch_size=32, hidden_dim=16,
              fanouts=(3, 3), max_epochs=6, phase0_fraction=0.5,
              seed=args.seed, engine_mode="stacked")
    t0 = time.time()
    run_eat_distgnn(EATConfig(**KW))
    plain_s = time.time() - t0
    ck = tempfile.mkdtemp()
    t0 = time.time()
    try:
        run_eat_distgnn(EATConfig(**KW, checkpoint_dir=ck),
                        fault_plan=FaultPlan(crash_epochs=frozenset({4})))
    except InjectedCrash:
        pass
    crash_s = time.time() - t0
    t0 = time.time()
    res = run_eat_distgnn(EATConfig(**KW, checkpoint_dir=ck, resume=True))
    resume_s = time.time() - t0
    ckpt_bytes = sum(os.path.getsize(os.path.join(ck, n))
                     for n in os.listdir(ck))

    out = {"dataset": args.dataset, "parts": args.parts,
           "num_nodes": int(g.num_nodes), "ticks_per_phase": args.ticks,
           "updates_per_tick": U, "queries_per_tick": Q,
           "failed_partition": args.fail_partition,
           "healthy": {"p50_tick_ms": p50_h, "p99_tick_ms": p99_h,
                       "qps": round(float(qps_h), 1)},
           "degraded": {"p50_tick_ms": p50_d, "p99_tick_ms": p99_d,
                        "qps": round(float(qps_d), 1),
                        "stale_answers": int(stale),
                        "updates_queued": int(queued),
                        "replay_attempts": int(
                            srv.stats["replay_attempts"])},
           "recovery_s": round(float(recovery_s), 4),
           "replayed_updates": int(srv.stats["replayed"]),
           "reconverged_bitwise": reconverged,
           "train_resume": {
               "dataset": "tiny", "crash_epoch": 4,
               "uninterrupted_s": round(plain_s, 2),
               "run_to_crash_s": round(crash_s, 2),
               "resume_to_finish_s": round(resume_s, 2),
               "resumed_from_epoch": int(res.resumed_from_epoch),
               "checkpoint_dir_bytes": int(ckpt_bytes)}}

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print(f"wrote {os.path.normpath(OUT_PATH)}")
    if not reconverged:
        print("WARNING: post-recovery logits are not bitwise the full "
              "rematerialization")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
