"""Serving driver (CLI): batched generation with any zoo architecture, or
the partitioned GNN inference service.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --batch 4 --prompt-len 32 --new-tokens 16 [--swa]

    PYTHONPATH=src python -m repro.launch.serve --gnn --dataset tiny \
        --parts 4 --ticks 20 --updates-per-tick 4 --queries-per-tick 16 \
        [--checkpoint results/ckpt.msgpack]

On CPU the transformer path runs the REDUCED config; on TPU hardware the
same ServeEngine steps are what the decode dry-run shapes lower for the
production mesh.  The GNN path precomputes per-partition layer embeddings
from an ``SPMDEngine`` export, then serves a synthetic request stream of
feature updates + logit queries with incremental recomputation.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import Transformer
from repro.serve import ServeEngine


def gnn_main(args) -> int:
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
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=args.hidden,
                      num_classes=g.num_classes)
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                     GPHyperParams(),
                     EngineConfig(mode="stacked", use_pallas_agg=False))
    if args.checkpoint:
        srv = GNNServingEngine.from_checkpoint(args.checkpoint, eng, pg)
    else:
        srv = GNNServingEngine.from_engine(eng, pg, model.init(args.seed))
    print(f"{g.name}: {g.num_nodes} nodes, P={args.parts}, "
          f"{model.num_layers}-layer SAGE, store ready "
          f"(halo rows live in recv-slot geometry)")

    if args.fail_partition >= 0:
        from repro.robustness import FaultPlan
        fail_tick = max(1, args.fail_at_tick)
        srv.set_fault_plan(FaultPlan(
            serve_fail={fail_tick: (args.fail_partition,)},
            serve_recover={fail_tick + args.recover_after_ticks:
                           (args.fail_partition,)}))
        print(f"fault plan: partition {args.fail_partition} fails at tick "
              f"{fail_tick}, recovers after {args.recover_after_ticks} ticks")

    rng = np.random.default_rng(args.seed)
    lat = []
    stale_answers = 0
    t_start = time.time()
    for _ in range(args.ticks):
        for v in rng.choice(g.num_nodes, args.updates_per_tick,
                            replace=False):
            srv.update_features(int(v), rng.normal(
                0, 1, g.feature_dim).astype(np.float32))
        srv.submit(rng.choice(g.num_nodes, args.queries_per_tick,
                              replace=False))
        t0 = time.perf_counter()
        _, tick_stats = srv.tick()
        lat.append(time.perf_counter() - t0)
        stale_answers += len(tick_stats.get("staleness", {}))
    wall = time.time() - t_start
    qps = args.ticks * args.queries_per_tick / wall
    p50, p99 = np.percentile(lat, [50, 99])
    s = srv.stats
    print(f"{args.ticks} ticks x ({args.updates_per_tick} updates + "
          f"{args.queries_per_tick} queries): p50 {p50 * 1e3:.1f} ms, "
          f"p99 {p99 * 1e3:.1f} ms, {qps:.0f} queries/s")
    print(f"rows recomputed {s['rows_recomputed']}, gather calls "
          f"{s['gather_calls']}, halo rows grown {s['halo_rows_grown']}")
    if s["failovers"] or s["updates_queued"]:
        print(f"degraded mode: {s['failovers']} failover(s), "
              f"{s['degraded_queries']} degraded queries "
              f"({stale_answers} stale answers), {s['updates_queued']} "
              f"updates queued, {s['replay_attempts']} replay attempts, "
              f"{s['replayed']} replayed after {s['recoveries']} "
              f"recovery(ies); final health {srv.health}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gnn", action="store_true",
                    help="serve the partitioned GNN instead of a "
                         "transformer")
    ap.add_argument("--dataset", default="tiny")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--ticks", type=int, default=20)
    ap.add_argument("--updates-per-tick", type=int, default=4)
    ap.add_argument("--queries-per-tick", type=int, default=16)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--fail-partition", type=int, default=-1,
                    help="GNN degraded-mode demo: fail this partition "
                         "mid-stream (queries keep answering from its "
                         "frozen store, updates queue)")
    ap.add_argument("--fail-at-tick", type=int, default=5)
    ap.add_argument("--recover-after-ticks", type=int, default=8)
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--swa", action="store_true",
                    help="rolling sliding-window cache serving variant")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    if args.gnn:
        return gnn_main(args)

    cfg = get_config(args.arch, "swa" if args.swa else None).reduced()
    model = Transformer(cfg)
    params = model.init(args.seed)
    rng = np.random.default_rng(args.seed)

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.prefix_tokens:
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(0, 1, (args.batch, cfg.prefix_tokens, cfg.d_model)),
            jnp.float32)
    if cfg.is_encoder_decoder:
        batch["enc_embeds"] = jnp.asarray(
            rng.normal(0, 1, (args.batch, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)

    rolling = args.swa and cfg.sliding_window is not None
    cache = (cfg.sliding_window if rolling
             else args.prompt_len + args.new_tokens + 4)
    engine = ServeEngine(model, params, cache_size=cache, rolling=rolling)
    t0 = time.time()
    out = engine.generate(batch, max_new_tokens=args.new_tokens,
                          temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    tps = out.size / dt
    print(f"{cfg.name}: {out.shape[0]} seqs x {out.shape[1]} tokens "
          f"in {dt:.2f}s ({tps:.1f} tok/s, reduced config on CPU)")
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
