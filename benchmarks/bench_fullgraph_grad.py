"""Full-graph training benchmark — the perf trajectory for the
differentiable-aggregation PR (DESIGN.md §6).

Times one full-batch phase-0 train step (``value_and_grad`` through the
distributed forward: per-layer halo exchange, blocked mean aggregation and
its transpose-blocked backward, cross-partition gradient mean, optimizer
update) with the aggregation routed through the Pallas custom-VJP op
(``kernel`` path) against the jnp segment-op fallback (``jnp`` path), on
the centralized (1-partition, Table IV) configuration and the partitioned
fleet.

Off a TPU the kernel path runs in Pallas INTERPRET mode, which executes
the kernel body as plain XLA ops — the recorded kernel/jnp ratio is then a
correctness-witnessed stand-in, not a speedup claim.  On a TPU mesh:

    PYTHONPATH=src python benchmarks/bench_fullgraph_grad.py --engine spmd

Emits ``results/BENCH_fullgraph_train.json`` with per-config step times,
the kernel/jnp ratios, and trace evidence that BOTH the forward and the
backward Pallas kernels were staged on the differentiated path.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_fullgraph_train.json")


def build_case(dataset: str, parts: int, seed: int, hidden: int):
    from repro.core import partition_graph
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS[dataset])
    if parts == 1:
        parts_vec = np.zeros(g.num_nodes, dtype=np.int64)
    else:
        parts_vec = partition_graph(g.indptr, g.indices, g.features,
                                    g.labels, parts, method="ew",
                                    seed=seed).parts
    pg = build_partitioned_graph(g, parts_vec, parts)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=hidden,
                      num_classes=g.num_classes)
    return g, pg, model, model.make_loss_fn(), AdamW(lr=1e-3)


def time_fullgraph_steps(eng, model, seed: int, repeats: int):
    """phase0_fullgraph_epoch's returned dt is the compiled train-scan wall
    time only (AOT-compiled, eval excluded) — exactly the step metric."""
    params = model.init(seed)
    opt_state = eng.optimizer.init(params)
    eng.phase0_fullgraph_epoch(params, opt_state, iters=1)   # warm/AOT
    times = []
    for _ in range(repeats):
        params, opt_state, _, _, dt = eng.phase0_fullgraph_epoch(
            params, opt_state, iters=1)
        times.append(dt)
    return {"step_s_median": round(float(np.median(times)), 5),
            "step_s_mean": round(float(np.mean(times)), 5),
            "step_s_min": round(float(np.min(times)), 5)}


def run_parts(args, parts: int) -> list[dict]:
    from repro.core import GPHyperParams
    from repro.engine import EngineConfig, SPMDEngine
    from repro.kernels import segment_agg as sa

    g, pg, model, loss_fn, opt = build_case(args.dataset, parts, args.seed,
                                            args.hidden)
    rows = []
    for path, use_pallas in (("kernel", True), ("jnp", False)):
        cfg = EngineConfig(mode=args.engine, use_pallas_agg=use_pallas)
        eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(), cfg)
        before = sa.pallas_call_count()
        row = {"dataset": args.dataset, "parts": parts, "path": path,
               "engine": eng.mode, "interpret": sa.default_interpret(),
               "num_nodes": g.num_nodes, "num_edges": g.num_edges,
               "max_nodes": pg.max_nodes,
               "halo_bytes_per_layer": pg.halo_bytes_per_layer}
        row.update(time_fullgraph_steps(eng, model, args.seed, args.repeats))
        row["pallas_calls_staged"] = sa.pallas_call_count() - before
        if path == "kernel":
            # 2 layers x (fwd + transpose bwd) in the grad trace + eval fwd
            assert row["pallas_calls_staged"] >= 5, row
        print(json.dumps(row))
        rows.append(row)
    return rows


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products-s")
    ap.add_argument("--parts", type=int, nargs="*", default=[1, 4],
                    help="1 = the centralized Table IV configuration")
    ap.add_argument("--engine", default="stacked",
                    choices=("stacked", "spmd"))
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rows = []
    for parts in args.parts:
        rows.extend(run_parts(args, parts))

    import jax

    from repro.kernels.segment_agg import default_interpret

    out = {"dataset": args.dataset, "engine": args.engine,
           "platform": jax.default_backend(),
           "interpret": default_interpret(), "configs": rows}
    for parts in args.parts:
        ker = next(r for r in rows
                   if r["parts"] == parts and r["path"] == "kernel")
        jnp_ = next(r for r in rows
                    if r["parts"] == parts and r["path"] == "jnp")
        out[f"kernel_vs_jnp_{parts}p"] = round(
            ker["step_s_median"] / max(1e-9, jnp_["step_s_median"]), 3)

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items() if k != "configs"},
                     indent=2))
    print(f"wrote {os.path.normpath(OUT_PATH)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
