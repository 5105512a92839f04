"""Halo-overlap benchmark — the perf trajectory for PR 3.

Times the distributed full-graph forward (the eval hot path: per-layer halo
exchange + mean aggregation + dense transforms) through the SYNCHRONOUS
engine (exchange fully serialises before aggregation, dense compute over the
whole padded local space) against the OVERLAPPED boundary/interior split
forward (DESIGN.md §5: exchange issued first, interior aggregation + the
self-term matmul run while it is in flight, dense compute restricted to
owned rows, static degrees, no edge-mask multiply), on `products-s` at 4
and 8 partitions.

On the single-device stacked fallback the collectives carry no latency to
hide, so the measured win is the split layout's structural work reduction
(halo rows here are 70-85% of the padded local space).  On a real mesh the
exchange additionally overlaps the interior work:

    PYTHONPATH=src python benchmarks/bench_halo_overlap.py --engine spmd

(``--engine spmd`` needs one device per partition; on a CPU host set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before starting.
The Pallas kernel runs compiled on a TPU and interpreted elsewhere.)

Emits ``results/BENCH_halo_overlap.json`` with per-config forward step
times, overlap/sync ratios, and the bytes each exchange moves (real halo
payload AND padded wire volume).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import numpy as np  # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_halo_overlap.json")

MODES = {"sync": dict(overlap_halo=False),
         "overlap": dict(overlap_halo=True),
         "overlap_ring": dict(overlap_halo=True, ring_chunks=4)}


def build_case(dataset: str, parts: int, seed: int):
    from repro.core import partition_graph
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS[dataset])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, parts,
                        method="ew", seed=seed)
    pg = build_partitioned_graph(g, r.parts, parts)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=64,
                      num_classes=g.num_classes)
    return g, pg, model, model.make_loss_fn(), AdamW(lr=1e-3)


def make_forward_step(eng, params):
    """AOT-compile the engine's raw distributed forward (no metrics) in its
    own execution mode and return a timed callable."""
    from repro.engine import AXIS

    if eng.mode == "spmd":
        from jax.sharding import PartitionSpec as P

        def shard_fn(prm, shard_s):
            sh = jax.tree.map(lambda x: x[0], shard_s)
            return eng.fwd(prm, sh)[None]

        fn = jax.shard_map(shard_fn, mesh=eng._mesh,
                           in_specs=(P(), P(AXIS)), out_specs=P(AXIS),
                           check_vma=False)
    else:
        def fn(prm, shards):
            return jax.vmap(eng.fwd, axis_name=AXIS,
                            in_axes=(None, 0))(prm, shards)

    compiled = jax.jit(fn).lower(params, eng.shards).compile()

    def step():
        jax.block_until_ready(compiled(params, eng.shards))

    return step


def time_step(step, repeats: int) -> dict:
    step()                                    # warm caches outside the window
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
    return {"forward_s_median": round(float(np.median(times)), 5),
            "forward_s_mean": round(float(np.mean(times)), 5),
            "forward_s_min": round(float(np.min(times)), 5)}


def run_parts(args, parts: int) -> list[dict]:
    from repro.core import GPHyperParams
    from repro.engine import EngineConfig, SPMDEngine
    from repro.kernels.segment_agg import default_interpret

    g, pg, model, loss_fn, opt = build_case(args.dataset, parts, args.seed)
    rows = []
    for mode, over_kw in MODES.items():
        cfg = EngineConfig(mode=args.engine, use_pallas_agg=args.pallas,
                           **over_kw)
        eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(), cfg)
        params = model.init(args.seed)
        row = {"dataset": args.dataset, "parts": parts, "mode": mode,
               "engine": eng.mode, "pallas_agg": args.pallas,
               "interpret": default_interpret(),
               "max_nodes": pg.max_nodes, "own_cap": pg.own_cap,
               "n_int": pg.n_int.tolist(),
               "n_boundary": pg.n_boundary.tolist(),
               "halo_bytes_per_layer": pg.halo_bytes_per_layer,
               "padded_wire_bytes_per_exchange":
                   pg.padded_wire_bytes_per_exchange}
        row.update(time_step(make_forward_step(eng, params), args.repeats))
        print(json.dumps(row))
        rows.append(row)
    return rows


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products-s")
    ap.add_argument("--parts", type=int, nargs="*", default=[4, 8])
    ap.add_argument("--engine", default="stacked",
                    choices=("stacked", "spmd"),
                    help="stacked single-device fallback (default) or "
                         "shard_map over a partition mesh")
    ap.add_argument("--pallas", action="store_true",
                    help="route aggregation through the Pallas kernel "
                         "(interpret mode is slow on CPU; default is the "
                         "jnp segment-op backend both sides)")
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.kernels.segment_agg import default_interpret

    rows = []
    for parts in args.parts:
        rows.extend(run_parts(args, parts))

    out = {"dataset": args.dataset, "engine": args.engine,
           "platform": jax.default_backend(),
           "interpret": default_interpret(), "configs": rows}
    ok = True
    for parts in args.parts:
        sync = next(r for r in rows
                    if r["parts"] == parts and r["mode"] == "sync")
        for mode in ("overlap", "overlap_ring"):
            ovl = next(r for r in rows
                       if r["parts"] == parts and r["mode"] == mode)
            ratio = round(ovl["forward_s_median"]
                          / max(1e-9, sync["forward_s_median"]), 3)
            out[f"{mode}_vs_sync_{parts}p"] = ratio
            if mode == "overlap":
                out[f"overlap_below_0p9_{parts}p"] = ratio <= 0.9
                ok &= ratio <= 0.9

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items() if k != "configs"},
                     indent=2))
    print(f"wrote {os.path.normpath(OUT_PATH)}")
    if not ok:
        print("WARNING: overlapped forward not <= 0.9x sync everywhere")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
