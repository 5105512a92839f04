"""Two-tier feature store benchmark — the memory/traffic trajectory for PR 10.

Runs the full EAT pipeline on `products-s` with the feature tier in four
regimes: all-resident baseline, and the two-tier store at hot_frac 0.5 /
0.25 / 0.1 (degree-ordered hot set, cold rows staged from the pinned host
store per compiled call).  Each row records the resident device feature
bytes, the cold-row host-to-device bytes per epoch, wall time per epoch,
and the final test micro-F1.

The acceptance gate (ISSUE 10): at hot_frac=0.25 the resident feature
bytes must be <= 0.5x the all-resident baseline AND the test micro-F1
within +-0.005 of it.  The 0.5/0.1 rows are recorded for the trade-off
table, not gated.

The second table is the bigger-than-device witness on `featstore-xl`
(wide features): with a device feature budget set BELOW the all-resident
footprint, the no-store run must refuse to build (FeatureBudgetError)
while `--feat-store --feat-groups 1` streams the eval partition-by-
partition under the same budget and trains end to end.

Emits ``results/BENCH_featstore.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_featstore.json")

HOT_FRACS = (0.5, 0.25, 0.1)


def run_products(args) -> list[dict]:
    from repro.pipeline import EATConfig, run_eat_distgnn

    rows = []
    for hot_frac in (None, *HOT_FRACS):
        kw = {} if hot_frac is None else dict(feat_store=True,
                                              hot_frac=hot_frac)
        cfg = EATConfig(dataset=args.dataset, num_parts=args.parts,
                        partition_method="ew", use_cbs=True, use_gp=False,
                        max_epochs=args.epochs, hidden_dim=64,
                        batch_size=128, fanouts=(5, 5), lr=3e-3,
                        seed=args.seed, use_pallas_agg=False,
                        async_generalize=True, **kw)
        t0 = time.monotonic()
        r = run_eat_distgnn(cfg)
        wall = time.monotonic() - t0
        epochs = max(1, r.epochs_run)
        row = {"dataset": args.dataset, "parts": args.parts,
               "mode": "all_resident" if hot_frac is None
               else f"feat_store_{hot_frac}",
               "hot_frac": hot_frac, "epochs_run": r.epochs_run,
               "resident_feature_bytes": int(r.resident_feature_bytes),
               "cold_h2d_bytes_per_epoch":
                   round(r.cold_h2d_bytes / epochs, 1),
               "cold_h2d_mb_total": round(r.cold_h2d_bytes / 1e6, 3),
               "epoch_time_s": round(wall / epochs, 3),
               "test_micro": round(float(r.f1.micro), 4)}
        print(json.dumps(row))
        rows.append(row)
    return rows


def run_bigger_than_stack(args) -> dict:
    """featstore-xl under a device feature budget below the all-resident
    footprint: no-store refuses to build, the streamed store trains."""
    from repro.core import partition_graph
    from repro.graph import (BENCHMARKS, build_partitioned_graph,
                             make_benchmark)
    from repro.graph.featstore import FeatureBudgetError, feat_peak_bytes
    from repro.pipeline import EATConfig, run_eat_distgnn

    g = make_benchmark(BENCHMARKS["featstore-xl"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels,
                        args.parts, method="ew", seed=args.seed)
    pg = build_partitioned_graph(g, r.parts, args.parts)
    base_peak = feat_peak_bytes(args.parts, pg.max_nodes, g.feature_dim, 4)
    budget_mb = base_peak * 0.7 / 1e6

    kw = dict(dataset="featstore-xl", num_parts=args.parts,
              partition_method="ew", use_cbs=True, use_gp=False,
              max_epochs=args.xl_epochs, hidden_dim=64, batch_size=128,
              fanouts=(5, 5), lr=3e-3, seed=args.seed,
              use_pallas_agg=False, async_generalize=False,
              feat_budget_mb=budget_mb)
    no_store_raises = False
    try:
        run_eat_distgnn(EATConfig(**kw))
    except FeatureBudgetError as e:
        no_store_raises = True
        print(json.dumps({"no_store_refused": str(e)[:160]}))

    t0 = time.monotonic()
    res = run_eat_distgnn(EATConfig(**kw, feat_store=True, hot_frac=0.25,
                                    feat_groups=1))
    wall = time.monotonic() - t0
    row = {"dataset": "featstore-xl", "parts": args.parts,
           "feat_budget_mb": round(budget_mb, 3),
           "all_resident_peak_mb": round(base_peak / 1e6, 3),
           "no_store_raises": no_store_raises,
           "store_epochs_run": res.epochs_run,
           "store_resident_feature_bytes": int(res.resident_feature_bytes),
           "store_cold_h2d_mb": round(res.cold_h2d_bytes / 1e6, 3),
           "store_wall_s": round(wall, 1),
           "store_test_micro": round(float(res.f1.micro), 4)}
    print(json.dumps(row))
    return row


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products-s")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--xl-epochs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-xl", action="store_true")
    args = ap.parse_args()

    rows = run_products(args)
    out = {"dataset": args.dataset, "epochs": args.epochs, "configs": rows}

    base = next(r for r in rows if r["mode"] == "all_resident")
    ok = True
    for r in rows:
        if r["hot_frac"] is None:
            continue
        ratio = round(r["resident_feature_bytes"]
                      / max(1, base["resident_feature_bytes"]), 3)
        delta = round(r["test_micro"] - base["test_micro"], 4)
        out[f"resident_ratio_{r['hot_frac']}"] = ratio
        out[f"micro_delta_{r['hot_frac']}"] = delta
        if r["hot_frac"] == 0.25:
            gate = ratio <= 0.5 and abs(delta) <= 0.005
            out["featstore_gate_0.25"] = gate
            ok &= gate

    if not args.skip_xl:
        out["bigger_than_stack"] = run_bigger_than_stack(args)
        xl_ok = (out["bigger_than_stack"]["no_store_raises"]
                 and out["bigger_than_stack"]["store_epochs_run"] > 0)
        out["bigger_than_stack_gate"] = xl_ok
        ok &= xl_ok

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items() if k != "configs"},
                     indent=2))
    print(f"wrote {os.path.normpath(OUT_PATH)}")
    if not ok:
        print("WARNING: feature store failed the <=0.5x resident / +-0.005 "
              "micro-F1 gate or the bigger-than-stack witness")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
