"""Historical halo-cache benchmark — the wire trajectory for PR 6.

Runs the full EAT pipeline on `products-s` with the eval-forward halo
exchange in three regimes:

  sync         every distributed eval pays the full two-layer exchange
               (2 * halo_bytes_per_layer per epoch);
  cache_k4     historical-embedding cache, full refresh every 4th eval,
               pure-cached evals in between ship ZERO halo bytes;
  cache_k4_cv  VR-GCN-style control-variate refresh: the same cadence, but
               the evals between full refreshes each re-ship one rotating
               chunk of the slot space (fresher rows, more wire than plain
               caching, still far less than always-exchange).

The acceptance gate: mean halo bytes/epoch under cache_k4 must be <= 0.5x
the always-exchange baseline at 4 AND 8 partitions (the refresh cadence
makes this structural: 2 refreshes in 6 epochs -> ~0.33x).  Final micro-F1
is recorded per regime so the wire saving is visibly not bought with
accuracy collapse.

Emits ``results/BENCH_halo_cache.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_halo_cache.json")

MODES = {"sync": dict(),
         "cache_k4": dict(halo_cache=True, halo_refresh_every=4),
         "cache_k4_cv": dict(halo_cache=True, halo_refresh_every=4,
                             halo_cv=True)}


def run_parts(args, parts: int) -> list[dict]:
    from repro.pipeline import EATConfig, run_eat_distgnn

    rows = []
    for mode, halo_kw in MODES.items():
        cfg = EATConfig(dataset=args.dataset, num_parts=parts,
                        partition_method="ew", use_cbs=True, use_gp=False,
                        max_epochs=args.epochs, hidden_dim=64,
                        batch_size=128, fanouts=(5, 5), lr=3e-3,
                        seed=args.seed, use_pallas_agg=False,
                        async_generalize=True, **halo_kw)
        r = run_eat_distgnn(cfg)
        hist = r.halo_exchange_history
        row = {"dataset": args.dataset, "parts": parts, "mode": mode,
               "engine": r.engine_mode, "epochs_run": r.epochs_run,
               "halo_bytes_per_layer": r.halo_bytes_per_layer,
               "halo_exchange_history": hist,
               "halo_bytes_per_epoch_mean": round(float(np.mean(hist)), 1),
               "comm_halo_exchange_mb": round(sum(hist) / 1e6, 3),
               "test_micro": round(float(r.f1.micro), 4)}
        print(json.dumps(row))
        rows.append(row)
    return rows


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products-s")
    ap.add_argument("--parts", type=int, nargs="*", default=[4, 8])
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rows = []
    for parts in args.parts:
        rows.extend(run_parts(args, parts))

    out = {"dataset": args.dataset, "epochs": args.epochs, "configs": rows}
    ok = True
    for parts in args.parts:
        sync = next(r for r in rows
                    if r["parts"] == parts and r["mode"] == "sync")
        for mode in ("cache_k4", "cache_k4_cv"):
            c = next(r for r in rows
                     if r["parts"] == parts and r["mode"] == mode)
            ratio = round(c["halo_bytes_per_epoch_mean"]
                          / max(1e-9, sync["halo_bytes_per_epoch_mean"]), 3)
            out[f"{mode}_vs_sync_{parts}p"] = ratio
            out[f"{mode}_micro_delta_{parts}p"] = round(
                c["test_micro"] - sync["test_micro"], 4)
            if mode == "cache_k4":
                # the PR's acceptance gate; CV deliberately ships more wire
                # (fresher halo rows) so it is recorded, not gated
                out[f"cache_k4_below_0p5_{parts}p"] = ratio <= 0.5
                ok &= ratio <= 0.5

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items() if k != "configs"},
                     indent=2))
    print(f"wrote {os.path.normpath(OUT_PATH)}")
    if not ok:
        print("WARNING: cached halo bytes/epoch not <= 0.5x sync everywhere")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
