"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only tableX]

Prints CSV rows `table,key=value,...` per experiment (see each module).
"""
from __future__ import annotations

import argparse
import sys
import time

from . import (fig1a_entropy_accuracy, fig3_convergence, kernels_micro,
               roofline, table2_overall, table3_scaling, table4_centralized,
               table5_partition_entropy)

MODULES = {
    "table5": table5_partition_entropy,
    "table2": table2_overall,
    "table3": table3_scaling,
    "table4": table4_centralized,
    "fig1a": fig1a_entropy_accuracy,
    "fig3": fig3_convergence,
    "kernels": kernels_micro,
    "roofline": roofline,
}


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(MODULES))
    args = ap.parse_args()
    names = [args.only] if args.only else list(MODULES)
    for name in names:
        print(f"# ---- {name} ----", flush=True)
        t0 = time.time()
        try:
            MODULES[name].main()
        except Exception as e:  # noqa: BLE001
            print(f"{name},status=error,error={e!r}", file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
