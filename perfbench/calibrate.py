#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one workload,
in one process at the cell's own size.

    python3 perfbench/calibrate.py --workload products-sampled \\
        --seeds 101,102,...,112 --control-seeds 101,102,103 \\
        --fault-seeds 101,102,103 --out readings.json

For each of ``--seeds``: the program's three checked epochs against the
plain reference (the lower readings).  For each of ``--control-seeds``:
the reference computed in bfloat16 put in the program's place (the
control).  For each fault of ``harness.FAULTS`` and each of
``--fault-seeds``: the program with that fault planted in its timed path.
A state left unchanged reads 1 on ``delta_gap`` by construction and is
read too, on the first fault seed.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def program_numbers(cell, seed: int) -> dict:
    from perfbench import harness, run

    trainer = harness.Trainer(cell, seed, record_epochs=run.CHECKED_EPOCHS)
    try:
        layers0 = run.host_tree(trainer.params0)
        prog, spec = run.checked_epochs(trainer)
    finally:
        trainer.close()
    return run.reference_numbers(cell, layers0, prog, spec)


def control_numbers(cell, seed: int) -> dict:
    """The reference in bfloat16 in the program's place: the same inputs
    (the program's sampled batches of this seed), its own outputs."""
    import jax.numpy as jnp

    from perfbench import check, harness, run
    from perfbench.reference import Reference

    trainer = harness.Trainer(cell, seed, record_epochs=run.CHECKED_EPOCHS)
    try:
        layers0 = run.host_tree(trainer.params0)
        _, spec = run.checked_epochs(trainer)
    finally:
        trainer.close()
    cfg, model = cell.spec.config, cell.spec.model
    low = Reference(cfg, model, cell.graph, cell.parts, dtype=jnp.bfloat16)
    ref = Reference(cfg, model, cell.graph, cell.parts, dtype=jnp.float32)
    rows = cell.val_ids
    return check.compare(low.run(layers0, spec, eval_rows=rows),
                         ref.run(layers0, spec, eval_rows=rows),
                         layers0, float(cfg["adam_b1"]))


def main(argv=None) -> int:
    from perfbench import harness, run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    run.enable_compile_cache(ROOT)

    spec = harness.load_cell(args.workload)
    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "program": {}, "control": {}, "faults": {}}

    def emit(kind, key, seed, numbers, t0):
        print(json.dumps({"kind": kind, "key": key, "seed": seed,
                          "numbers": numbers,
                          "seconds": round(time.perf_counter() - t0, 3)}),
              flush=True)

    cell = harness.Cell(spec)
    for s in args.seeds:
        t0 = time.perf_counter()
        out["program"][s] = program_numbers(cell, s)
        emit("program", "", s, out["program"][s], t0)
    # per chip (stderr): the program's peak; the first chip's holds the
    # reference's too
    run.peak_bytes(cell.devices)
    for s in args.control_seeds:
        t0 = time.perf_counter()
        out["control"][s] = control_numbers(cell, s)
        emit("control", "bfloat16", s, out["control"][s], t0)
    cell.engine = None
    cell.close()
    for fault in harness.FAULTS if args.fault_seeds else ():
        seeds = args.fault_seeds[:1] if fault == "frozen_state" \
            else args.fault_seeds
        fcell = harness.Cell(spec, faults=(fault,), like=cell)
        out["faults"][fault] = {}
        for s in seeds:
            t0 = time.perf_counter()
            out["faults"][fault][s] = program_numbers(fcell, s)
            emit("fault", fault, s, out["faults"][fault][s], t0)
        fcell.engine = None
        fcell.close()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
