"""Helpers for the tests that drive whole runs on the CPU at a tiny size:
``run.main`` with the look for a chip skipped and, where asked, a fault
planted in the timed path."""
import contextlib
import io
import json

import pytest


def run_cell(root, workload, seed, faults=()):
    from perfbench import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.2"], require_chip=False,
                      faults=faults, root=root, compile_cache=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def control_verdict(root, workload, seed):
    """The control (the reference in bfloat16 in the program's place) at
    the tiny size, judged by the cell's limits."""
    from perfbench import calibrate, check, harness

    spec = harness.load_cell(workload, root)
    cell = harness.Cell(spec)
    try:
        numbers = calibrate.control_numbers(cell, seed)
    finally:
        cell.close()
    return check.judge(numbers, check.load_limits(workload))


