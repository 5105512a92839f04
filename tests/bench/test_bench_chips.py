"""The cell's ``chips`` decides the engine, on a host with four devices (a
child process with ``--xla_force_host_platform_device_count=4``): a
one-chip cell vmaps every partition on the first device, a four-chip
cell puts one partition on each device under ``shard_map``.  The
four-chip cell driven whole at a tiny size: ``correct`` true as the
program is, false for each fault of its timed path, the exchange between
chips among them."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import REPO

FAULTS = ("frozen_state", "half_batch", "no_exchange", "altered_rows")
CHILD = r'''
import json, sys
root = sys.argv[1]
sys.path[:0] = [sys.argv[2]]
import jax
from perfbench import harness
from _cpu_runs import run_cell

out = {"devices": len(jax.devices()), "cells": {}, "runs": {}}
for name in ("flickr-fullgraph", "products-x4-fullgraph"):
    cell = harness.Cell(harness.load_cell(name, root))
    res = cell.engine._resident["shards"]["features"]
    out["cells"][name] = {
        "mode": cell.engine.mode,
        "devices": sorted(d.id for d in cell.devices),
        "holds": sorted(d.id for d in res.sharding.device_set)}
    cell.close()
for fault in ("",) + tuple(sys.argv[3].split(",")):
    r = run_cell(root, "products-x4-fullgraph", 2**33 + 11,
                 faults=(fault,) if fault else ())
    out["runs"][fault] = {"correct": r["correct"], "checks": r["checks"],
                          "count": r["device"]["count"]}
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def four_devices(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(tiny_root),
         str(Path(__file__).resolve().parent), ",".join(FAULTS)],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert p.returncode == 0, p.stderr[-4000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_one_chip_cell_is_stacked_on_one_device(four_devices):
    assert four_devices["devices"] == 4
    c = four_devices["cells"]["flickr-fullgraph"]
    assert c == {"mode": "stacked", "devices": [0], "holds": [0]}


def test_four_chip_cell_is_spmd_over_four(four_devices):
    c = four_devices["cells"]["products-x4-fullgraph"]
    assert c == {"mode": "spmd", "devices": [0, 1, 2, 3],
                 "holds": [0, 1, 2, 3]}


def test_four_chip_sound_run_is_correct(four_devices):
    r = four_devices["runs"][""]
    assert r["correct"], r["checks"]
    assert r["count"] == 4


@pytest.mark.parametrize("fault", FAULTS)
def test_four_chip_fault_is_not_correct(four_devices, fault):
    r = four_devices["runs"][fault]
    assert not r["correct"], (fault, r["checks"])
