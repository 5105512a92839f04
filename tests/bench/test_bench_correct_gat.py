"""``correct`` on the GAT full-graph cell, driven whole on the CPU at a
tiny size (``data/tiny-gat.json``: the published heads at a per-head width
of 8) under the cell's real name, so its real limits apply: true for the
program as it is, false for each fault of the timed path, and the
control (the reference in bfloat16) fails the cell's limits."""
import json
import shutil

import pytest

from _cpu_runs import control_verdict, run_cell
from conftest import DATA, REPO, TINY_TRAFFIC

CELL = "gat-flickr-fullgraph"


@pytest.fixture(scope="module")
def gat_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("gat-checkout")
    (root / "perfbench" / "traffic").mkdir(parents=True)
    (root / "perfbench" / "configs").mkdir(parents=True)
    shutil.copy(REPO / "perfbench" / "peaks.json", root / "perfbench")
    shutil.copy(DATA / "tiny-gat.json", root / "perfbench" / "configs")
    (root / "perfbench" / "traffic" / "tiny_full.json").write_text(
        json.dumps(TINY_TRAFFIC["tiny_full"]))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-gat", "source": "tests",
                         "file": "perfbench/configs/tiny-gat.json",
                         "reduced": [], "why": "tests"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-gat",
                           "traffic": "tiny_full", "chips": 1,
                           "why": "tests"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_sound_run_is_correct(gat_root):
    r = run_cell(gat_root, CELL, seed=2**33 + 7)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_nodes_per_s", "peak_hbm_gib",
                                 "setup_s"}


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch",
                                   "no_exchange", "altered_rows"])
def test_fault_is_not_correct(gat_root, fault):
    r = run_cell(gat_root, CELL, seed=19, faults=(fault,))
    assert not r["correct"], (fault, r["checks"])


def test_control_fails_the_limits(gat_root):
    assert not control_verdict(gat_root, CELL, seed=29)["ok"]
