"""``correct`` on the sampled cell, driven whole on the CPU at a tiny size:
true for the program as it is, false for each fault of the timed path,
and the control (the reference in bfloat16) fails the cell's limits."""
import pytest

from _cpu_runs import control_verdict, run_cell

CELL = "products-sampled"


def test_sound_run_is_correct(tiny_root):
    r = run_cell(tiny_root, CELL, seed=2**33 + 5)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_nodes_per_s", "peak_hbm_gib",
                                 "setup_s"}


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch",
                                   "no_exchange", "altered_rows"])
def test_fault_is_not_correct(tiny_root, fault):
    r = run_cell(tiny_root, CELL, seed=17, faults=(fault,))
    assert not r["correct"], (fault, r["checks"])


def test_control_fails_the_limits(tiny_root):
    assert not control_verdict(tiny_root, CELL, seed=23)["ok"]
