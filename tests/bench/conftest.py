"""The benchmark's tests: CPU only, tiny sizes, no chip topology described.

``tiny_root`` is a checkout-like directory holding a ``BENCHMARK.json``
whose cells carry the real cells' names (so the real limits of
``perfbench/limits`` apply) on a 600-node graph.  The four-chip cell runs
in a child process that asks for four host devices."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
DATA = Path(__file__).resolve().parent / "data"

TINY_TRAFFIC = {
    "tiny_sampled": {"kind": "sampled",
                     "partition": {"num_parts": 4, "method": "ew",
                                   "fanout_k": 5, "seed": 0},
                     "fanouts": [5, 3], "batch_size": 32,
                     "subset_fraction": 0.25, "class_balanced": True},
    "tiny_full": {"kind": "fullgraph",
                  "partition": {"num_parts": 4, "method": "ew",
                                "fanout_k": 5, "seed": 0},
                  "full_graph_iters": 1},
}
CELLS = {"products-sampled": "tiny_sampled", "flickr-fullgraph": "tiny_full",
         "products-x4-fullgraph": "tiny_full"}
# cells on more than one chip; run only where JAX has that many devices
CHIPS = {"products-x4-fullgraph": 4}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("checkout")
    (root / "perfbench" / "traffic").mkdir(parents=True)
    (root / "perfbench" / "configs").mkdir(parents=True)
    shutil.copy(REPO / "perfbench" / "peaks.json", root / "perfbench")
    shutil.copy(DATA / "tiny.json", root / "perfbench" / "configs")
    for name, t in TINY_TRAFFIC.items():
        (root / "perfbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "perfbench/configs/tiny.json",
                         "reduced": [], "why": "tests"}]
    bench["workloads"] = [{"name": w, "config": "tiny", "traffic": t,
                           "chips": CHIPS.get(w, 1), "why": "tests"}
                          for w, t in CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
