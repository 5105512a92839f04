"""The benchmark's generator is a faithful copy of the program's."""
import dataclasses

import numpy as np
import pytest

from perfbench import graphgen


@pytest.mark.parametrize("name", ["tiny", "products-s"])
def test_generator_matches_program_copy(name):
    from repro.graph.synthetic import BENCHMARKS, make_benchmark

    spec = BENCHMARKS[name]
    cfg = dataclasses.asdict(spec)
    cfg["graph_seed"] = cfg.pop("seed")
    ours, theirs = graphgen.generate(cfg), make_benchmark(spec)
    for key in ("indptr", "indices", "features", "labels", "train_idx",
                "val_idx", "test_idx"):
        np.testing.assert_array_equal(getattr(ours, key), getattr(theirs, key))
