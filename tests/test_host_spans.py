"""The host spans change nothing they enclose: one seed's epoch draws,
through ``stack_epoch_batches`` and through ``_EpochPrefetcher``, are the
same batches, iteration counts and order under the profiler as without
it; and the worker's ``eat.draw`` span carries the draw's counters."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData

from repro.core import partition_graph
from repro.core.sampler import CBSampler
from repro.engine import stack_epoch_batches
from repro.graph import BENCHMARKS, NeighborSampler, make_benchmark
from repro.pipeline import _EpochPrefetcher

P, BATCH, EPOCHS = 4, 32, 3


def draws(via_prefetcher: bool):
    """``EPOCHS`` epoch draws from fresh samplers of one seed."""
    g = make_benchmark(BENCHMARKS["tiny"])
    parts = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                            method="ew", seed=0).parts
    neigh = NeighborSampler(g, fanouts=(3, 2), seed=5)
    samplers = [CBSampler(g.indptr, g.indices, g.labels,
                          g.train_idx[parts[g.train_idx] == p],
                          batch_size=BATCH, subset_fraction=0.25,
                          class_balanced=True, seed=5 + p) for p in range(P)]
    feats = np.asarray(g.features, np.float32)

    def make_batch(nodes):
        b = neigh.sample(nodes)
        x_t, x_1, x_2 = b.feature_views(feats)
        return {"x_t": jnp.asarray(x_t), "x_1": jnp.asarray(x_1),
                "x_2": jnp.asarray(x_2), "nodes": jnp.asarray(nodes)}

    draw = lambda: stack_epoch_batches(samplers, make_batch, P)
    if not via_prefetcher:
        return [draw() for _ in range(EPOCHS)]
    pre = _EpochPrefetcher(draw)
    try:
        return [pre.next() for _ in range(EPOCHS)]
    finally:
        pre.close()


def host(out):
    batches, _, iters = out
    return jax.tree.map(np.asarray, batches), iters


def profiled(fn, logdir):
    jax.profiler.start_trace(str(logdir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def assert_same(a, b):
    assert len(a) == len(b)
    for (ba, ia), (bb, ib) in zip(map(host, a), map(host, b)):
        assert ia == ib
        assert jax.tree.structure(ba) == jax.tree.structure(bb)
        for x, y in zip(jax.tree.leaves(ba), jax.tree.leaves(bb)):
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(x, y)


def test_draws_are_the_same_under_the_profiler(tmp_path):
    plain = draws(via_prefetcher=False)
    assert_same(plain, profiled(lambda: draws(False), tmp_path / "a"))
    assert_same(plain, draws(via_prefetcher=True))
    traced = profiled(lambda: draws(True), tmp_path / "b")
    assert_same(plain, traced)

    path, = glob.glob(os.path.join(tmp_path, "b", "**", "*.xplane.pb"),
                      recursive=True)
    # each draw runs on a thread, and so a trace line, of its own
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "eat.draw"]
    stats = [dict(ev.stats) for ev in sorted(events,
                                             key=lambda ev: ev.start_ns)]
    expect = []
    for batches, _, iters in traced:
        leaves = jax.tree.leaves(batches)
        expect.append({"batches": iters * P,
                       "bytes": sum(x.nbytes for x in leaves)})
    # the prefetcher starts the next epoch's draw as it hands one out
    assert stats[:EPOCHS] == expect
