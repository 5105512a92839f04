"""The sampled batches' feature rows gathered on the device: served from the
sampler's staged copy of its own graph table, bit for bit the numpy gather;
any other table is gathered in numpy; the feature store stages no table."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import partition_graph
from repro.core.sampler import CBSampler
from repro.engine import stack_epoch_batches
from repro.graph import BENCHMARKS, NeighborSampler, make_benchmark
from repro.pipeline import _EpochPrefetcher

P, BATCH, EPOCHS, FANOUTS = 4, 32, 3, (5, 3)


@pytest.fixture(scope="module")
def tiny():
    return make_benchmark(BENCHMARKS["tiny"])


def gather_spans(logdir) -> list[dict]:
    """Metadata of the ``eat.draw.gather`` spans in a profiler trace."""
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "eat.draw.gather"]
    return [dict(ev.stats) for ev in sorted(events, key=lambda e: e.start_ns)]


# table handed to feature_views -> (sampler stages, served on the device)
TABLES = {
    "own": (True, True),
    "own-unstaged": (False, False),
    "cast": (True, False),
    "other": (True, False),
}


@pytest.mark.parametrize("which", sorted(TABLES))
def test_feature_views_match_the_numpy_gather(tiny, which, tmp_path):
    stage, on_device = TABLES[which]
    feats = {"cast": lambda: np.asarray(tiny.features, np.float16),
             "other": lambda: tiny.features.copy()}.get(
                 which, lambda: tiny.features)()
    s = NeighborSampler(tiny, fanouts=FANOUTS, seed=3, stage_features=stage)
    b = s.sample(tiny.train_idx[:BATCH])
    jax.profiler.start_trace(str(tmp_path))
    try:
        views = b.feature_views(feats)
    finally:
        jax.profiler.stop_trace()

    f1, f2 = FANOUTS
    want = (feats[b.targets],
            feats[b.nbrs1.reshape(-1)].reshape(BATCH, f1, -1),
            feats[b.nbrs2.reshape(-1)].reshape(BATCH, f1, f2, -1))
    for got, ref in zip(views, want):
        assert isinstance(got, jax.Array) == on_device
        assert isinstance(got, np.ndarray) != on_device
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), ref)
    assert (s.table is not None) == on_device
    assert gather_spans(tmp_path) == [
        {"rows": BATCH * (1 + f1 + f1 * f2), "device": int(on_device)}]


def draws(g, stage: bool, via_prefetcher: bool):
    """``EPOCHS`` epoch draws from fresh samplers of one seed; the pipeline's
    make_batch gathering from the graph's own table."""
    parts = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                            method="ew", seed=0).parts
    neigh = NeighborSampler(g, fanouts=FANOUTS, seed=5, stage_features=stage)
    samplers = [CBSampler(g.indptr, g.indices, g.labels,
                          g.train_idx[parts[g.train_idx] == p],
                          batch_size=BATCH, subset_fraction=0.25,
                          class_balanced=True, seed=5 + p) for p in range(P)]

    def make_batch(nodes):
        b = neigh.sample(nodes)
        x_t, x_1, x_2 = b.feature_views(g.features)
        return {"x_t": jnp.asarray(x_t), "x_1": jnp.asarray(x_1),
                "x_2": jnp.asarray(x_2), "nodes": jnp.asarray(nodes)}

    draw = lambda: stack_epoch_batches(samplers, make_batch, P)
    if not via_prefetcher:
        return [draw() for _ in range(EPOCHS)], neigh
    pre = _EpochPrefetcher(draw)
    try:
        out = [pre.next() for _ in range(EPOCHS)]
    finally:
        pre.close()
    return out, neigh


def test_prefetched_device_draws_stack_the_host_gather_batches(tiny):
    host, unstaged = draws(tiny, stage=False, via_prefetcher=False)
    dev, neigh = draws(tiny, stage=True, via_prefetcher=True)
    assert unstaged.table is None
    assert isinstance(neigh.table, jax.Array)
    np.testing.assert_array_equal(np.asarray(neigh.table), tiny.features)
    assert len(host) == len(dev) == EPOCHS
    for (bh, _, ih), (bd, _, idv) in zip(host, dev):
        assert ih == idv
        assert jax.tree.structure(bh) == jax.tree.structure(bd)
        for x, y in zip(jax.tree.leaves(bh), jax.tree.leaves(bd)):
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("feat_store", [False, True])
def test_pipeline_stages_the_table_unless_feat_store(monkeypatch, feat_store):
    """The host-sampled pipeline stages one device copy of the feature table
    and gathers from it; under the feature store it stages none."""
    import repro.pipeline as pipeline

    made = []

    class Recorded(NeighborSampler):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(pipeline, "NeighborSampler", Recorded)
    pipeline.run_eat_distgnn(pipeline.EATConfig(
        dataset="tiny", num_parts=P, batch_size=BATCH, hidden_dim=16,
        fanouts=(3, 3), max_epochs=2, phase0_fraction=1.0, seed=3,
        use_pallas_agg=False, engine_mode="stacked", feat_store=feat_store))
    neigh, = made
    assert neigh.stage_features is not feat_store
    assert (neigh.table is None) == feat_store
