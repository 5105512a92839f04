"""Property + engine tier for the two-tier feature store (DESIGN.md §12).

Locks the PR-10 tentpole's load-bearing invariants:

  · the hot/cold split gather is BITWISE equal to a direct full-feature
    gather — for arbitrary access patterns (duplicates, out-of-order),
    hot fractions including 0.0 and 1.0, and ragged partitions;
  · hot-set construction is a permutation (no row lost or duplicated);
  · the feat-store engine's eval is bitwise the all-resident engine's,
    and the feat_groups streamed eval is bitwise the sequential oracle's;
  · ``cold_h2d_bytes`` follows the closed-form ``cold_rows x D x itemsize``
    per staging, and ``hot_frac=1.0`` reports exactly the pre-PR-10
    counters (regression lock on the existing accounting).
"""
import functools

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import partition_graph
from repro.engine import EngineConfig, SPMDEngine, SequentialReference
from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                         make_benchmark)
from repro.graph.featstore import (FeatureBudgetError, assemble_features,
                                   build_global_feat_store,
                                   build_partition_feat_store,
                                   check_feat_budget, feat_peak_bytes,
                                   hot_order, reconstruct_features)
from repro.train.optim import AdamW

P = 4


# a plain cached builder, not a pytest fixture: hypothesis refuses
# function-scoped fixtures in @given-decorated tests
@functools.lru_cache(maxsize=1)
def _case():
    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, P)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    loss_fn = model.make_loss_fn()
    opt = AdamW(lr=3e-3, grad_clip=5.0)
    params = model.init(0)
    return g, pg, model, loss_fn, opt, params


@pytest.fixture(scope="module")
def case():
    return _case()


def _engine(case, **kw):
    g, pg, model, loss_fn, opt, params = case
    return SPMDEngine(model, loss_fn, opt, pg,
                      config=EngineConfig(mode="stacked", use_pallas_agg=False,
                                          **kw))


# ---------------------------------------------------------------- properties

@settings(max_examples=20, deadline=None)
@given(st.floats(0.0, 1.0), st.sampled_from(["degree", "freq"]))
def test_partition_split_reconstructs_bitwise(hot_frac, policy):
    """Scattering hot + cold tiers into a zero plane reproduces the ragged
    partitioned feature stack bitwise (the module invariant), and each
    partition's tier rows partition range(own_cap)."""
    pg = _case()[1]
    fs = build_partition_feat_store(pg, hot_frac, policy, np.float32)
    ref = np.asarray(pg.features, np.float32)
    rec = reconstruct_features(fs, pg.max_nodes)
    assert rec.shape == ref.shape
    assert (rec == ref).all()
    own_cap = pg.own_cap
    for p in range(P):
        rows = np.concatenate([fs.rows_hot[p], fs.rows_cold[p]])
        assert np.array_equal(np.sort(rows), np.arange(own_cap))


@settings(max_examples=20, deadline=None)
@given(st.floats(0.0, 1.0), st.sampled_from(["degree", "freq"]))
def test_partition_assemble_on_trace_bitwise(hot_frac, policy):
    """The ON-TRACE assembly (what the engine's compiled calls run) is
    bitwise the resident shard plane, hot_frac 0.0 and 1.0 included."""
    pg = _case()[1]
    fs = build_partition_feat_store(pg, hot_frac, policy, np.float32)
    ref = jnp.asarray(pg.features, jnp.float32)
    for p in range(P):
        plane = assemble_features(
            jnp.asarray(fs.hot[p]), jnp.asarray(fs.rows_hot[p]),
            jnp.asarray(fs.cold[p]), jnp.asarray(fs.rows_cold[p]),
            pg.max_nodes)
        assert (np.asarray(plane) == np.asarray(ref[p])).all()


@settings(max_examples=25, deadline=None)
@given(st.floats(0.0, 1.0), st.sampled_from(["degree", "freq"]),
       st.lists(st.integers(0, 599), min_size=1, max_size=64),
       st.booleans())
def test_global_store_gather_bitwise(hot_frac, policy, idx, dup):
    """Batch gathers through remap into [hot | cold] equal a direct gather
    from the full feature table — with duplicate and out-of-order indices
    (exactly what fanout sampling produces)."""
    g = _case()[0]
    gfs = build_global_feat_store(g, hot_frac, policy, np.float32)
    idx = np.asarray(idx, np.int64)
    if dup:  # force duplicates + reversal on top of the drawn pattern
        idx = np.concatenate([idx, idx[::-1]])
    table = np.concatenate([gfs.hot, gfs.cold], axis=0)
    direct = np.asarray(g.features, np.float32)[idx]
    assert (table[gfs.remap[idx]] == direct).all()


@settings(max_examples=10, deadline=None)
@given(st.floats(0.0, 1.0), st.sampled_from(["degree", "freq"]))
def test_global_store_is_permutation(hot_frac, policy):
    g = _case()[0]
    gfs = build_global_feat_store(g, hot_frac, policy, np.float32)
    ids = np.concatenate([gfs.hot_ids, gfs.cold_ids])
    assert np.array_equal(np.sort(ids), np.arange(g.num_nodes))
    # remap is the inverse permutation split at Nh
    assert np.array_equal(np.sort(gfs.remap), np.arange(g.num_nodes))
    nh = gfs.hot.shape[0]
    assert (gfs.remap[gfs.hot_ids] == np.arange(nh)).all()


def test_hot_order_deterministic_stable():
    scores = np.array([3.0, 1.0, 3.0, 2.0, 1.0])
    order = hot_order(scores)
    # descending score, ties broken by index (stable)
    assert order.tolist() == [0, 2, 3, 1, 4]
    assert np.array_equal(order, hot_order(scores))


def test_bad_hot_frac_and_policy_raise(case):
    pg = case[1]
    with pytest.raises(ValueError, match="hot_frac"):
        build_partition_feat_store(pg, 1.5, "degree", np.float32)
    with pytest.raises(ValueError, match="hot_policy"):
        build_partition_feat_store(pg, 0.5, "nope", np.float32)


# ------------------------------------------------------------ budget guard

def test_feat_budget_error_is_value_error():
    assert issubclass(FeatureBudgetError, ValueError)
    check_feat_budget(0.0, 10**12)          # disabled: never raises
    check_feat_budget(1.0, 999_999)         # under budget
    with pytest.raises(FeatureBudgetError, match="feat_budget_mb"):
        check_feat_budget(1.0, 1_000_001)


def test_feat_peak_bytes_monotone():
    base = feat_peak_bytes(4, 1000, 64, 4)
    store = feat_peak_bytes(4, 1000, 64, 4, hot_rows=100, cold_rows=900)
    streamed = feat_peak_bytes(4, 1000, 64, 4, hot_rows=100, cold_rows=900,
                               groups=1)
    assert streamed < store
    assert streamed < base
    assert base == 4 * 1000 * 64 * 4


def test_engine_refuses_over_budget(case):
    with pytest.raises(FeatureBudgetError):
        _engine(case, feat_budget_mb=1e-3)
    _engine(case, feat_budget_mb=10.0)   # generous budget builds fine


def test_streaming_passes_budget_all_resident_fails(case):
    """The bigger-than-stack gate in miniature: a budget between the
    streamed peak and the all-resident footprint."""
    g, pg = case[0], case[1]
    base_peak = feat_peak_bytes(P, pg.max_nodes, g.feature_dim, 4)
    budget_mb = base_peak * 0.6 / 1e6
    with pytest.raises(FeatureBudgetError):
        _engine(case, feat_budget_mb=budget_mb)
    eng = _engine(case, feat_store=True, hot_frac=0.25, feat_groups=1,
                  feat_budget_mb=budget_mb)
    assert eng.mode == "stacked"


# ------------------------------------------------------- engine-level locks

def test_feat_store_eval_bitwise_all_resident(case):
    params = case[5]
    base = _engine(case)
    fs = _engine(case, feat_store=True, hot_frac=0.25)
    for split in ("val", "test"):
        m0, p0 = base.evaluate(params, split, per_partition_params=False)
        m1, p1 = fs.evaluate(params, split, per_partition_params=False)
        assert (np.asarray(m0) == np.asarray(m1)).all()
        assert (np.asarray(p0) == np.asarray(p1)).all()


def test_streamed_eval_bitwise_sequential(case):
    g, pg, model, loss_fn, opt, params = case
    st_eng = _engine(case, feat_store=True, hot_frac=0.25, feat_groups=2)
    seq = SequentialReference(model, loss_fn, opt, pg,
                              config=EngineConfig(mode="sequential"))
    m0, p0 = st_eng.evaluate(params, "test", per_partition_params=False)
    m1, p1 = seq.evaluate(params, "test", per_partition_params=False)
    assert (np.asarray(m0) == np.asarray(m1)).all()
    assert (np.asarray(p0) == np.asarray(p1)).all()


def test_cold_bytes_closed_form(case):
    """k plain evals stage exactly k * P*C*D*B cold bytes; the streamed
    eval pays the deliberate 2x (pass A + pass B); hot_frac=1.0 is 0."""
    params = case[5]
    eng = _engine(case, feat_store=True, hot_frac=0.25)
    C = eng._fs.cold.shape[1]
    D = eng._fs.cold.shape[2]
    per_eval = P * C * D * np.dtype(np.float32).itemsize
    assert eng._fs.cold.nbytes == per_eval
    for k in range(1, 4):
        eng.evaluate(params, "val", per_partition_params=False)
        assert eng.cold_h2d_bytes == k * per_eval

    st_eng = _engine(case, feat_store=True, hot_frac=0.25, feat_groups=2)
    st_eng.evaluate(params, "val", per_partition_params=False)
    assert st_eng.cold_h2d_bytes == 2 * per_eval

    full = _engine(case, feat_store=True, hot_frac=1.0)
    assert full._fs.cold.shape[1] == 0
    full.evaluate(params, "val", per_partition_params=False)
    assert full.cold_h2d_bytes == 0


def test_async_cold_bytes_closed_form(case):
    """Fused async epochs: phase-0 stages the sampler's Nc*D*B cold table
    plus the fused eval's P*C*D*B; phase-1's train scan stages only the
    sampler table, its separate val eval the engine tier."""
    from repro.core import broadcast_to_partitions
    from repro.core.sampler import build_device_epoch_sampler

    g, pg, model, loss_fn, opt, params = case
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                        method="ew", seed=0)
    host_train = [g.train_idx[r.parts[g.train_idx] == p] for p in range(P)]
    eng = _engine(case, feat_store=True, hot_frac=0.25)
    ds = build_device_epoch_sampler(g, host_train, P, batch_size=32,
                                    fanouts=(3, 3), feat_store=True,
                                    hot_frac=0.25)
    eng.set_device_sampler(ds)
    opt_state = opt.init(params)
    keys = jax.random.split(jax.random.PRNGKey(1), P)
    eng.phase0_epoch_async(params, opt_state, keys)
    expect_p0 = ds.cold_host.nbytes + eng._fs.cold.nbytes
    assert eng.cold_h2d_bytes == expect_p0

    pp = broadcast_to_partitions(params, P)
    po = jax.vmap(opt.init)(pp)
    bud = jnp.asarray(np.full(P, 2, np.int32))
    eng.phase1_epoch_async(pp, po, keys, bud, params)
    assert eng.cold_h2d_bytes == expect_p0 + ds.cold_host.nbytes \
        + eng._fs.cold.nbytes


# ------------------------------------------------------------ config guards

def test_config_guards(case):
    g, pg, model, loss_fn, opt, params = case
    with pytest.raises(ValueError, match="feat_store"):
        _engine(case, feat_groups=2)                 # groups need the store
    with pytest.raises(ValueError, match="feat_groups"):
        _engine(case, feat_store=True, feat_groups=9)
    with pytest.raises(ValueError, match="stacked"):
        SPMDEngine(model, loss_fn, opt, pg,
                   config=EngineConfig(mode="spmd", feat_store=True,
                                       feat_groups=2))
    with pytest.raises(ValueError, match="pick one"):
        _engine(case, feat_store=True, feat_groups=2, halo_cache=True)
    with pytest.raises(ValueError, match="all-resident oracle"):
        SequentialReference(model, loss_fn, opt, pg,
                            config=EngineConfig(mode="sequential",
                                                feat_store=True))
    eng = _engine(case, feat_store=True, hot_frac=0.25)
    with pytest.raises(ValueError, match="full-graph"):
        eng.phase0_fullgraph_epoch(params, opt.init(params))
    # streamed engines reject the fused async phase-0 (the streamed eval
    # cannot live inside one device program)
    from repro.core.sampler import build_device_epoch_sampler
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                        method="ew", seed=0)
    host_train = [g.train_idx[r.parts[g.train_idx] == p] for p in range(P)]
    ds_fs = build_device_epoch_sampler(g, host_train, P, batch_size=32,
                                       fanouts=(3, 3), feat_store=True)
    st_eng = _engine(case, feat_store=True, hot_frac=0.25, feat_groups=2)
    st_eng.set_device_sampler(ds_fs)
    with pytest.raises(ValueError, match="feat_groups"):
        st_eng.phase0_epoch_async(params, opt.init(params),
                                  jax.random.split(jax.random.PRNGKey(0), P))


def test_pipeline_config_guards():
    from repro.pipeline import EATConfig, run_eat_distgnn
    with pytest.raises(ValueError, match="full_graph_train"):
        run_eat_distgnn(EATConfig(dataset="tiny", feat_store=True,
                                  full_graph_train=True))
    with pytest.raises(ValueError, match="async"):
        run_eat_distgnn(EATConfig(dataset="tiny", feat_store=True,
                                  feat_groups=2, async_generalize=True))


def test_sampler_engine_agreement(case):
    from repro.core.sampler import build_device_epoch_sampler
    g = case[0]
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                        method="ew", seed=0)
    host_train = [g.train_idx[r.parts[g.train_idx] == p] for p in range(P)]
    ds_plain = build_device_epoch_sampler(g, host_train, P, batch_size=32,
                                          fanouts=(3, 3))
    ds_fs = build_device_epoch_sampler(g, host_train, P, batch_size=32,
                                       fanouts=(3, 3), feat_store=True)
    eng = _engine(case, feat_store=True, hot_frac=0.25)
    with pytest.raises(ValueError, match="feat-store mismatch"):
        eng.set_device_sampler(ds_plain)
    base = _engine(case)
    with pytest.raises(ValueError, match="feat-store mismatch"):
        base.set_device_sampler(ds_fs)
    # make_batch's cold argument must match how the sampler was built
    with pytest.raises(ValueError, match="feat-store mismatch"):
        nodes = jnp.zeros((32,), jnp.int32)
        valid = jnp.ones((32,), jnp.float32)
        ds_fs.make_batch(jax.random.PRNGKey(0), nodes, valid)


# ----------------------------------------------- pipeline counter regression

def test_pipeline_hot_frac_one_matches_pre_store_counters():
    """hot_frac=1.0 keeps every row resident: the run must report exactly
    the counters (and micro-F1) of a no-store run — the regression lock on
    the pre-PR-10 accounting."""
    from repro.pipeline import EATConfig, run_eat_distgnn
    kw = dict(dataset="tiny", num_parts=P, batch_size=32, hidden_dim=16,
              fanouts=(3, 3), max_epochs=2, phase0_fraction=1.0, seed=3,
              use_pallas_agg=False, engine_mode="stacked")
    r0 = run_eat_distgnn(EATConfig(**kw))
    r1 = run_eat_distgnn(EATConfig(**kw, feat_store=True, hot_frac=1.0))
    assert r1.host_to_device_bytes_phase0 == r0.host_to_device_bytes_phase0
    assert r1.host_to_device_bytes_phase1 == r0.host_to_device_bytes_phase1
    assert r1.cold_h2d_bytes == 0
    assert r0.cold_h2d_bytes == 0
    assert r1.f1.micro == r0.f1.micro
    # hot_frac=1.0 keeps every OWN row resident; the hot tier is (P, own_cap,
    # D) while the resident plane is (P, max_nodes, D) incl. zero halo slots,
    # so the footprint may only shrink, never grow
    assert 0 < r1.resident_feature_bytes <= r0.resident_feature_bytes
