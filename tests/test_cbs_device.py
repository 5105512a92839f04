"""Statistical / property tier for the on-device CBS sampler.

The async personalization path replaces the host NumPy mini-epoch draw with
jax PRNG programs (core/sampler/cbs_device.py).  That machinery is
nondeterministic by design, so parity with the host sampler is proven
statistically rather than bit-for-bit:

  1. the jax Eq. 3 probability vector matches the NumPy reference to 1e-12
     (under x64) on seeds × {power-law, isolated-nodes, single-hub} graphs;
  2. a chi-squared test (n >= 50k draws, alpha = 1e-3) confirms the device
     categorical draw follows Eq. 3;
  3. the Gumbel top-k subset draw is a real without-replacement sample
     (distinct picks, exact size, zero-probability nodes never drawn);
  4. the async phase-1 path performs ZERO host mini-epoch draws — the
     call-counter check behind the "no host NumPy on the mini-epoch path"
     acceptance criterion — while staging the device draw;
  5. the phase-0 epoch draw (PR 5): chi-squared on 60k draws for the
     uniform path (end to end through ``draw_epoch``) AND the CBS-weighted
     path, plus permutation validity — each epoch visits each valid index
     at most once before the next key reshuffles;
  6. phase-0 host isolation: across async generalization epochs the host
     RNG draw counter stays at 0 and ``_EpochPrefetcher`` is never
     constructed.

All seeds are fixed: every assertion is deterministic.
"""
import jax
import numpy as np
import pytest
import scipy.stats

from repro.core.sampler import (cbs_probabilities, cbs_probabilities_device,
                                device_fanout, gumbel_subset)

# --------------------------------------------------------------------------
# adversarial graph profiles (the engine parity suite's degree shapes, plus
# imbalanced labels so the class-frequency division in Eq. 3 is exercised)
# --------------------------------------------------------------------------

KINDS = ["powerlaw", "isolated", "single_hub"]


def _graph(kind: str, seed: int, n: int = 300):
    import zlib

    rng = np.random.default_rng([seed, zlib.crc32(kind.encode())])
    if kind == "powerlaw":
        deg = np.minimum((1.0 / rng.power(2.0, n) - 1).astype(np.int64), 150)
        deg = np.maximum(deg, 0)
    elif kind == "isolated":
        deg = rng.integers(0, 6, n)
        deg[rng.random(n) < 0.5] = 0          # half the graph isolated
    elif kind == "single_hub":
        deg = rng.integers(0, 4, n)
        deg[int(rng.integers(0, n))] = 2000   # one hub dominating the mass
    else:
        raise ValueError(kind)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1])).astype(np.int64)
    labels = rng.choice(5, n, p=[0.45, 0.25, 0.15, 0.10, 0.05])
    train_idx = np.sort(rng.choice(n, int(0.7 * n), replace=False))
    return indptr, indices, labels, train_idx


# --------------------------------------------------------------------------
# 1. Eq. 3 parity: jax == NumPy to 1e-12
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_probabilities_match_host_1e12(kind, seed):
    indptr, indices, labels, train_idx = _graph(kind, seed)
    p_host = cbs_probabilities(indptr, indices, labels, train_idx)
    with jax.enable_x64():
        p_dev = np.asarray(
            cbs_probabilities_device(indptr, indices, labels, train_idx))
    assert p_dev.shape == p_host.shape
    assert np.abs(p_dev - p_host).max() < 1e-12
    assert abs(p_dev.sum() - 1.0) < 1e-12


def test_device_probabilities_zero_support_uniform():
    """All-isolated graph: Eq. 3 mass is zero everywhere -> uniform fallback,
    same contract as the host reference."""
    n = 40
    indptr = np.zeros(n + 1, np.int64)
    indices = np.zeros(0, np.int64)
    labels = np.zeros(n, np.int64)
    train_idx = np.arange(n)
    p_host = cbs_probabilities(indptr, indices, labels, train_idx)
    p_dev = np.asarray(
        cbs_probabilities_device(indptr, indices, labels, train_idx))
    np.testing.assert_allclose(p_dev, p_host, atol=1e-6)
    np.testing.assert_allclose(p_dev, 1.0 / n, atol=1e-6)


# --------------------------------------------------------------------------
# 2. chi-squared: the device categorical draw follows Eq. 3
# --------------------------------------------------------------------------

N_DRAWS = 60_000
ALPHA = 1e-3


def _merged_chisquare(counts: np.ndarray, probs: np.ndarray):
    """Pearson chi-squared with standard small-expectation bin merging
    (every merged bin keeps expected count >= 5)."""
    n = counts.sum()
    exp = probs * n
    order = np.argsort(exp)
    obs_m, exp_m = [], []
    acc_o = acc_e = 0.0
    for i in order:
        acc_o += counts[i]
        acc_e += exp[i]
        if acc_e >= 5.0:
            obs_m.append(acc_o)
            exp_m.append(acc_e)
            acc_o = acc_e = 0.0
    if acc_e > 0:                      # fold the tail into the last bin
        obs_m[-1] += acc_o
        exp_m[-1] += acc_e
    return scipy.stats.chisquare(np.asarray(obs_m), np.asarray(exp_m))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1])
def test_device_draw_follows_eq3(kind, seed):
    """The PRODUCTION draw (gumbel_subset, the Gumbel top-k behind
    draw_epoch) is chi-squared against Eq. 3: the first slot of the ranking
    is exactly a categorical(P) sample, so its frequencies over >=50k
    independent draws must match the probability vector."""
    import jax
    import jax.numpy as jnp

    indptr, indices, labels, train_idx = _graph(kind, seed)
    probs = cbs_probabilities(indptr, indices, labels, train_idx)
    with np.errstate(divide="ignore"):
        logp = jnp.asarray(np.log(probs), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed * 7919 + 13), N_DRAWS)
    first = jax.vmap(lambda k: gumbel_subset(k, logp, 1)[0])(keys)
    counts = np.bincount(np.asarray(first),
                         minlength=len(train_idx)).astype(np.float64)
    # zero-probability slots (isolated nodes) must never be drawn
    assert counts[probs == 0].sum() == 0
    res = _merged_chisquare(counts, probs)
    assert res.pvalue > ALPHA, (kind, seed, res)


# --------------------------------------------------------------------------
# 3. without-replacement subset properties (the mini-epoch draw)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_gumbel_subset_is_without_replacement(kind):
    import jax
    import jax.numpy as jnp

    indptr, indices, labels, train_idx = _graph(kind, 3)
    probs = cbs_probabilities(indptr, indices, labels, train_idx)
    with np.errstate(divide="ignore"):
        logp = jnp.asarray(np.log(probs), jnp.float32)
    support = int((probs > 0).sum())
    k = min(50, support)
    for s in range(5):
        pick = np.asarray(gumbel_subset(jax.random.PRNGKey(s), logp, k))
        assert len(np.unique(pick)) == k          # distinct slots
        assert (probs[pick] > 0).all()            # never a zero-prob node


def test_gumbel_subset_oversamples_minority():
    """Inclusion frequency under the subset draw still tracks Eq. 3: the
    rarest class's mean inclusion rate beats the majority's (the
    class-balancing claim, now on device)."""
    import jax
    import jax.numpy as jnp

    indptr, indices, labels, train_idx = _graph("powerlaw", 4)
    probs = cbs_probabilities(indptr, indices, labels, train_idx)
    with np.errstate(divide="ignore"):
        logp = jnp.asarray(np.log(probs), jnp.float32)
    k = len(train_idx) // 4
    incl = np.zeros(len(train_idx))
    reps = 400
    base = jax.random.PRNGKey(42)
    picks = jax.vmap(lambda kk: gumbel_subset(kk, logp, k))(
        jax.random.split(base, reps))
    for row in np.asarray(picks):
        incl[row] += 1
    incl /= reps
    tl = labels[train_idx]
    pop = np.bincount(tl, minlength=5) / len(tl)
    rare, major = int(np.argmin(pop)), int(np.argmax(pop))
    assert incl[tl == rare].mean() > incl[tl == major].mean()


def test_device_fanout_matches_host_semantics():
    """Fanout picks land inside each node's CSR span; isolated nodes
    self-loop (NeighborSampler's contract)."""
    import jax
    import jax.numpy as jnp

    indptr, indices, labels, train_idx = _graph("isolated", 5)
    nodes = jnp.asarray(train_idx[:64].astype(np.int32))
    nbrs = np.asarray(device_fanout(
        jax.random.PRNGKey(0), nodes, jnp.asarray(indptr, jnp.int32),
        jnp.asarray(indices, jnp.int32), 7))
    deg = (indptr[1:] - indptr[:-1])[train_idx[:64]]
    for i, v in enumerate(train_idx[:64]):
        if deg[i] == 0:
            assert (nbrs[i] == v).all()
        else:
            legal = set(indices[indptr[v]: indptr[v + 1]].tolist())
            assert set(nbrs[i].tolist()) <= legal


def test_epoch_sampler_caps_mini_epoch_at_support():
    """A partition whose mini-epoch size exceeds its positive-probability
    support must cap there: the staged epoch never marks a zero-probability
    (isolated) node as a valid training example."""
    import jax
    import jax.numpy as jnp

    from repro.core.sampler import build_device_epoch_sampler

    class G:
        pass

    n = 120
    g = G()
    # 30 connected nodes, 90 isolated -> Eq. 3 support is tiny
    rng = np.random.default_rng(0)
    deg = np.zeros(n, np.int64)
    deg[:30] = rng.integers(1, 4, 30)
    g.indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=g.indptr[1:])
    g.indices = rng.integers(0, 30, int(g.indptr[-1])).astype(np.int64)
    g.features = rng.normal(0, 1, (n, 8)).astype(np.float32)
    g.labels = rng.integers(0, 3, n)
    train = [np.arange(n), np.arange(20)]      # host 0: support << batch
    ds = build_device_epoch_sampler(g, train, 2, batch_size=64,
                                    subset_fraction=0.5, fanouts=(3, 3))
    for p in range(2):
        probs = np.exp(np.asarray(ds.logp[p], np.float64))
        support = int((np.asarray(ds.logp[p]) > -np.inf).sum())
        assert int(ds.k[p]) <= support
        nodes, valid = jax.tree.map(
            np.asarray,
            ds.draw_epoch(jax.random.PRNGKey(p), ds.logp[p],
                          ds.train_idx[p], ds.k[p]))
        picked = nodes[valid]
        assert len(picked) == int(ds.k[p])
        # every valid pick carries positive Eq. 3 probability (train sets are
        # arange here, so a node's slot in the padded row == its id)
        assert all(probs[int(v)] > 0 for v in picked)
        # valid examples stay PACKED in the leading slots: the partition's
        # natural_iters budgeted batches cover exactly its own mini-epoch
        flat = valid.reshape(-1)
        assert flat[: int(ds.k[p])].all() and not flat[int(ds.k[p]):].any()


# --------------------------------------------------------------------------
# 5. phase-0 epoch draw (the PR-5 generalization): uniform-path and
#    CBS-path statistics + the permutation-validity property
# --------------------------------------------------------------------------

def _phase0_sampler(class_balanced: bool, n: int = 160, seed: int = 6):
    """A DeviceEpochSampler staged the way the async phase-0 path stages it
    (build_device_epoch_sampler over a graph + per-host train sets)."""
    from repro.core.sampler import build_device_epoch_sampler

    class G:
        pass

    indptr, indices, labels, train_idx = _graph("powerlaw", seed, n)
    g = G()
    g.indptr, g.indices, g.labels = indptr, indices, labels
    g.features = np.random.default_rng(seed).normal(
        0, 1, (n, 8)).astype(np.float32)
    half = len(train_idx) // 2
    host_train = [train_idx[:half], train_idx[half:]]
    ds = build_device_epoch_sampler(
        g, host_train, 2, batch_size=32,
        subset_fraction=0.25 if class_balanced else 1.0,
        class_balanced=class_balanced, fanouts=(3, 3))
    return ds, host_train


def test_phase0_uniform_draw_is_uniform_chisquared():
    """The uniform (no-CBS) phase-0 path END TO END through the production
    ``draw_epoch``: the first batch slot of the drawn-and-shuffled epoch is
    a uniform categorical over the partition's train set — chi-squared on
    60k device draws."""
    import jax

    ds, host_train = _phase0_sampler(class_balanced=False)
    p = 0
    t = len(host_train[p])

    def first_slot(key):
        nodes, _ = ds.draw_epoch(key, ds.logp[p], ds.train_idx[p], ds.k[p])
        return nodes[0, 0]

    keys = jax.random.split(jax.random.PRNGKey(991), N_DRAWS)
    first = np.asarray(jax.vmap(first_slot)(keys))
    # every draw lands on a real train node of this partition
    assert set(first.tolist()) <= set(host_train[p].tolist())
    counts = np.zeros(t, np.float64)
    for i, v in enumerate(host_train[p]):
        counts[i] = (first == v).sum()
    res = _merged_chisquare(counts, np.full(t, 1.0 / t))
    assert res.pvalue > ALPHA, res


def test_phase0_cbs_draw_follows_eq3_chisquared():
    """The CBS-weighted phase-0 path: the first slot of the Gumbel top-k
    ranking over the sampler's STAGED per-partition log-Eq.3 row is exactly
    a categorical(Eq. 3) sample — chi-squared on 60k device draws against
    the staged probabilities (the shuffle on top is covered by the uniform
    end-to-end test and the permutation property below)."""
    import jax

    from repro.core.sampler import gumbel_subset

    ds, host_train = _phase0_sampler(class_balanced=True)
    p = 1
    logp = np.asarray(ds.logp[p], np.float64)
    probs = np.exp(logp)
    probs /= probs.sum()
    keys = jax.random.split(jax.random.PRNGKey(41), N_DRAWS)
    first = np.asarray(
        jax.vmap(lambda k: gumbel_subset(k, ds.logp[p], 1)[0])(keys))
    counts = np.bincount(first, minlength=len(probs)).astype(np.float64)
    assert counts[probs == 0].sum() == 0
    res = _merged_chisquare(counts, probs)
    assert res.pvalue > ALPHA, res


@pytest.mark.parametrize("class_balanced", [True, False])
def test_phase0_epoch_is_valid_permutation(class_balanced):
    """Permutation validity of the phase-0 epoch: within one epoch each
    valid index is visited AT MOST once (exactly k distinct nodes), the
    uniform path covers the full train set exactly once, and a fresh epoch
    key reshuffles (different batch order)."""
    import jax

    ds, host_train = _phase0_sampler(class_balanced=class_balanced)
    orders = []
    for p in range(2):
        for epoch in (0, 1, 2):
            key = jax.random.fold_in(jax.random.PRNGKey(17 + p), epoch)
            nodes, valid = jax.tree.map(
                np.asarray,
                ds.draw_epoch(key, ds.logp[p], ds.train_idx[p], ds.k[p]))
            picked = nodes.reshape(-1)[valid.reshape(-1)]
            assert len(picked) == int(ds.k[p])
            assert len(np.unique(picked)) == len(picked)   # no revisits
            assert set(picked.tolist()) <= set(host_train[p].tolist())
            if not class_balanced:
                # uniform epoch == one full pass over the local train set
                assert sorted(picked.tolist()) == sorted(
                    host_train[p].tolist())
            if p == 0:
                orders.append(tuple(picked.tolist()))
    # reshuffle across epochs: the three epoch orders are not all identical
    assert len(set(orders)) > 1


# --------------------------------------------------------------------------
# 4. the acceptance call-counter: async phase-1 never draws on host
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def async_run():
    from repro.core.sampler import cbs, cbs_device
    from repro.pipeline import EATConfig, run_eat_distgnn

    host_before = cbs.host_draw_count()
    dev_before = cbs_device.device_trace_count()
    cfg = EATConfig(dataset="tiny", num_parts=4, partition_method="ew",
                    use_cbs=True, use_gp=True, max_epochs=12, hidden_dim=32,
                    batch_size=64, fanouts=(3, 3), lr=3e-3, seed=0,
                    flatten_tol=0.08, async_personalize=True)
    result = run_eat_distgnn(cfg)
    return result, cbs_device.device_trace_count() - dev_before


def test_async_phase1_no_host_numpy_draw(async_run):
    result, dev_traces = async_run
    assert result.phase1_epochs > 0, "personalization never ran"
    assert result.host_draws_phase1 == 0, (
        f"{result.host_draws_phase1} host NumPy mini-epoch draws leaked "
        "onto the async phase-1 path")
    assert dev_traces > 0, "the device mini-epoch draw was never staged"


def test_async_phase1_still_learns(async_run):
    result, _ = async_run
    assert result.f1.micro > 0.30
    assert np.isfinite(result.loss_history).all()


# --------------------------------------------------------------------------
# 6. phase-0 host isolation: the fused generalization epoch never touches
#    the host RNG and never constructs the prefetcher
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def async_phase0_run():
    from repro import pipeline
    from repro.core.sampler import cbs, cbs_device
    from repro.pipeline import EATConfig, run_eat_distgnn

    class _ForbiddenPrefetcher:
        def __init__(self, *a, **k):
            raise AssertionError(
                "_EpochPrefetcher constructed on the fully-async path")

    host_before = cbs.host_draw_count()
    dev_before = cbs_device.device_trace_count()
    orig = pipeline._EpochPrefetcher
    pipeline._EpochPrefetcher = _ForbiddenPrefetcher
    try:
        cfg = EATConfig(dataset="tiny", num_parts=4, partition_method="ew",
                        use_cbs=True, use_gp=True, max_epochs=12,
                        hidden_dim=32, batch_size=64, fanouts=(3, 3),
                        lr=3e-3, seed=0, flatten_tol=0.08,
                        async_generalize=True, async_personalize=True)
        result = run_eat_distgnn(cfg)
    finally:
        pipeline._EpochPrefetcher = orig
    return (result, cbs.host_draw_count() - host_before,
            cbs_device.device_trace_count() - dev_before)


def test_async_phase0_no_host_numpy_draw(async_phase0_run):
    """Mirror of test_async_phase1_no_host_numpy_draw for generalization:
    across async phase-0 epochs the host RNG draw counter stays at 0, the
    device draw is demonstrably staged, and ``_EpochPrefetcher`` is never
    constructed (the fixture swaps in a constructor that raises)."""
    result, host_delta, dev_traces = async_phase0_run
    assert result.epochs_run > 0 and result.phase1_epochs > 0
    assert result.host_draws_phase0 == 0, (
        f"{result.host_draws_phase0} host NumPy epoch draws leaked onto "
        "the async phase-0 path")
    assert result.host_draws_phase1 == 0
    assert host_delta == 0, f"host RNG drew {host_delta} times"
    assert dev_traces > 0, "the device epoch draw was never staged"


def test_async_phase0_still_learns(async_phase0_run):
    result, _, _ = async_phase0_run
    assert result.f1.micro > 0.30
    assert np.isfinite(result.loss_history).all()
