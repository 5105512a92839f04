import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.train.losses import cross_entropy_loss, focal_loss, prox_penalty
from repro.train.metrics import f1_scores, f1_scores_jnp
from repro.models.transformer import chunked_ce_loss


# ---------------------------------------------------------------- metrics --

def test_f1_perfect():
    preds = np.array([0, 1, 2, 2, 1])
    r = f1_scores(preds, preds, 3)
    assert r.micro == r.macro == r.weighted == 1.0


def test_f1_known_case():
    # classic 2-class example
    labels = np.array([0, 0, 0, 1, 1])
    preds = np.array([0, 0, 1, 1, 0])
    r = f1_scores(preds, labels, 2)
    # class0: tp=2 fp=1 fn=1 -> f1=2*2/(4+1+1)=0.8/..: 4/(4+2)=0.666..? compute:
    # f1_0 = 2*2/(2*2+1+1)=4/6; f1_1 = 2*1/(2*1+1+1)=2/4
    assert r.per_class[0] == pytest.approx(4 / 6)
    assert r.per_class[1] == pytest.approx(0.5)
    assert r.micro == pytest.approx(3 / 5)          # accuracy
    assert r.weighted == pytest.approx((4 / 6) * 0.6 + 0.5 * 0.4)


def test_f1_ignores_unlabelled():
    labels = np.array([0, 1, -1, -1])
    preds = np.array([0, 1, 1, 0])
    assert f1_scores(preds, labels, 2).micro == 1.0


@given(st.integers(1, 500))
@settings(max_examples=25, deadline=None)
def test_f1_jnp_matches_numpy(seed):
    rng = np.random.default_rng(seed)
    n, k = 200, 6
    labels = rng.integers(0, k, n)
    preds = rng.integers(0, k, n)
    r = f1_scores(preds, labels, k)
    micro, macro, weighted = f1_scores_jnp(jnp.asarray(preds),
                                           jnp.asarray(labels), k)
    assert float(micro) == pytest.approx(r.micro, abs=1e-5)
    assert float(macro) == pytest.approx(r.macro, abs=1e-5)
    assert float(weighted) == pytest.approx(r.weighted, abs=1e-5)


def test_micro_f1_is_accuracy():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 300)
    preds = rng.integers(0, 4, 300)
    assert f1_scores(preds, labels, 4).micro == pytest.approx(
        (preds == labels).mean())


# ----------------------------------------------------------------- losses --

def test_ce_uniform_logits():
    logits = jnp.zeros((8, 10))
    labels = jnp.arange(8) % 10
    assert float(cross_entropy_loss(logits, labels)) == pytest.approx(
        np.log(10), abs=1e-5)


def test_ce_masks_negative_labels():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(6, 4)),
                         jnp.float32)
    labels = jnp.array([0, 1, 2, -1, -1, -1])
    a = cross_entropy_loss(logits, labels)
    b = cross_entropy_loss(logits[:3], labels[:3])
    assert float(a) == pytest.approx(float(b), rel=1e-6)


def test_focal_downweights_easy():
    """Well-classified example contributes far less under focal loss."""
    easy = jnp.array([[10.0, 0.0]])
    hard = jnp.array([[0.5, 0.0]])
    lab = jnp.array([0])
    ce_ratio = float(cross_entropy_loss(hard, lab) / cross_entropy_loss(easy, lab))
    fl_ratio = float(focal_loss(hard, lab) / focal_loss(easy, lab))
    assert fl_ratio > 10 * ce_ratio


def test_prox_penalty_zero_at_global():
    p = {"a": jnp.ones((3, 3)), "b": {"c": jnp.zeros(5)}}
    assert float(prox_penalty(p, p)) == 0.0
    q = jax.tree.map(lambda x: x + 1.0, p)
    assert float(prox_penalty(q, p)) == pytest.approx(9 + 5)


def test_chunked_ce_matches_dense():
    rng = np.random.default_rng(0)
    t, d, v = 64, 16, 50
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, v)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, t), jnp.int32)
    labels = labels.at[5].set(-1)
    want = cross_entropy_loss(h @ w, labels)
    for chunk in (8, 16, 64, 37):
        got = chunked_ce_loss(h, w, labels, chunk=chunk)
        assert float(got) == pytest.approx(float(want), rel=1e-5), chunk


def test_chunked_ce_grad_matches_dense():
    rng = np.random.default_rng(1)
    t, d, v = 32, 8, 20
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, v)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, t), jnp.int32)
    g1 = jax.grad(lambda w_: chunked_ce_loss(h, w_, labels, chunk=8))(w)
    g2 = jax.grad(lambda w_: cross_entropy_loss(h @ w_, labels))(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_f1_out_of_range_preds_are_fn_only():
    """An out-of-range prediction names no class: fn on the true class,
    fp nowhere — and the numpy path must not crash or wrap indices."""
    labels = np.array([0, 1, 1])
    preds = np.array([-3, 2, 0])       # negative, == num_classes, valid-miss
    tp_fp_fn = f1_scores(preds, labels, 2)
    # class 0: tp=0 fp=1(from pred 0 on label 1) fn=1; class 1: tp=0 fp=0 fn=2
    assert tp_fp_fn.per_class.tolist() == [0.0, 0.0]
    micro, macro, weighted = f1_scores_jnp(jnp.asarray(preds),
                                           jnp.asarray(labels), 2)
    assert float(micro) == pytest.approx(tp_fp_fn.micro, abs=1e-6)
    # a negative pred must NOT be counted as class 0: one real class-0 fp
    # (the valid miss), not two
    labels2 = np.array([1, 1])
    preds2 = np.array([-1, 0])
    m_np = f1_scores(preds2, labels2, 2)
    m_j = f1_scores_jnp(jnp.asarray(preds2), jnp.asarray(labels2), 2)
    assert float(m_j[0]) == pytest.approx(m_np.micro, abs=1e-6)


@given(st.integers(1, 500))
@settings(max_examples=25, deadline=None)
def test_f1_jnp_matches_numpy_adversarial(seed):
    """Parity sweep with adversarial preds: negatives, == num_classes,
    beyond num_classes, mixed with unlabelled and all-invalid labels."""
    rng = np.random.default_rng(seed)
    n, k = 120, 5
    labels = rng.integers(0, k, n)
    labels[rng.random(n) < 0.3] = -1          # unlabelled mix
    if seed % 5 == 0:
        labels[:] = -1                        # all-invalid labels
    preds = rng.integers(-2, k + 2, n)        # includes -2..-1 and k..k+1
    r = f1_scores(preds, labels, k)
    micro, macro, weighted = f1_scores_jnp(jnp.asarray(preds),
                                           jnp.asarray(labels), k)
    assert float(micro) == pytest.approx(r.micro, abs=1e-5)
    assert float(macro) == pytest.approx(r.macro, abs=1e-5)
    assert float(weighted) == pytest.approx(r.weighted, abs=1e-5)


def test_f1_all_preds_out_of_range():
    labels = np.array([0, 1, 2])
    preds = np.array([3, 4, -1])
    r = f1_scores(preds, labels, 3)
    assert r.micro == 0.0 and r.macro == 0.0 and r.weighted == 0.0
    micro, macro, weighted = f1_scores_jnp(jnp.asarray(preds),
                                           jnp.asarray(labels), 3)
    assert float(micro) == 0.0 and float(macro) == 0.0
