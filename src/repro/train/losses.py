"""Loss functions: cross-entropy, focal loss (artifact's macro-F1 companion
to CBS), and the GP proximal penalty (paper Eq. 4)."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["cross_entropy_loss", "focal_loss", "prox_penalty", "multilabel_bce_loss"]

PyTree = Any


def _ordered_sum(x: jnp.ndarray, axis: int | None = None) -> jnp.ndarray:
    """Sum (of all elements, or along ``axis``) in a fixed pairwise order
    built from elementwise adds, so the result is bitwise the same whether
    or not the caller runs under ``vmap`` or ``scan`` (XLA vectorises a
    plain reduction differently in different programs, which moves the
    last ulp — and the fp64 parity oracles compare losses bitwise)."""
    if axis is None:
        x, axis = x.reshape(-1), 0
    x = jnp.moveaxis(x, axis, 0)
    n = 1
    while n < x.shape[0]:
        n *= 2
    x = jnp.pad(x, [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
    while x.shape[0] > 1:
        x = x[: x.shape[0] // 2] + x[x.shape[0] // 2:]
    return x[0]


def _log_softmax(logits: jnp.ndarray) -> jnp.ndarray:
    """``jax.nn.log_softmax`` over the class axis with the order-fixed sum,
    in at least float32 and in float64 for float64 logits."""
    z = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
    z = z - jax.lax.stop_gradient(z.max(axis=-1, keepdims=True))
    return z - jnp.log(_ordered_sum(jnp.exp(z), axis=-1))[..., None]


def _masked_mean(per_example: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Mean over the valid examples in the terms' precision (float64 for
    float64 logits), summed in a fixed order, returned as float32.

    XLA compiles the same loss a little differently inside different
    programs (a vmapped scan, a jitted loop), which moves the float64 mean
    by an ulp; rounding it to float32 removes that noise, so two programs
    computing the same loss report it bitwise equal."""
    w = valid.astype(per_example.dtype)
    mean = _ordered_sum(per_example * w) / jnp.maximum(_ordered_sum(w), 1.0)
    return mean.astype(jnp.float32)


def cross_entropy_loss(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    label_smoothing: float = 0.0,
) -> jnp.ndarray:
    """Mean softmax cross-entropy over (optionally masked) examples.

    ``labels`` are int class ids; entries < 0 are treated as padding and
    excluded (on top of ``mask`` if given).
    """
    valid = labels >= 0
    if mask is not None:
        valid = valid & (mask > 0)
    safe_labels = jnp.maximum(labels, 0)
    logp = _log_softmax(logits)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    if label_smoothing > 0.0:
        nll = (1.0 - label_smoothing) * nll - label_smoothing * logp.mean(axis=-1)
    return _masked_mean(nll, valid)


def focal_loss(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    gamma: float = 2.0,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Focal loss FL = (1-p_t)^γ · CE — down-weights easy (majority-class)
    examples; the artifact pairs it with CBS to lift macro-F1."""
    valid = labels >= 0
    if mask is not None:
        valid = valid & (mask > 0)
    safe_labels = jnp.maximum(labels, 0)
    logp = _log_softmax(logits)
    logpt = jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    pt = jnp.exp(logpt)
    fl = -jnp.power(1.0 - pt, gamma) * logpt
    return _masked_mean(fl, valid)


def multilabel_bce_loss(
    logits: jnp.ndarray, targets: jnp.ndarray, mask: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Sigmoid BCE for multilabel graphs (the paper's Yelp benchmark)."""
    logits = logits.astype(jnp.float32)
    per = jnp.maximum(logits, 0) - logits * targets + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    per = per.mean(axis=-1)
    if mask is None:
        return per.mean()
    w = mask.astype(jnp.float32)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def prox_penalty(personal_params: PyTree, global_params: PyTree) -> jnp.ndarray:
    """Eq. 4 regulariser: ‖W_P − W_G‖₂² summed over the whole pytree.

    ``global_params`` is the frozen phase-0 model (treated as a constant —
    callers should ``lax.stop_gradient`` it or simply not differentiate
    w.r.t. it, which is the default when it enters as a closure constant).
    """
    diffs = jax.tree.map(
        lambda p, g: _ordered_sum(jnp.square(p.astype(jnp.float32)
                                             - g.astype(jnp.float32))),
        personal_params,
        global_params,
    )
    return sum(jax.tree_util.tree_leaves(diffs))
