"""The comparison that decides ``correct``: a run's first checked epochs
against the plain reference (``reference.py``) that followed them.

Four numbers, each a gap between two readings of the same quantity, taken
by the worst case:

  loss_gap    each step's per-partition loss, relative to the reference's;
  grad_gap    per weight leaf, the norm of the first gradient as the
              optimizer got it (its first moment after the first epoch over
              ``1 - b1``), the gap between the program's norm and the
              reference's relative to the larger of the reference's norm of
              that leaf and the median leaf's;
  delta_gap   per weight leaf, the norm of the change the checked epochs
              made, the same way.  Leaves whose reference gradient is under
              a thousandth of the median leaf's are left out: Adam moves
              them by round-off alone;
  eval_gap    after each checked epoch, per validation node, how far the
              reference's logit of the class the program's validation
              forward put first lies below the reference's best, relative
              to the median spread (best minus worst) of the reference's
              logits over those nodes; the widest over nodes and epochs.
              A near-tie that rounding flips reads near 0.

The limits are per workload, in ``limits/<workload>.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

__all__ = ["NUMBERS", "compare", "eval_gap", "load_limits", "judge"]

NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "eval_gap")
LIMITS = Path(__file__).resolve().parent / "limits"


def _leaves(layers) -> dict[str, np.ndarray]:
    return {f"l{i}.{k}": np.asarray(v, np.float64)
            for i, lp in enumerate(layers) for k, v in sorted(lp.items())}


def _norms(layers) -> dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(layers).items()}


def _worst_gap(prog: dict, ref: dict, keys) -> float:
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def eval_gap(preds: list, logits: list) -> float:
    """Per epoch, the program's class per validation node (``preds``) and
    the reference's logits of those nodes (``logits``, (nodes, C))."""
    if len(preds) != len(logits) or not preds:
        return float("inf")
    gap = 0.0
    for p, lg in zip(preds, logits):
        p, lg = np.asarray(p), np.asarray(lg, np.float64)
        if p.shape != lg.shape[:1] or p.min(initial=0) < 0 \
                or p.max(initial=0) >= lg.shape[1]:
            return float("inf")
        best = lg.max(axis=1)
        got = lg[np.arange(len(p)), p]
        scale = float(np.median(best - lg.min(axis=1)))
        gap = max(gap, float(np.max(best - got, initial=0.0))
                  / max(scale, 1e-30))
    return gap


def compare(prog: dict, ref: dict, layers0, b1: float) -> dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (per epoch, (steps, P)),
    ``mu1`` (first moment after the first epoch, per-layer dicts) and
    ``params`` (weights after the last checked epoch); ``prog`` holds
    ``val_preds`` and ``ref`` ``val_logits`` (per epoch, for the validation
    nodes in one order)."""
    loss_gap = 0.0
    for lp, lr in zip(prog["losses"], ref["losses"]):
        lp, lr = np.asarray(lp, np.float64), np.asarray(lr, np.float64)
        if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
            return {k: float("inf") for k in NUMBERS}
        loss_gap = max(loss_gap, float(np.max(
            np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30))))
    scale = 1.0 / (1.0 - b1)
    g_prog = {k: v * scale for k, v in _norms(prog["mu1"]).items()}
    g_ref = {k: v * scale for k, v in _norms(ref["mu1"]).items()}
    grad_gap = _worst_gap(g_prog, g_ref, g_ref)
    med_g = float(np.median(list(g_ref.values())))
    moved = [k for k, v in g_ref.items() if v >= 1e-3 * med_g]
    l0 = _leaves(layers0)
    d_prog = {k: float(np.linalg.norm(v - l0[k]))
              for k, v in _leaves(prog["params"]).items()}
    d_ref = {k: float(np.linalg.norm(v - l0[k]))
             for k, v in _leaves(ref["params"]).items()}
    delta_gap = _worst_gap(d_prog, d_ref, moved)
    out = {"loss_gap": loss_gap, "grad_gap": grad_gap, "delta_gap": delta_gap,
           "eval_gap": eval_gap(prog["val_preds"], ref["val_logits"])}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def load_limits(workload: str, root: Path = LIMITS) -> dict[str, float]:
    path = Path(root) / f"{workload}.json"
    if not path.exists():
        return {}
    with open(path) as f:
        data = json.load(f)
    return {k: float(v["limit"]) for k, v in data.items() if k in NUMBERS}


def judge(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """``{name: [value, limit]}`` and whether every value is within its
    limit; a number without a limit fails."""
    checks = {k: [numbers.get(k, float("inf")), limits.get(k)]
              for k in NUMBERS}
    ok = all(lim is not None and val <= lim for val, lim in checks.values())
    return {"checks": checks, "ok": ok}
