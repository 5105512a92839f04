"""``eval_gap``, the comparison of the validation forward's answers, on
hand-made logits; and ``judge`` with and without limits."""
import math

import numpy as np
import pytest

from perfbench import check

LOGITS = np.array([[0.0, 1.0, 3.0],        # best 2, spread 3
                   [2.0, 0.0, 1.9],        # best 0, near-tie with 2
                   [0.0, 4.0, 1.0]])       # best 1, spread 4


def test_eval_gap_reads_zero_for_the_reference_classes():
    assert check.eval_gap([np.array([2, 0, 1])], [LOGITS]) == 0.0


def test_eval_gap_of_a_near_tie_is_small():
    # row 1 answers 2: 0.1 below the best, over the median spread 3
    gap = check.eval_gap([np.array([2, 2, 1])], [LOGITS])
    assert gap == pytest.approx(0.1 / 3)


def test_eval_gap_of_answers_from_other_rows_is_large():
    # each row answers with the next row's class: the widest gap is row 2
    # answering 2 (4 - 1 = 3) and row 0 answering 0 (3), over spread 3
    gap = check.eval_gap([np.array([0, 1, 2])], [LOGITS])
    assert gap == pytest.approx(1.0)


def test_eval_gap_takes_the_widest_epoch():
    sound, tie = np.array([2, 0, 1]), np.array([2, 2, 1])
    assert check.eval_gap([sound, tie], [LOGITS, LOGITS]) == pytest.approx(
        0.1 / 3)


@pytest.mark.parametrize("preds,logits", [
    ([np.array([2, 0])], [LOGITS]),                 # a node left out
    ([np.array([2, 0, 3])], [LOGITS]),              # no such class
    ([np.array([2, 0, 1])], [LOGITS, LOGITS]),      # an epoch left out
    ([], [])])
def test_eval_gap_without_a_full_answer_is_infinite(preds, logits):
    assert math.isinf(check.eval_gap(preds, logits))


def test_judge_needs_a_limit_for_every_number():
    numbers = {k: 0.0 for k in check.NUMBERS}
    limits = {k: 1.0 for k in check.NUMBERS}
    assert check.judge(numbers, limits)["ok"]
    del limits["eval_gap"]
    verdict = check.judge(numbers, limits)
    assert not verdict["ok"] and verdict["checks"]["eval_gap"] == [0.0, None]
