"""The signoff comparison: per-variant counts matched into the reference's
tie ranges, and the folded statistics held to the range the ties leave."""
import numpy as np
import pytest

from benchlib import registry

signoff = registry.Registry().generator("signoff")


def _want(rows, lo=None, hi=None, floor=0.5, n=10):
    rows = np.asarray(rows)
    lo = rows if lo is None else np.asarray(lo)
    hi = rows if hi is None else np.asarray(hi)
    acc = rows / n
    return dict(rows=rows, lo=lo, hi=hi, mean=float(acc.mean()),
                worst=float(acc.min()), best=float(acc.max()),
                in_spec=int((acc >= floor).sum()), ties=int((hi - lo).sum()))


def _got(rows, n=10, floor=0.5):
    rows = np.asarray(rows)
    acc = rows / n
    return dict(signoff.answer(acc.mean(), acc.min(), acc.max(),
                               (acc >= floor).sum(),
                               np.bincount(rows, minlength=n + 1)), n=n)


@pytest.mark.parametrize("points,lo,hi,want", [
    ([3, 5, 7], [3, 5, 7], [3, 5, 7], 0),        # the same multiset
    ([7, 3, 5], [5, 7, 3], [5, 7, 3], 0),        # in any order
    ([3, 5, 8], [3, 5, 7], [3, 5, 7], 1),        # one count moved
    ([3, 6, 7], [3, 5, 7], [3, 6, 7], 0),        # moved within its tie
    ([4, 4, 7], [3, 5, 7], [4, 6, 7], 1),        # two counts, one range
    ([5, 6], [4, 5], [5, 6], 0),                 # greedy must not waste 4..5
    ([5], [5, 5], [5, 5], 1),                    # a variant missing
])
def test_unexplained(points, lo, hi, want):
    assert signoff.unexplained(points, lo, hi) == want


def test_sound_answer_reads_nought():
    rows = [4, 6, 6, 9]
    g = signoff.gaps(_got(rows), _want(rows), 0.5)
    assert g == {"variants_unexplained": 0.0, "fold_gap": 0.0}


def test_ties_explain_a_moved_row_and_nothing_more():
    want = _want([4, 6, 6, 9], lo=[4, 5, 6, 9], hi=[4, 6, 6, 9])
    assert signoff.gaps(_got([4, 5, 6, 9]), want, 0.5) == \
        {"variants_unexplained": 0.0, "fold_gap": 0.0}
    g = signoff.gaps(_got([4, 4, 6, 9]), want, 0.5)
    assert g["variants_unexplained"] == 1.0
    assert g["fold_gap"] == pytest.approx(1.0)


def test_fold_read_off_its_own_histogram_is_held_to_the_reference():
    got = _got([4, 6, 6, 9])
    got["mean"] += 2.0 / (4 * 10)                # two rows more in the mean
    g = signoff.gaps(got, _want([4, 6, 6, 9]), 0.5)
    assert g["variants_unexplained"] == 0.0
    assert g["fold_gap"] == pytest.approx(2.0)
