"""The restricted streaming Monte-Carlo step (DESIGN.md §10.6).

A one-assignment ``iid`` stream whose assignment selects a strict subset of
the analog candidates lowers only those analog pairs, in size groups.  The
existing streaming tests select every pair, so they run the candidate step;
these select part of them:

  * draws: a pair's draw at a shorter leading axis is the first rows of its
    draw at the candidate M (the per-pair key's definition, under
    ``jax_threefry_partitionable``), in the raw normals and in the lowered
    variant banks of a machine;
  * parity: the restricted stream gives the statistics of the candidate
    machine's dense bits, and its bits are the candidate bits wherever the
    assignment reads them, up to comparator ties (DESIGN.md §1.4);
  * bypass: a sweep over every pair, ``is``, ``sobol`` and ``stratified``
    run the candidate step, the same program as before;
  * layout: the size groups of the optdigits machine's analog pairs.
"""
import itertools
import operator
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import MixedKernelSVM
from repro.api import compiled as api
from repro.core import dse, trainer
from repro.core.analog import N_ALPHA_OFFSETS, N_GAUSS_OFFSETS
from test_mc_streaming import MOMENT_TOL, _tiny_candidates

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "bench")

#: DESIGN.md §1.4: an analog pair bit whose score lies this close to 0 is a
#: comparator tie and may go either way.
ANALOG_TIE_EPS = 1e-3

#: True M of the 23 analog pairs the committed optdigits fit
#: (``bench/configs/digits_fit``) assigns, sorted.
DIGITS_M = [13, 32, 38, 41, 45, 46, 61, 61, 67, 68, 71, 82, 82, 84, 91, 97,
            100, 100, 100, 124, 125, 127, 130]


def _rows(n: int = 40, d: int = 3):
    rng = np.random.default_rng(1)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.integers(0, 3, size=n).astype(np.int32))


def _tiny_machine(method: str = "iid", chunk: int = 8):
    return api.compile_mc_stream(_tiny_candidates(), n_classes=3,
                                 key=jax.random.PRNGKey(0), method=method,
                                 mc_chunk=chunk)


@pytest.fixture(scope="module")
def balance():
    """The committed Balance fit, its test rows and its assignment."""
    est = MixedKernelSVM.load(os.path.join(BENCH, "configs", "balance_fit"))
    d = np.load(os.path.join(BENCH, "configs", "balance_test.npz"))
    a = dse.assignment_from_kernel_map(est.kernel_map_)[None, :]
    return (est._candidates(), est.n_classes_, np.asarray(d["x"][:48]),
            np.asarray(d["y"][:48]), a)


# -- draws ---------------------------------------------------------------------


@pytest.mark.parametrize("short,full", [((130, 5, N_GAUSS_OFFSETS),
                                         (165, 5, N_GAUSS_OFFSETS)),
                                        ((13, N_ALPHA_OFFSETS),
                                         (165, N_ALPHA_OFFSETS))])
def test_pair_draw_is_prefix_stable(short, full):
    """``normal(fold_in(key, v), (m, ...))`` is the first ``m`` rows of the
    draw at the candidate M: what keeps a restricted group's cells on the
    mismatch the candidate step and the plain reference give them."""
    assert jax.config.jax_threefry_partitionable
    for v in (0, 7, 2**31 - 1):
        k = jax.random.fold_in(jax.random.PRNGKey(13), v)
        np.testing.assert_array_equal(
            np.asarray(jax.random.normal(k, short)),
            np.asarray(jax.random.normal(k, full))[: short[0]])


def test_restricted_banks_keep_the_candidate_mismatch():
    """The lowered variant bank of a restricted step holds, on each true
    cell, the shift, gain, coefficients and offset of the candidate bank."""
    sm = _tiny_machine()
    view = sm._view(np.array([[0, 0, 1]]))
    assert view is not sm and view._lanes.needed == frozenset({2})
    v_idx = np.arange(5, 13, dtype=np.int32)
    u = np.zeros((8, 0), np.float32)
    (cand,), _, _ = _variant_banks(sm, v_idx, u)
    (rest,), _, _ = _variant_banks(view, v_idx, u)
    (bank,) = [b for b, bc in zip(sm._lanes.kernel_banks,
                                  sm._lanes.stream_consts) if bc is not None]
    row = list(bank.pair_idx).index(3 + 2)
    m = 4
    for f in ("shift", "gain", "coef_pos", "coef_neg"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rest, f))[:, 0, :m],
            np.asarray(getattr(cand, f))[:, row, :m], err_msg=f)
    np.testing.assert_array_equal(np.asarray(rest.offset)[:, 0],
                                  np.asarray(cand.offset)[:, row])


def _variant_banks(sm, v_idx, u):
    banks, w, log_ref = sm._chunk_banks(v_idx, u)
    return [b for b in banks if b is not None], w, log_ref


# -- parity --------------------------------------------------------------------


def _stats(sm, x, y, a, n_variants: int, chunk: int):
    """Candidate dense bits of the stream's variants, and the restricted
    ones, in chunks of ``chunk``."""
    starts = range(0, n_variants, chunk)
    idx = [np.arange(s, min(s + chunk, n_variants)) for s in starts]
    cand = np.concatenate([sm.pair_bits_dense(x, i) for i in idx])
    rest = np.concatenate([sm.pair_bits_dense(x, i, a) for i in idx])
    scores = np.concatenate([np.asarray(jax.jit(sm._scores)(
        x, i.astype(np.int32), np.zeros((len(i), 0), np.float32))[0])
        for i in idx])
    return cand, rest, scores


def _check_parity(cands, k, x, y, a, chunk, record_property):
    n_variants, floor = 40, 0.5
    sm = api.compile_mc_stream(cands, n_classes=k, key=jax.random.PRNGKey(13),
                               mc_chunk=chunk)
    out = sm.stream(x, y, a, n_variants=n_variants, accuracy_floor=floor)
    assert sm.restricted_steps_run == sm.steps_run > 0
    cand, rest, scores = _stats(sm, x, y, a, n_variants, chunk)
    # Every bit the assignment reads: the linear bit of each pair and the
    # analog bit of each selected pair.
    read = np.stack([np.ones(a.shape[1], bool), a[0].astype(bool)], -1)
    differ = (cand != rest) & read[None, None]
    assert np.all(np.abs(scores[differ]) <= ANALOG_TIE_EPS)
    record_property("tie_bits", int(differ.sum()))
    # An analog column no one reads repeats the pair's linear bit.
    off = [i for i in sorted(sm._analog_pairs) if not a[0, i]]
    np.testing.assert_array_equal(rest[..., off, 1], rest[..., off, 0])
    acc = dse.assignment_accuracies_mc(cand, a, y, k)[:, 0]
    n = len(y)
    assert out["count"] == float(n_variants)
    assert abs(out["mean"][0] - acc.mean()) < MOMENT_TOL
    assert out["worst"][0] == acc.min()
    assert out["best"][0] == acc.max()
    assert out["yield"][0] == (acc >= floor).mean()
    np.testing.assert_array_equal(
        out["hist"][0], np.bincount(np.rint(acc * n).astype(int),
                                    minlength=n + 1))


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("selected", [[1, 1, 0], [0, 0, 1], [1, 0, 0]])
def test_restricted_stream_matches_candidate_bits_tiny(chunk, selected,
                                                        record_property):
    """Tiny candidates (pair 0 digital RBF, pairs 1 and 2 analog): one
    analog pair, the other, and none."""
    x, y = _rows()
    _check_parity(_tiny_candidates(), 3, x, y, np.array([selected]), chunk,
                  record_property)


@pytest.mark.parametrize("chunk", [16, 40])
def test_restricted_stream_matches_candidate_bits_balance(balance, chunk,
                                                           record_property):
    """The committed Balance fit: linear (0, 1), analog (0, 2) and (1, 2) of
    the three analog candidates."""
    cands, k, x, y, a = balance
    assert a.tolist() == [[0, 1, 1]]
    _check_parity(cands, k, x, y, a, chunk, record_property)


def test_restricted_layout_of_balance(balance):
    """The two analog pairs (M 48 and 54) run as two banks of their own M:
    102 cells a variant-row in place of the candidates' 3 x 65."""
    cands, k, x, y, a = balance
    sm = api.compile_mc_stream(cands, n_classes=k, key=jax.random.PRNGKey(13),
                               mc_chunk=16)
    assert sm.lane_grids() == [(3, 65)]
    assert sorted(sm.lane_grids(a)) == [(1, 48), (1, 54)]
    sm.stream(x, y, a, n_variants=16, accuracy_floor=0.5)
    assert sm.lane_cells_run == 16 * len(y) * 102
    assert sm.lane_slices_run == 1


# -- bypass --------------------------------------------------------------------


@pytest.mark.parametrize("method,assignments", [
    ("iid", [[1, 1, 0], [0, 0, 1]]),       # a sweep: the union is every pair
    ("is", [[1, 1, 0]]),
    ("sobol", [[1, 1, 0]]),
    ("stratified", [[1, 1, 0]]),
])
def test_candidate_step_runs_where_restriction_would_change_the_estimate(
        method, assignments):
    x, y = _rows()
    a = np.array(assignments, bool)
    sm = _tiny_machine(method)
    assert sm._view(a) is sm
    hlo = sm.compiled_step(len(y), len(a)).as_text()
    before = obs.snapshot()["mc_lanes_restricted_traced"]
    assert sm.compiled_step(len(y), assignments=a).as_text() == hlo
    sm.stream(x, y, a, n_variants=12, accuracy_floor=0.5)
    assert obs.snapshot()["mc_lanes_restricted_traced"] == before
    assert sm.restricted_steps_run == 0
    assert sm.lane_cells_run == 2 * 8 * len(y) * 2 * 6


def test_restricted_step_is_counted_once_per_set_of_pairs():
    x, y = _rows()
    sm = _tiny_machine()
    a = np.array([[1, 1, 0]], bool)
    before = obs.snapshot()["mc_lanes_restricted_traced"]
    sm.stream(x, y, a, n_variants=12, accuracy_floor=0.5)
    mid = obs.snapshot()["mc_lanes_restricted_traced"]
    assert mid == before + 1
    sm.stream(x, y, np.array([[0, 1, 0]], bool), n_variants=12,
              accuracy_floor=0.5)
    assert obs.snapshot()["mc_lanes_restricted_traced"] == mid
    assert len(sm._restricted) == 1
    assert (sm.steps_run, sm.restricted_steps_run) == (4, 4)
    assert sm.lane_cells_run == 4 * 8 * len(y) * 1 * 6


# -- layout --------------------------------------------------------------------


def test_digits_layout_four_groups():
    """On the optdigits machine's 23 analog pairs: 1,951 cells in 4 groups
    (13-46 x 6, 61-71 x 5, 82-100 x 8, 124-130 x 4), 91.5 % of them true,
    in place of 45 x 165 = 7,425."""
    groups = api._size_groups(DIGITS_M)
    m = np.asarray(DIGITS_M)
    assert [(len(g), int(m[g].min()), int(m[g].max())) for g in groups] == [
        (6, 13, 46), (5, 61, 71), (8, 82, 100), (4, 124, 130)]
    cells = sum(len(g) * int(m[g].max()) for g in groups)
    assert cells == 1951
    assert sum(DIGITS_M) / cells == pytest.approx(0.915, abs=5e-4)


@pytest.mark.parametrize("seed", range(4))
def test_size_sorted_groups_is_optimal(seed):
    """The dynamic programme against every contiguous partition of the
    sorted sizes, under the summed cost with a fixed cost a group."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 60, size=7).tolist()
    cost = lambda n, m: n * m + 5                       # noqa: E731
    groups = trainer.size_sorted_groups(sizes, 3, cost, operator.add)
    flat = np.sort(np.concatenate(groups))
    np.testing.assert_array_equal(flat, np.arange(len(sizes)))
    s = sorted(sizes)

    def total(bounds):
        edges = [0, *bounds, len(s)]
        return sum(cost(j - i, s[j - 1]) for i, j in zip(edges, edges[1:]))

    best = min(total(c) for r in range(3)
               for c in itertools.combinations(range(1, len(s)), r))
    got = sum(cost(len(g), max(sizes[i] for i in g)) for g in groups)
    assert got == best
