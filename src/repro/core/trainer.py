"""Batched Algorithm-1 training engine (DESIGN.md §4).

The sequential reference path (``selection.train_pairs_sequential``) runs
2-3 ``svm.fit_best`` calls per OvO pair, and every pair's unique subset
size forces fresh jit compilations of the CV-grid program and the solver:
O(pairs) compiles, each covering a single pair.  This module restructures
the whole exploration as a *fixed-shape batched program*:

1.  **Padding** (`pad_pairs`): every binary subset D_ij is padded to the
    shared ``n_max`` and stacked into ``(P, n_max, d)`` tensors.  Padding
    rows get ``valid = 0``, which zeroes their box constraint (``c_box =
    c * mask * valid``) — the solver's own masking mechanism (alpha frozen
    at 0, see ``svm.dual_coordinate_ascent``) — AND their CV-validation
    weight.  A padded row is therefore a *bit-exact no-op*: its coordinate
    update clips to [0, 0] and contributes an exact 0 to every reduction,
    so the padded solve returns the same alphas as the unpadded one.

2.  **One compile per kernel family** (`family_cv_grid` / `family_refit`):
    all pairs x CV folds x (C, gamma) grid cells run in ONE jitted vmap
    nest per family (linear, rbf, and the sech2 hardware-in-the-loop
    family).  The vmap order is chosen so the Gram matrix is built once
    per (pair, gamma) — ``pairwise_sq_dists`` does not depend on the
    mapped gamma axis, so vmap hoists it to once per pair, and the
    fold x C cells close over the finished Gram — instead of once per
    grid cell as in the sequential path.

3.  **Selection as argmax** (`train_pairs`): Algorithm 1's line-8 keeps
    RBF only when strictly better; here it is an argmax over the
    ``(P, |gamma|, |C|)`` CV-accuracy tensor per family (gamma-major flat
    order, matching ``np.unravel_index`` in ``svm.fit_best``), followed by
    one vmapped full-set refit per family and a host-side extraction of
    the support sets (identical expressions to ``svm.train_binary``).

4.  **Scaling out** (`mesh=`): the same CV-grid program optionally runs
    under ``shard_map`` over the flattened pair x gamma axis
    (``"pairgrid"``, see ``launch.mesh.make_trainer_mesh``) — the work is
    embarrassingly parallel (no collectives), at the cost of recomputing
    the pairwise distances per gamma inside each shard.

The engine reproduces the sequential path's selections and accuracies up
to the documented comparator-tie epsilon (DESIGN.md §1.4): batched-shape
BLAS reductions may differ in the last ulp, which can only matter for a
CV fold whose decision score sits exactly on the comparator threshold.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernels as kern
from repro.core.analog import AnalogRBFModel
from repro.core.ovo import class_pairs
from repro.core.svm import (
    SVMModel,
    cv_lanes_accuracy_pallas,
    resolve_use_pallas,
)
from repro.kernels import ops as kops

#: fit_best's hyper-parameter grid defaults (paper Sec. V-A2).
DEFAULT_CS = np.logspace(-1, 3, 7)
DEFAULT_RBF_GAMMAS = np.logspace(-1, 2, 7)


@dataclasses.dataclass
class PairResult:
    """Per-OvO-pair outcome of Algorithm 1 (both candidates kept)."""

    pair: tuple[int, int]
    kernel: str                      # selected kernel kind
    model: SVMModel                  # selected float model
    acc_linear: float                # CV accuracy of the linear candidate
    acc_rbf: float                   # CV accuracy of the RBF candidate
    model_linear: SVMModel           # both candidates kept for baselines
    model_rbf: SVMModel
    # Hardware-aware co-optimized model (sech2 kernel) for analog deployment;
    # only trained for pairs that Algorithm 1 assigns to RBF.
    model_hw: Optional[SVMModel] = None


def binary_subset(
    x: np.ndarray, y: np.ndarray, ci: int, cj: int
) -> tuple[np.ndarray, np.ndarray]:
    """Line 5: D_ij = {(x, y) in D | y in {c_i, c_j}}, labels -> {+1, -1}.

    +1 encodes c_i (the pair's first class) so bit==1 <=> c_i wins.
    """
    mask = (y == ci) | (y == cj)
    yy = np.where(y[mask] == ci, 1.0, -1.0)
    return x[mask], yy


def default_hw(seed: int = 0, params=None) -> AnalogRBFModel:
    """The default calibrated analog behavioral model (one fabricated core).

    ``params`` optionally overrides the :class:`CircuitParams` the
    surrogate sweeps run with (sigma sweeps, bias studies) — the
    construction stays deterministic in ``(seed, params)``, which is what
    makes estimators built this way serializable.
    """
    from repro.core.analog import CircuitParams

    return AnalogRBFModel.from_circuit(
        params if params is not None else CircuitParams(),
        key=jax.random.PRNGKey(seed))


def hw_gamma_grid(hw: AnalogRBFModel, n: int = 7) -> np.ndarray:
    """Hardware-realizable gamma* grid for the sech2 co-optimized training.

    The input scaling of Eq. (8) must keep the scaled differential voltage
    within the cell's usable range: s * v_scale * max|dx| <= v_range with
    max|dx| = 1 for [0,1]-normalized features.  Everything below that cap is
    realizable; we search log-uniformly under it.
    """
    g_cap = hw.gamma0_feature() * (hw.params.v_range / hw.v_scale) ** 2
    return np.logspace(-1.0, np.log10(g_cap), n)


# ---------------------------------------------------------------------------
# Padded pair stack
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PaddedPairs:
    """All OvO binary subsets padded to a shared ``n_max`` and stacked.

    Device-facing arrays (f32): ``x (P, n_max, d)``, ``y (P, n_max)``,
    ``valid (P, n_max)`` (1 real / 0 padding), ``fold_masks (P, F, n_max)``
    (1 train / 0 held-out, 0 on padding — validation weight is
    ``(1 - mask) * valid`` so padding rows count for neither side).

    ``subsets`` keeps the unpadded host views (float64, exactly as
    ``binary_subset`` produced them) for the final model extraction.
    """

    pairs: list[tuple[int, int]]
    x: np.ndarray
    y: np.ndarray
    valid: np.ndarray
    fold_masks: np.ndarray
    n_true: list[int]
    subsets: list[tuple[np.ndarray, np.ndarray]]

    @property
    def n_pairs(self) -> int:
        return len(self.pairs)

    @property
    def n_max(self) -> int:
        return int(self.x.shape[1])

    def take(self, idx: Sequence[int]) -> "PaddedPairs":
        """Sub-stack along the pair axis (e.g. the RBF-selected pairs)."""
        idx = list(idx)
        return PaddedPairs(
            pairs=[self.pairs[i] for i in idx],
            x=self.x[idx], y=self.y[idx], valid=self.valid[idx],
            fold_masks=self.fold_masks[idx],
            n_true=[self.n_true[i] for i in idx],
            subsets=[self.subsets[i] for i in idx],
        )

    def trim(self) -> "PaddedPairs":
        """Re-pad to this stack's OWN max subset size.

        ``take`` keeps the original global ``n_max``; a size-sharded
        layout (``shard_lane_layout``) trims each shard so its solver
        lanes pay only the shard's padding, not the global tail's.
        Slicing is exact: rows past a pair's ``n_true`` are padding
        (valid 0, y +1, mask 0) whatever the stack width.
        """
        m = max(self.n_true)
        if m == self.n_max:
            return self
        return PaddedPairs(
            pairs=self.pairs, x=self.x[:, :m], y=self.y[:, :m],
            valid=self.valid[:, :m], fold_masks=self.fold_masks[:, :, :m],
            n_true=self.n_true, subsets=self.subsets)


def cv_fold_assignment(n: int, n_folds: int, seed: int) -> np.ndarray:
    """Fold id per sample — IDENTICAL to ``svm.cv_grid_accuracy`` (each pair
    draws from a fresh ``RandomState(seed)`` over its own subset size)."""
    rng = np.random.RandomState(seed)
    return rng.permutation(n) % n_folds


def pad_pairs(
    x_train: np.ndarray,
    y_train: np.ndarray,
    n_classes: int,
    n_folds: int = 5,
    seed: int = 0,
) -> PaddedPairs:
    """Extract every OvO binary subset and stack them padded to ``n_max``."""
    x_train = np.asarray(x_train)
    y_train = np.asarray(y_train)
    pairs = class_pairs(n_classes)
    subsets = [binary_subset(x_train, y_train, ci, cj) for ci, cj in pairs]
    n_true = [len(yb) for _, yb in subsets]
    n_max = max(n_true)
    p, d = len(pairs), x_train.shape[1]

    x = np.zeros((p, n_max, d), np.float32)
    y = np.ones((p, n_max), np.float32)     # +1 on padding: inert either way
    valid = np.zeros((p, n_max), np.float32)
    masks = np.zeros((p, n_folds, n_max), np.float32)
    for i, (xb, yb) in enumerate(subsets):
        n = n_true[i]
        x[i, :n] = xb
        y[i, :n] = yb
        valid[i, :n] = 1.0
        fold_of = cv_fold_assignment(n, n_folds, seed)
        for f in range(n_folds):
            masks[i, f, :n] = (fold_of != f)
    return PaddedPairs(pairs=pairs, x=x, y=y, valid=valid, fold_masks=masks,
                       n_true=n_true, subsets=subsets)


# ---------------------------------------------------------------------------
# Blocked Gauss-Seidel solver: the batched engine's inner loop
# ---------------------------------------------------------------------------

#: Coordinate-block size of the batched solver: block-local traffic grows
#: with the block while the per-block margin GEMM amortizes as 1/block;
#: ~sqrt(n) balances the two for the paper's subset sizes.
SOLVER_BLOCK = 16


def dual_coordinate_ascent_blocked(
    kp: jnp.ndarray,
    y: jnp.ndarray,
    c_box: jnp.ndarray,
    n_epochs: int,
    block: int = SOLVER_BLOCK,
) -> jnp.ndarray:
    """``svm.dual_coordinate_ascent`` restructured for batched lanes.

    The reference solver maintains the full margin vector ``f`` with one
    O(n) read+write per coordinate: under a vmap over hundreds of
    (C, fold) lanes that streams the whole (lanes, n) state n_epochs * n
    times and the program becomes memory-bound.  Here coordinates are
    processed in blocks of ``block``, and no margin state is carried at
    all: entering a block, its margins are computed *fresh* from the
    current alphas with ONE GEMM (``(alpha * y) @ kp[:, blk]`` — the Gram
    operand is shared by every lane that closes over it), and the
    Gauss-Seidel recurrence inside the block only touches the block-local
    ``kp[blk, blk]`` tile and (lanes, block) state.

    The coordinate *update sequence* is identical to the reference solver
    (same visit order; every coordinate's margin reflects all prior
    updates); only the summation association of the margins differs
    (fresh contraction vs incremental accumulation), so results agree to
    f32 round-off rather than bit-exactly (DESIGN.md §4.5).  Masked
    samples (``c_box = 0``) remain exact no-ops — their alphas stay 0 and
    contribute exact zeros to the margin GEMM — which is what makes
    trailing padding rows inert.
    """
    n = kp.shape[0]
    block = int(min(block, n))
    n_pad = -(-n // block) * block
    if n_pad != n:
        kp = jnp.pad(kp, ((0, n_pad - n), (0, n_pad - n)))
        y = jnp.pad(y, (0, n_pad - n), constant_values=1.0)
        c_box = jnp.pad(c_box, (0, n_pad - n))
    qdiag = jnp.clip(jnp.diag(kp), 1e-12, None)
    n_blocks = n_pad // block

    def block_body(b, alpha):
        j0 = b * block
        # Row slice, NOT columns: the reference margin is f_j = sum_i
        # K'[j, i] a_i y_i, and the hardware measured-curve kernel is not
        # exactly symmetric (the fitted center offset mu shifts the bell),
        # so rows and columns differ at the ~1e-4 level there.
        rows = jax.lax.dynamic_slice(kp, (j0, 0), (block, n_pad))
        kbb = jax.lax.dynamic_slice(rows, (0, j0), (block, block))
        yb = jax.lax.dynamic_slice(y, (j0,), (block,))
        cb = jax.lax.dynamic_slice(c_box, (j0,), (block,))
        qb = jax.lax.dynamic_slice(qdiag, (j0,), (block,))
        ab = jax.lax.dynamic_slice(alpha, (j0,), (block,))
        fb = kern.matmul(rows, alpha * y)      # fresh block margins, one GEMM

        def coord(i, c2):
            ab, fb = c2
            g = 1.0 - yb[i] * fb[i]
            a_new = jnp.clip(ab[i] + g / qb[i], 0.0, cb[i])
            d = a_new - ab[i]
            fb = fb + d * yb[i] * kbb[:, i]
            return ab.at[i].set(a_new), fb

        ab, _ = jax.lax.fori_loop(0, block, coord, (ab, fb))
        return jax.lax.dynamic_update_slice(alpha, ab, (j0,))

    def epoch(_, alpha):
        return jax.lax.fori_loop(0, n_blocks, block_body, alpha)

    alpha = jax.lax.fori_loop(0, n_epochs, epoch,
                              jnp.zeros((n_pad,), kp.dtype))
    return alpha[:n]


# ---------------------------------------------------------------------------
# Hardware-in-the-loop training kernel: uniform-grid fast interpolation
# ---------------------------------------------------------------------------

# id(hw) -> (hw, fast kernel fn), insertion-ordered.  Keyed by identity
# (the behavioral model's ndarray fields make it unhashable); a stable
# function object per hw instance keeps one jit cache entry per model.
# Bounded FIFO: every default-constructed estimator calibrates a fresh
# AnalogRBFModel, so without eviction a long-lived sweep process would pin
# models (and their compiled programs) forever.
_HW_KERNEL_CACHE: dict[int, tuple] = {}
_HW_KERNEL_CACHE_MAX = 8


def _training_kernel(kind):
    """Resolve the kernel used *inside* the compiled training programs.

    A bound ``AnalogRBFModel.kernel_response`` is swapped for an equivalent
    closure that interpolates the measured transfer curve with the O(1)
    uniform-grid bin location of ``kernels._uniform_interp`` (the DC-sweep
    abscissa is a linspace) instead of ``jnp.interp``'s per-query binary
    search — the same substitution the compiled inference path makes,
    tracking the behavioral model to ~1e-6 (within the comparator-tie
    epsilon the training contract already carries, DESIGN.md §4.5).
    """
    hw = getattr(kind, "__self__", None)
    if not isinstance(hw, AnalogRBFModel):
        return kind
    hit = _HW_KERNEL_CACHE.get(id(hw))
    if hit is not None and hit[0] is hw:
        return hit[1]
    fp = kern._grid_fast_path(np.asarray(hw.dv_grid))
    if not fp["uniform_grid"]:
        return kind
    curve = jnp.asarray(hw.kernel_curve, jnp.float32)
    lo = float(np.asarray(hw.dv_grid, np.float32)[0])
    hi = float(np.asarray(hw.dv_grid, np.float32)[-1])
    left = float(hw.kernel_curve[0])
    right = float(hw.kernel_curve[-1])
    inv_step = jnp.float32(fp["inv_step"])

    def fast_hw_kernel(x, sv, gamma_star):
        s = hw.input_scale(gamma_star)
        # Per-dimension accumulation, as in the compiled inference path:
        # (n, m) temporaries instead of one (n, m, d) tensor, whose
        # d-minor layout the TPU pads to 128 lanes (26x the bytes at
        # d = 5).  Same sequential multiply order as jnp.prod.
        acc = None
        for k in range(x.shape[1]):
            dv = hw.v_scale * s * (x[:, k:k + 1] - sv[None, :, k]) + hw.mu
            k1 = kern._uniform_interp(dv, curve, lo, hi, left, right,
                                      inv_step)
            acc = k1 if acc is None else acc * k1
        return acc

    while len(_HW_KERNEL_CACHE) >= _HW_KERNEL_CACHE_MAX:
        _HW_KERNEL_CACHE.pop(next(iter(_HW_KERNEL_CACHE)))
    _HW_KERNEL_CACHE[id(hw)] = (hw, fast_hw_kernel)
    return fast_hw_kernel


# ---------------------------------------------------------------------------
# Jitted cores: ONE compile per (kernel family, shape)
# ---------------------------------------------------------------------------


def _cell_cv_accuracy(kp, yp, mask, vp, c, n_epochs):
    """Train on (mask & valid), validate on (~mask & valid) — the padded
    counterpart of ``svm._train_eval_masked``.

    The fused-solver twin is ``svm.cv_lanes_accuracy_pallas``: same
    train/validate weighting, but the prediction margins come out of the
    Pallas solver's fused ``f`` output instead of a ``kp @ (alpha * y)``
    against a materialized Gram (DESIGN.md §7.1)."""
    alpha = dual_coordinate_ascent_blocked(kp, yp, c * mask * vp, n_epochs)
    f = kern.matmul(kp, alpha * yp)
    pred = jnp.where(f >= 0.0, 1.0, -1.0)
    val = (1.0 - mask) * vp
    return jnp.sum((pred == yp) * val) / jnp.clip(jnp.sum(val), 1.0, None)


#: Gram-footprint gate for the batched CV grid: when the vmapped
#: per-gamma Gram stack (P * G * n_max^2 f32 bytes) of one program would
#: exceed this, the gamma axis runs sequentially under ``lax.map`` so at
#: most one gamma's Grams are live per pair.  At the scale-out workload
#: (P=66, G=7, n_max=1582) the vmapped stack is ~4.6 GB; sequential
#: gammas bring it under 700 MB for the same lane math.
CV_GRID_VMAP_BYTES = 1 << 30


def _pair_cv_grid(xp, yp, fm, vp, gammas, cs, kind, n_epochs,
                  seq_gamma=False):
    """(G, C) mean CV accuracy of one pair; all folds x cells vmapped.

    The Gram matrix is built inside the gamma vmap, so the
    gamma-independent work (pairwise distances / feature products) is
    hoisted to once per pair, and every fold x C lane closes over the
    finished per-gamma Gram.  The C x folds lanes are flattened into one
    vmap axis (smaller jaxpr, one fused solver loop nest).

    ``seq_gamma`` trades the gamma vmap for ``lax.map`` — identical
    results, one live Gram per gamma instead of G (see
    :data:`CV_GRID_VMAP_BYTES`).
    """
    n_c, n_f = cs.shape[0], fm.shape[0]
    c_lanes = jnp.repeat(cs, n_f)                      # (C*F,)
    m_lanes = jnp.tile(fm, (n_c, 1))                   # (C*F, n)

    def per_gamma(g):
        kp = kern.kernel_matrix(kind, xp, xp, g) + 1.0  # bias-as-feature
        accs = jax.vmap(
            lambda c, m: _cell_cv_accuracy(kp, yp, m, vp, c, n_epochs)
        )(c_lanes, m_lanes)
        return accs.reshape(n_c, n_f).mean(axis=1)      # (C,)

    if seq_gamma:
        return jax.lax.map(per_gamma, gammas).reshape(gammas.shape[0], n_c)
    return jax.vmap(per_gamma)(gammas).reshape(gammas.shape[0], n_c)


def _seq_gamma(x, gammas) -> bool:
    """Trace-time choice of the sequential-gamma CV grid from shapes."""
    p, n = x.shape[0], x.shape[1]
    return p * gammas.shape[0] * n * n * 4 > CV_GRID_VMAP_BYTES


@partial(jax.jit, static_argnames=("kind", "n_epochs", "use_pallas",
                                   "interpret"))
def _cv_grid_all_pairs(x, y, fold_masks, valid, gammas, cs, kind, n_epochs,
                       use_pallas=False, interpret=None):
    """CV grid only, (P, G, C) — the utility/shard-path entry point.

    ``train_pairs`` itself uses `_family_program` (grid + argmax + refit
    fused); this standalone program backs `family_cv_grid` so callers that
    only want the accuracy tensor don't pay a discarded refit.
    """
    if use_pallas and isinstance(kind, str):
        gammas_pg = jnp.broadcast_to(gammas[None], (x.shape[0],
                                                    gammas.shape[0]))
        return cv_lanes_accuracy_pallas(
            x, y, fold_masks, valid, gammas_pg, cs, kind=kind,
            n_epochs=n_epochs, interpret=interpret, block=SOLVER_BLOCK)
    seq = _seq_gamma(x, gammas)
    return jax.vmap(
        lambda xp, yp, fm, vp: _pair_cv_grid(xp, yp, fm, vp, gammas, cs,
                                             kind, n_epochs, seq_gamma=seq)
    )(x, y, fold_masks, valid)


@partial(jax.jit, static_argnames=("kind", "cv_epochs", "n_epochs",
                                   "use_pallas", "interpret"),
         donate_argnames=("y",))
def _family_program(x, y, fold_masks, valid, gammas, cs, kind, cv_epochs,
                    n_epochs, use_pallas=False, interpret=None):
    """The whole family in ONE program: CV grid -> argmax -> full refit.

    Returns ``(acc (P, G, C), gi (P,), ci (P,), alpha (P, n))``.  The
    argmax runs on device over the gamma-major flattened grid — the same
    first-maximum tie-break as ``np.unravel_index(np.argmax(...))`` in
    ``svm.fit_best``.

    ``use_pallas`` (string kinds) swaps both the CV grid and the refit
    onto the fused Gram-free solver lanes (``repro.kernels.solver``); the
    per-lane Gram matrices the vmap path materializes disappear from the
    program entirely.  ``y`` is donated: its buffer is dead by the time
    the refit alphas are produced, so XLA reuses it for the (P, n) output
    instead of growing the peak.
    """
    n_c = cs.shape[0]

    if use_pallas and isinstance(kind, str):
        gammas_pg = jnp.broadcast_to(gammas[None], (x.shape[0],
                                                    gammas.shape[0]))
        acc = cv_lanes_accuracy_pallas(
            x, y, fold_masks, valid, gammas_pg, cs, kind=kind,
            n_epochs=cv_epochs, interpret=interpret, block=SOLVER_BLOCK)
        flat = jnp.argmax(acc.reshape(acc.shape[0], -1), axis=1)
        gi, ci = flat // n_c, flat % n_c
        c_box = (cs[ci][:, None] * valid)[:, None, :]      # (P, 1, n)
        alpha, _ = kops.solve_lanes(
            x, y, c_box, gammas[gi][:, None], kind=kind,
            n_epochs=n_epochs, block=SOLVER_BLOCK, interpret=interpret)
        return acc, gi, ci, alpha[:, 0, 0]

    seq = _seq_gamma(x, gammas)

    def per_pair(xp, yp, fm, vp):
        acc = _pair_cv_grid(xp, yp, fm, vp, gammas, cs, kind, cv_epochs,
                            seq_gamma=seq)
        flat = jnp.argmax(acc)                         # gamma-major order
        gi, ci = flat // n_c, flat % n_c
        kp = kern.kernel_matrix(kind, xp, xp, gammas[gi]) + 1.0
        alpha = dual_coordinate_ascent_blocked(kp, yp, cs[ci] * vp, n_epochs)
        return acc, gi, ci, alpha

    return jax.vmap(per_pair)(x, y, fold_masks, valid)


@partial(jax.jit, static_argnames=("kind", "n_epochs", "use_pallas",
                                   "interpret"),
         donate_argnames=("y",))
def _refit_all_pairs(x, y, valid, gamma_sel, c_sel, kind, n_epochs,
                     use_pallas=False, interpret=None):
    """Full-set refit of every pair at its selected (gamma, C): (P, n).

    Only used by the shard_map path, where selection happens on host
    between the sharded CV grid and the refit.  ``y`` is donated (see
    ``_family_program``).
    """
    if use_pallas and isinstance(kind, str):
        c_box = (c_sel[:, None] * valid)[:, None, :]       # (P, 1, n)
        alpha, _ = kops.solve_lanes(
            x, y, c_box, gamma_sel[:, None], kind=kind,
            n_epochs=n_epochs, block=SOLVER_BLOCK, interpret=interpret)
        return alpha[:, 0, 0]

    def one(xp, yp, vp, g, c):
        kp = kern.kernel_matrix(kind, xp, xp, g) + 1.0
        return dual_coordinate_ascent_blocked(kp, yp, c * vp, n_epochs)

    return jax.vmap(one)(x, y, valid, gamma_sel, c_sel)


def _family_use_pallas(use_pallas, kind) -> bool:
    """Pallas solver applies to the stateless string kinds only; the
    hardware-in-the-loop measured-curve kernel keeps the blocked path."""
    return bool(use_pallas) and isinstance(kind, str) and \
        kind in ("linear", "rbf", "sech2")


def family_cv_grid(
    padded: PaddedPairs,
    kind,
    gammas: np.ndarray,
    cs: np.ndarray,
    n_epochs: int,
    mesh=None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """CV-accuracy tensor ``(P, |gammas|, |cs|)`` for one kernel family.

    ``kind`` is a kernel name or a callable (hardware-in-the-loop).  With a
    ``mesh`` the grid runs under shard_map over the pair x gamma axis.
    ``use_pallas`` (string kinds) runs the fused Gram-free solver lanes.
    """
    kind = _training_kernel(kind)
    use_pallas = _family_use_pallas(resolve_use_pallas(use_pallas), kind)
    if mesh is not None:
        return _cv_grid_sharded(padded, kind, gammas, cs, n_epochs, mesh,
                                use_pallas=use_pallas, interpret=interpret)
    return np.asarray(_cv_grid_all_pairs(
        jnp.asarray(padded.x), jnp.asarray(padded.y),
        jnp.asarray(padded.fold_masks), jnp.asarray(padded.valid),
        jnp.asarray(gammas, jnp.float32), jnp.asarray(cs, jnp.float32),
        kind=kind, n_epochs=n_epochs, use_pallas=use_pallas,
        interpret=interpret))


def family_refit(
    padded: PaddedPairs,
    kind,
    gamma_sel: np.ndarray,
    c_sel: np.ndarray,
    n_epochs: int,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """Vmapped full-set solve at the selected hyper-parameters: (P, n_max)."""
    kind = _training_kernel(kind)
    return np.asarray(_refit_all_pairs(
        jnp.asarray(padded.x), jnp.asarray(padded.y),
        jnp.asarray(padded.valid),
        jnp.asarray(gamma_sel, jnp.float32),
        jnp.asarray(c_sel, jnp.float32),
        kind=kind, n_epochs=n_epochs,
        use_pallas=_family_use_pallas(resolve_use_pallas(use_pallas), kind),
        interpret=interpret))


# ---------------------------------------------------------------------------
# shard_map variant: the pair x gamma axis across devices
# ---------------------------------------------------------------------------

#: Mesh axis the sharded CV grid distributes over (DESIGN.md §4.4).
PAIRGRID_AXIS = "pairgrid"


def _cv_grid_sharded(padded, kind, gammas, cs, n_epochs, mesh,
                     use_pallas=False, interpret=None):
    """The same (P, G, C) CV grid, shard_mapped over flattened pair x gamma.

    Each (pair, gamma) entry is independent (no collectives), so the only
    cost of distribution is that the pairwise-distance hoisting happens per
    entry instead of per pair.  The flattened axis is padded with repeats
    of entry 0 up to a device-count multiple; padded outputs are dropped.
    With ``use_pallas`` each shard's cells run through the fused solver
    lanes (P=cells, G=1) instead of the vmapped blocked solver.
    """
    from jax.sharding import PartitionSpec as P

    if PAIRGRID_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh must carry a {PAIRGRID_AXIS!r} axis (see "
            "launch.mesh.make_trainer_mesh); got axes {mesh.axis_names}")
    n_dev = mesh.shape[PAIRGRID_AXIS]
    p, g = padded.n_pairs, len(gammas)
    total = p * g

    def rep(a):  # (P, ...) -> (P*G, ...), pair-major like the output reshape
        return np.repeat(a, g, axis=0)

    xg, yg = rep(padded.x), rep(padded.y)
    fmg, vg = rep(padded.fold_masks), rep(padded.valid)
    gg = np.tile(np.asarray(gammas, np.float32), p)
    n_pad = (-total) % n_dev
    if n_pad:
        pad = slice(0, 1)
        xg = np.concatenate([xg] + [xg[pad]] * n_pad)
        yg = np.concatenate([yg] + [yg[pad]] * n_pad)
        fmg = np.concatenate([fmg] + [fmg[pad]] * n_pad)
        vg = np.concatenate([vg] + [vg[pad]] * n_pad)
        gg = np.concatenate([gg] + [gg[pad]] * n_pad)

    def local(xs, ys, fs, vs, gs, cs_rep):
        if use_pallas and isinstance(kind, str):
            acc = cv_lanes_accuracy_pallas(
                xs, ys, fs, vs, gs[:, None], cs_rep, kind=kind,
                n_epochs=n_epochs, interpret=interpret, block=SOLVER_BLOCK)
            return acc[:, 0, :]

        def cell(xp, yp, fm, vp, gamma):
            kp = kern.kernel_matrix(kind, xp, xp, gamma) + 1.0
            accs = jax.vmap(
                lambda c: jax.vmap(
                    lambda m: _cell_cv_accuracy(kp, yp, m, vp, c, n_epochs)
                )(fm)
            )(cs_rep)
            return accs.mean(axis=1)
        return jax.vmap(cell)(xs, ys, fs, vs, gs)

    sharded = P(PAIRGRID_AXIS)
    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded, sharded, P()),
        out_specs=sharded, check_vma=False))
    out = fn(jnp.asarray(xg), jnp.asarray(yg), jnp.asarray(fmg),
             jnp.asarray(vg), jnp.asarray(gg),
             jnp.asarray(cs, jnp.float32))
    return np.asarray(out)[:total].reshape(p, g, len(cs))


# ---------------------------------------------------------------------------
# Size-sharded lane layout: per-device programs padded to their own shard max
# ---------------------------------------------------------------------------


def size_sorted_groups(n_true: Sequence[int], n_groups: int,
                       cost: Callable[[int, int], float],
                       combine: Callable[[float, float], float] = max
                       ) -> list[np.ndarray]:
    """Partition items into ``<= n_groups`` contiguous size-sorted groups.

    Items are sorted by true size, and the group boundaries are chosen by
    dynamic programming to minimise ``combine`` over the groups of
    ``cost(count, group_max)``: ``max`` minimises the makespan of
    per-device programs, ``operator.add`` the total work of programs that
    run one after the other.  Of the group counts that reach the same
    minimum the fewest wins.

    Returns a list of index arrays into the ORIGINAL item order; their
    concatenation is a permutation of ``range(len(n_true))``.
    O(n_groups * P^2).
    """
    p = len(n_true)
    if p == 0:
        return []
    n_groups = max(1, min(int(n_groups), p))
    order = np.argsort(np.asarray(n_true), kind="stable")
    sizes = np.asarray(n_true)[order].astype(np.int64)

    inf = float("inf")
    best = [[inf] * (n_groups + 1) for _ in range(p + 1)]
    cut = [[0] * (n_groups + 1) for _ in range(p + 1)]
    best[0][0] = 0.0
    for j in range(1, p + 1):
        for s in range(1, min(n_groups, j) + 1):
            for i in range(s - 1, j):
                if best[i][s - 1] == inf:
                    continue
                # group = sorted items [i, j)
                c = combine(best[i][s - 1], cost(j - i, int(sizes[j - 1])))
                if c < best[j][s]:
                    best[j][s], cut[j][s] = c, i
    s_best = min(range(1, n_groups + 1), key=lambda s: best[p][s])
    bounds, j = [], p
    for s in range(s_best, 0, -1):
        i = cut[j][s]
        bounds.append((i, j))
        j = i
    bounds.reverse()
    return [order[i:j] for i, j in bounds]


def shard_lane_layout(n_true: Sequence[int], n_shards: int
                      ) -> list[np.ndarray]:
    """Partition pairs into ``<= n_shards`` contiguous size-sorted shards.

    The global-pad layout (`pad_pairs` + one program) makes every solver
    lane pay ``n_max^2`` work; with the long-tailed subset sizes of a
    K>=10 OvO grid (har12: 198..1582) most of that is padding.  This
    layout sorts pairs by true subset size and chooses shard boundaries
    by dynamic programming (`size_sorted_groups`) to minimize the
    MAKESPAN of padded work, modeling a shard's cost as
    ``count * shard_max^2`` (the blocked solver's dominant term).  Each
    shard is then trimmed to its own max (``PaddedPairs.trim``) and
    dispatched as its own program, so the padding waste is bounded by the
    within-shard size spread rather than the global one.

    Returns a list of index arrays into the ORIGINAL pair order; their
    concatenation is a permutation of ``range(len(n_true))``.  At
    ``n_shards=1`` this degenerates to the seed layout (one shard, global
    max).  O(n_shards * P^2) — trivial at P=66.
    """
    return size_sorted_groups(n_true, n_shards, lambda n, m: n * m ** 2)


def family_cv_grid_size_sharded(
    padded: PaddedPairs,
    kind,
    gammas: np.ndarray,
    cs: np.ndarray,
    n_epochs: int,
    devices=None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """(P, G, C) CV grid via size-sharded per-device lane programs.

    Where `_cv_grid_sharded` shard_maps ONE program padded to the global
    ``n_max`` over the pair x gamma axis, this driver partitions pairs by
    subset size (`shard_lane_layout`), trims each shard to its own max,
    and dispatches one `_cv_grid_all_pairs` program per shard to its
    device.  Dispatch is asynchronous (jit returns before completion), so
    on a multi-device host shards overlap; results are gathered back into
    the original pair order.  Compile budget: one compile per distinct
    shard shape, i.e. <= len(devices) programs.

    On a single-device host the win is the padded-work saving alone —
    har12's size spread makes the summed ``count * shard_max^2`` roughly
    3.9x smaller at 8 shards than the global pad, independent of device
    count.
    """
    kind = _training_kernel(kind)
    use_pallas = _family_use_pallas(resolve_use_pallas(use_pallas), kind)
    if devices is None:
        devices = jax.devices()
    shards = shard_lane_layout(padded.n_true, len(devices))
    g_host = np.asarray(gammas, np.float32)
    c_host = np.asarray(cs, np.float32)
    out = np.empty((padded.n_pairs, len(g_host), len(c_host)), np.float32)
    pending = []
    for shard_idx, dev in zip(shards, devices):
        sub = padded.take([int(i) for i in shard_idx]).trim()
        put = lambda a: jax.device_put(jnp.asarray(a), dev)
        acc = _cv_grid_all_pairs(
            put(sub.x), put(sub.y), put(sub.fold_masks), put(sub.valid),
            put(g_host), put(c_host), kind=kind, n_epochs=n_epochs,
            use_pallas=use_pallas, interpret=interpret)
        pending.append((shard_idx, acc))
    for shard_idx, acc in pending:
        out[np.asarray(shard_idx)] = np.asarray(acc)
    return out


# ---------------------------------------------------------------------------
# Selection + model extraction (host-side, replicates svm.train_binary)
# ---------------------------------------------------------------------------


def _argmax_grid(acc: np.ndarray, gammas: np.ndarray, cs: np.ndarray
                 ) -> tuple[float, float, float]:
    """fit_best's line-8 pick: first flat argmax, gamma-major order."""
    gi, ci = np.unravel_index(np.argmax(acc), acc.shape)
    return float(gammas[gi]), float(cs[ci]), float(acc[gi, ci])


def _extract_model(
    kind,
    xb: np.ndarray,
    yb: np.ndarray,
    alpha_row: np.ndarray,
    gamma: float,
    c: float,
    sv_tol: float = 1e-6,
) -> SVMModel:
    """Support-set extraction — the exact tail of ``svm.train_binary``."""
    alpha = np.asarray(alpha_row[: len(yb)])
    sv = alpha > sv_tol
    bias = float(np.sum(alpha[sv] * yb[sv]))
    w = None
    if kind == "linear":
        w = np.asarray((alpha[sv] * yb[sv]) @ xb[sv], np.float64)
    return SVMModel(
        kind=kind if isinstance(kind, str) else "hw",
        support_x=np.asarray(xb[sv], np.float64),
        support_y=np.asarray(yb[sv], np.float64),
        alpha=np.asarray(alpha[sv], np.float64),
        bias=bias,
        gamma=float(gamma),
        c=float(c),
        w=w,
        kernel_fn=None if isinstance(kind, str) else kind,
    )


def _train_family(
    padded: PaddedPairs,
    kind,
    gammas: np.ndarray,
    cs: np.ndarray,
    n_epochs: int,
    cv_epochs: int,
    mesh=None,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
) -> tuple[list[SVMModel], list[float]]:
    """CV-grid + select + refit one family for every pair in ``padded``.

    Without a mesh this is ONE compiled program (`_family_program`); the
    shard_map path splits into the sharded CV grid, a host-side argmax and
    the (small) vmapped refit program.
    """
    if mesh is not None:
        acc = family_cv_grid(padded, kind, gammas, cs, cv_epochs, mesh=mesh,
                             use_pallas=use_pallas, interpret=interpret)
        sel = [_argmax_grid(acc[i], gammas, cs)
               for i in range(padded.n_pairs)]
        g_sel = np.asarray([s[0] for s in sel], np.float32)
        c_sel = np.asarray([s[1] for s in sel], np.float32)
        alphas = family_refit(padded, kind, g_sel, c_sel, n_epochs,
                              use_pallas=use_pallas, interpret=interpret)
    else:
        kind_t = _training_kernel(kind)
        acc, gi, ci, alphas = _family_program(
            jnp.asarray(padded.x), jnp.asarray(padded.y),
            jnp.asarray(padded.fold_masks), jnp.asarray(padded.valid),
            jnp.asarray(gammas, jnp.float32), jnp.asarray(cs, jnp.float32),
            kind=kind_t, cv_epochs=int(cv_epochs),
            n_epochs=int(n_epochs),
            use_pallas=_family_use_pallas(use_pallas, kind_t),
            interpret=interpret)
        acc, alphas = np.asarray(acc), np.asarray(alphas)
        sel = [(float(gammas[g]), float(cs[c]), float(acc[p, g, c]))
               for p, (g, c) in enumerate(zip(np.asarray(gi),
                                              np.asarray(ci)))]
    models = [
        _extract_model(kind, xb, yb, alphas[i], sel[i][0], sel[i][1])
        for i, (xb, yb) in enumerate(padded.subsets)
    ]
    return models, [s[2] for s in sel]


def train_pairs(
    x_train: np.ndarray,
    y_train: np.ndarray,
    n_classes: int,
    hw: Optional[AnalogRBFModel] = None,
    n_epochs: int = 200,
    seed: int = 0,
    tie_margin: float = 0.005,
    cv_epochs: Optional[int] = None,
    n_folds: int = 5,
    mesh=None,
    hw_all: bool = False,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> list[PairResult]:
    """Algorithm 1, batched: one compiled program per kernel family.

    Semantics match ``selection.train_pairs_sequential`` (same CV folds,
    grids, tie margin and hardware-in-the-loop retraining), with
    ``cv_epochs`` controlling the fold-training epochs (default: the
    historical ``max(60, n_epochs // 2)``).  ``mesh`` optionally runs the
    CV grids under shard_map (see :data:`PAIRGRID_AXIS`).

    ``use_pallas`` routes the linear/rbf families through the fused
    Gram-free Pallas solver (``repro.kernels.solver``); ``None`` follows
    the ``api/compiled.py`` convention (on only where the tiles compile
    to Mosaic, i.e. TPU), and ``interpret`` forces the Pallas interpreter
    so CPU CI can exercise the code path deliberately.  The
    hardware-in-the-loop family always keeps the blocked XLA solver
    (measured-curve kernels have no tile body).  Selections agree with
    the blocked path to the documented comparator-tie epsilon.

    ``hw_all=True`` keeps the hardware co-optimized ``model_hw`` for EVERY
    pair instead of only the RBF-selected ones.  The engine trains the hw
    family for all pairs anyway (see the jobs comment below), so this is
    free — it is what gives the kernel-assignment design space
    (``repro.core.dse``) an RBF-analog candidate per pair.  The default
    ``False`` preserves the sequential path's deployment contract.
    """
    if hw is None:
        hw = default_hw(seed)
    if cv_epochs is None:
        cv_epochs = max(60, n_epochs // 2)
    cv_epochs = int(cv_epochs)
    use_pallas = resolve_use_pallas(use_pallas)

    padded = pad_pairs(x_train, y_train, n_classes, n_folds=n_folds,
                       seed=seed)
    cs = DEFAULT_CS

    # The three families (linear, rbf, sech2 hardware-in-the-loop) are
    # data-independent, so their compiled programs are dispatched from
    # worker threads: XLA compilation and execution overlap across cores.
    # The hw family is trained for EVERY pair up front (rather than a
    # sub-stack of the RBF-selected pairs afterwards) — a little wasted
    # compute on linear-bound pairs (the paper's regime is P <= 10) buys
    # full three-way concurrency and a sub-stack-shape-independent compile.
    jobs = {
        "linear": (padded, "linear", np.array([1.0]), cs),
        "rbf": (padded, "rbf", DEFAULT_RBF_GAMMAS, cs),
        "hw": (padded, hw.kernel_response, hw_gamma_grid(hw), cs),
    }
    if mesh is None:
        import os
        from concurrent.futures import ThreadPoolExecutor

        workers = max(1, min(len(jobs), os.cpu_count() or 1))
        with ThreadPoolExecutor(max_workers=workers) as ex:
            futs = {k: ex.submit(_train_family, *a, n_epochs, cv_epochs,
                                 None, use_pallas, interpret)
                    for k, a in jobs.items()}
            out = {k: f.result() for k, f in futs.items()}
    else:
        # shard_map programs already span every device; run them in turn.
        out = {k: _train_family(*a, n_epochs, cv_epochs, mesh,
                                use_pallas, interpret)
               for k, a in jobs.items()}
    lin_models, lin_accs = out["linear"]
    rbf_models, rbf_accs = out["rbf"]
    hw_models, _ = out["hw"]

    # Line 8: RBF only when STRICTLY better (beyond the CV-noise margin).
    kinds = ["rbf" if a_r > a_l + tie_margin else "linear"
             for a_l, a_r in zip(lin_accs, rbf_accs)]

    results = []
    for i, pair in enumerate(padded.pairs):
        kind = kinds[i]
        # model_hw is only *kept* for RBF-assigned pairs (the deployment
        # contract of the sequential path) unless hw_all opts into keeping
        # every pair's analog candidate for the DSE.
        m_hw = hw_models[i] if (hw_all or kind == "rbf") else None
        results.append(PairResult(
            pair=pair, kernel=kind,
            model=m_hw if kind == "rbf" else lin_models[i],
            acc_linear=lin_accs[i], acc_rbf=rbf_accs[i],
            model_linear=lin_models[i], model_rbf=rbf_models[i],
            model_hw=m_hw,
        ))
    return results
