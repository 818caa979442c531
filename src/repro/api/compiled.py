"""CompiledMachine: one batched JAX inference path for an OvO classifier bank.

The legacy object path (``MulticlassSVM.predict_bits``) is a host-side Python
loop: one ``predict_bits`` call per pair classifier, each with its own device
dispatches and host round-trips.  ``compile_machine`` *lowers* any bank of
bit-classifiers into padded, stacked arrays grouped into a small number of
homogeneous "banks", and ``CompiledMachine.predict`` evaluates the whole
machine — every pair score, the comparator bits, and the decision encoder —
inside a single jit-compiled function: one device round-trip per batch.

Pytree layout (DESIGN.md §1.2)
------------------------------
Pairs are grouped by datapath; each group is one bank of stacked arrays:

* ``_LinearBank``  — all pairs whose score is an affine form.  One fused
  matmul ``x_q @ W.T + b`` scores every linear pair at once.
  Fields: ``w (P, d)``, ``b (P,)``; static: ``input_bits``, ``pair_idx``.

* ``_KernelBank``  — kernel pairs sharing (kernel kind, input quantization,
  transfer curve).  Support vectors are padded to the bank max ``M`` and
  stacked; padded slots carry coefficient 0 so they contribute exactly
  nothing.  Fields: ``sv (P, M, d)``, ``coef_pos/coef_neg (P, M)``,
  ``bias_pos/bias_neg/offset/gamma/scale (P,)`` plus the measured transfer
  curve (``grid``, ``curve``) for the analog 'hw' kind.  The pos/neg split
  mirrors the analog rails: ``f = (K @ c+ + b+) - (K @ c- + b-) + offset``
  reproduces the comparator's current difference bit-for-bit; digital and
  float pairs simply keep the negative rail empty.

Kernel dispatch: 'rbf' and 'sech2' banks go to the tiled Pallas kernel
(``repro.kernels.ops.rbf_matrix``) when ``use_pallas`` is on (default: only
on TPU, where the tiles compile to Mosaic; the CPU container would run the
Pallas interpreter, so it uses the identical-math jnp path instead).  The
'hw' kind evaluates the calibrated measured-curve kernel (interp + product)
exactly as the behavioral model does.

The decision encoder is the packed truth table of ``build_encoder_table``
for P <= 12 pair bits (the paper's K <= 5 regime); larger machines fall back
to the equivalent votes-matmul + argmax (lowest-index tiebreak).

``compile_candidates`` / ``CandidateMachine`` reuse the same lowering and
bank evaluation to expose the assignment-independent per-pair candidate
bit tensor ``pair_bits(x) -> (n, P, 2)`` that the kernel-assignment
design-space explorer (``repro.core.dse``, DESIGN.md §5) recombines into
every candidate machine's output without re-evaluating any classifier.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import json
import operator
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import dse
from repro.core import kernels as kern
from repro.core import mcstream, quant, trainer
from repro.core.analog import (
    N_ALPHA_OFFSETS,
    N_GAUSS_OFFSETS,
    AnalogBinaryClassifier,
    VariantSet,
    variant_dim,
    variant_set_from_flat,
    variant_transfer_params,
)
from repro.core.ovo import (
    MAX_TABLE_BITS,
    DigitalLinearClassifier,
    DigitalRBFClassifier,
    MulticlassSVM,
    build_encoder_table,
    class_pairs,
    pair_index_matrix,
)
from repro.core.svm import SVMModel

_FORMAT_VERSION = 1

# MAX_TABLE_BITS (re-exported above from repro.core.ovo): encoder truth
# tables are materialised up to that many pair bits (2^12 = 4096 entries);
# beyond it the votes matmul — or the O(K) DAG front — is used.


# ---------------------------------------------------------------------------
# Per-pair lowering specs (host-side, produced by compile_machine)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _LinearSpec:
    pair: int
    input_bits: int          # 0 = float input, else ADC bits
    w: np.ndarray            # (d,)
    b: float


@dataclasses.dataclass
class _KernelSpec:
    pair: int
    kind: str                # 'rbf' | 'sech2' | 'hw'
    input_bits: int
    sv: np.ndarray           # (m, d)
    coef_pos: np.ndarray     # (m,)
    coef_neg: np.ndarray     # (m,)
    bias_pos: float
    bias_neg: float
    offset: float            # comparator offset (analog), else 0
    gamma: float             # rbf/sech2 width; unused for 'hw'
    scale: float             # 'hw': prefolded v_scale * input_scale(gamma*)
    shift: float = 0.0       # 'hw': fitted center offset mu (kernel_1d query)
    grid: Optional[np.ndarray] = None    # 'hw': measured sweep abscissa (V)
    curve: Optional[np.ndarray] = None   # 'hw': measured transfer, peak 1
    left: float = 0.0        # interp clamp values
    right: float = 0.0


def _f32(a) -> np.ndarray:
    return np.asarray(a, np.float32)


def _lower_svm_model(idx: int, model: SVMModel) -> _LinearSpec | _KernelSpec:
    """Lower a float SVMModel (a FloatBitClassifier's payload)."""
    if model.kind == "linear" and model.w is not None:
        return _LinearSpec(pair=idx, input_bits=0, w=_f32(model.w),
                           b=float(model.bias))
    coef = _f32(model.alpha * model.support_y)
    base = dict(pair=idx, input_bits=0, sv=_f32(model.support_x),
                coef_pos=coef, coef_neg=np.zeros_like(coef),
                bias_pos=float(model.bias), bias_neg=0.0, offset=0.0)
    if model.kind in ("rbf", "sech2"):
        return _KernelSpec(kind=model.kind, gamma=float(model.gamma),
                           scale=1.0, **base)
    if model.kind == "hw":
        hw = getattr(model.kernel_fn, "__self__", None)
        if hw is None:
            raise TypeError(
                "cannot lower kind='hw' model: kernel_fn is not a bound "
                "AnalogRBFModel.kernel_response method")
        # Prefold the Eq.-8 input scaling exactly as kernel_response does:
        # dv = (v_scale * s) * (x - sv), with the product taken in f32.
        scale = float(jnp.float32(hw.v_scale)
                      * hw.input_scale(jnp.float32(model.gamma)))
        curve = _f32(hw.kernel_curve)
        return _KernelSpec(kind="hw", gamma=float(model.gamma), scale=scale,
                           shift=float(hw.mu), grid=_f32(hw.dv_grid),
                           curve=curve, left=float(hw.kernel_curve[0]),
                           right=float(hw.kernel_curve[-1]), **base)
    raise TypeError(f"cannot lower SVMModel of kind {model.kind!r}")


def _lower_classifier(idx: int, clf) -> _LinearSpec | _KernelSpec:
    """Lower one bit-classifier object into its stacked-array spec."""
    if isinstance(clf, DigitalLinearClassifier):
        return _LinearSpec(pair=idx, input_bits=clf.input_bits,
                           w=_f32(clf.w_q), b=float(clf.b_q))
    if isinstance(clf, DigitalRBFClassifier):
        coef = _f32(clf.coef)
        return _KernelSpec(
            pair=idx, kind="rbf", input_bits=clf.input_bits,
            sv=_f32(clf.support_x), coef_pos=coef,
            coef_neg=np.zeros_like(coef), bias_pos=float(clf.bias),
            bias_neg=0.0, offset=0.0, gamma=float(clf.gamma), scale=1.0)
    if isinstance(clf, AnalogBinaryClassifier):
        hw = clf.hw
        # Freeze the alpha path at compile time with the very same f32 ops
        # the behavioral model runs per call: desired alpha -> control
        # voltage (Eq. 9) -> realised alpha (measured sweep).
        dva = hw.alpha_control_voltage(jnp.asarray(clf.alpha_hw, jnp.float32))
        a = _f32(hw.alpha_realized(dva))
        pos = (clf.support_y > 0)
        scale = float(jnp.float32(hw.v_scale)
                      * hw.input_scale(jnp.float32(clf.gamma_star)))
        return _KernelSpec(
            pair=idx, kind="hw", input_bits=0, sv=_f32(clf.support_x),
            coef_pos=a * pos, coef_neg=a * (~pos),
            bias_pos=float(max(clf.bias_hw, 0.0)),
            bias_neg=float(max(-clf.bias_hw, 0.0)),
            offset=float(hw.params.comparator_offset / hw.params.i_bias),
            gamma=float(clf.gamma_star), scale=scale, shift=float(hw.mu),
            grid=_f32(hw.dv_grid), curve=_f32(hw.kernel_curve),
            left=float(hw.kernel_curve[0]), right=float(hw.kernel_curve[-1]))
    if isinstance(clf, SVMModel):
        return _lower_svm_model(idx, clf)
    model = getattr(clf, "model", None)   # FloatBitClassifier & duck-typed
    if isinstance(model, SVMModel):
        return _lower_svm_model(idx, model)
    raise TypeError(f"cannot lower classifier of type {type(clf).__name__}")


# ---------------------------------------------------------------------------
# Banks: grouped, padded, stacked arrays
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _LinearBank:
    input_bits: int
    pair_idx: np.ndarray     # (P,)
    w: jnp.ndarray           # (P, d)
    b: jnp.ndarray           # (P,)

    @classmethod
    def build(cls, specs: list[_LinearSpec]) -> "_LinearBank":
        return cls(
            input_bits=specs[0].input_bits,
            pair_idx=np.asarray([s.pair for s in specs]),
            w=jnp.asarray(np.stack([s.w for s in specs])),
            b=jnp.asarray(np.asarray([s.b for s in specs], np.float32)),
        )


@dataclasses.dataclass
class _KernelBank:
    kind: str
    input_bits: int
    pair_idx: np.ndarray     # (P,)
    sv: jnp.ndarray          # (P, M, d), zero-padded to bank max M
    coef_pos: jnp.ndarray    # (P, M), 0 on padded slots
    coef_neg: jnp.ndarray    # (P, M)
    bias_pos: jnp.ndarray    # (P,)
    bias_neg: jnp.ndarray    # (P,)
    offset: jnp.ndarray      # (P,)
    gamma: jnp.ndarray       # (P,)
    scale: jnp.ndarray       # (P,)
    shift: jnp.ndarray = None  # (P,) 'hw' center offsets
    grid: Optional[jnp.ndarray] = None
    curve: Optional[jnp.ndarray] = None
    left: float = 0.0
    right: float = 0.0
    # Uniform-grid fast path for the measured-curve interpolation (derived
    # from `grid` at build/load time, not serialized).
    uniform_grid: bool = False
    inv_step: float = 0.0

    @classmethod
    def build(cls, specs: list[_KernelSpec]) -> "_KernelBank":
        m_max = max(s.sv.shape[0] for s in specs)

        def pad(a):
            out = np.zeros((m_max,) + a.shape[1:], np.float32)
            out[: a.shape[0]] = a
            return out

        s0 = specs[0]
        return cls(
            kind=s0.kind, input_bits=s0.input_bits,
            pair_idx=np.asarray([s.pair for s in specs]),
            sv=jnp.asarray(np.stack([pad(s.sv) for s in specs])),
            coef_pos=jnp.asarray(
                np.stack([pad(s.coef_pos) for s in specs])),
            coef_neg=jnp.asarray(
                np.stack([pad(s.coef_neg) for s in specs])),
            bias_pos=jnp.asarray(
                np.asarray([s.bias_pos for s in specs], np.float32)),
            bias_neg=jnp.asarray(
                np.asarray([s.bias_neg for s in specs], np.float32)),
            offset=jnp.asarray(
                np.asarray([s.offset for s in specs], np.float32)),
            gamma=jnp.asarray(
                np.asarray([s.gamma for s in specs], np.float32)),
            scale=jnp.asarray(
                np.asarray([s.scale for s in specs], np.float32)),
            shift=jnp.asarray(
                np.asarray([s.shift for s in specs], np.float32)),
            grid=None if s0.grid is None else jnp.asarray(s0.grid),
            curve=None if s0.curve is None else jnp.asarray(s0.curve),
            left=s0.left, right=s0.right,
            **_grid_fast_path(s0.grid),
        )


# Uniform-grid fast-path helpers now live in repro.core.kernels (the batched
# trainer uses them for hardware-in-the-loop training too); re-exported here
# for existing call sites and tests.
from repro.core.kernels import (  # noqa: E402  (re-export)
    _grid_fast_path,
    _grid_is_uniform,
    _uniform_interp,
)


# The banks are genuine pytrees: array fields are leaves, everything that
# selects a compiled program (kind, bit widths, grid geometry) is static
# aux data.  Today the machines close over the banks as jit constants;
# registration is what lets them cross a jit boundary as *arguments*
# instead (the bank-donation refactor ROADMAP item 2 needs) without the
# trace treating them as opaque objects.  pair_idx is host-side build
# metadata — it rides in aux as a hashable tuple.

def _linear_bank_flatten(b: _LinearBank):
    return (b.w, b.b), (b.input_bits, tuple(b.pair_idx.tolist()))


def _linear_bank_unflatten(aux, children) -> _LinearBank:
    input_bits, pair_idx = aux
    w, b = children
    return _LinearBank(input_bits=input_bits,
                       pair_idx=np.asarray(pair_idx), w=w, b=b)


jax.tree_util.register_pytree_node(
    _LinearBank, _linear_bank_flatten, _linear_bank_unflatten)

_KERNEL_BANK_DATA = ("sv", "coef_pos", "coef_neg", "bias_pos", "bias_neg",
                     "offset", "gamma", "scale", "shift", "grid", "curve")
_KERNEL_BANK_AUX = ("kind", "input_bits", "left", "right", "uniform_grid",
                    "inv_step")


def _kernel_bank_flatten(b: _KernelBank):
    aux = tuple(getattr(b, f) for f in _KERNEL_BANK_AUX) \
        + (tuple(b.pair_idx.tolist()),)
    return tuple(getattr(b, f) for f in _KERNEL_BANK_DATA), aux


def _kernel_bank_unflatten(aux, children) -> _KernelBank:
    kw = dict(zip(_KERNEL_BANK_DATA, children))
    kw.update(zip(_KERNEL_BANK_AUX, aux))
    kw["pair_idx"] = np.asarray(aux[-1])
    return _KernelBank(**kw)


jax.tree_util.register_pytree_node(
    _KernelBank, _kernel_bank_flatten, _kernel_bank_unflatten)


def _kernel_group_key(s: _KernelSpec):
    curve_key = None
    if s.grid is not None:
        curve_key = (s.grid.shape[0], hash(s.grid.tobytes()),
                     hash(s.curve.tobytes()))
    return (s.kind, s.input_bits, curve_key)


# ---------------------------------------------------------------------------
# Bank evaluation: shared by CompiledMachine and CandidateMachine
# ---------------------------------------------------------------------------


def _bank_cell(bank: _KernelBank, dv: jnp.ndarray) -> jnp.ndarray:
    """The bank's measured 1-D transfer (shared nominal/variant code path)."""
    return kern.measured_cell(dv, bank.grid, bank.curve, bank.left,
                              bank.right, bank.uniform_grid,
                              jnp.float32(bank.inv_step))


def _pair_kernel(bank: _KernelBank, xv: jnp.ndarray, sv: jnp.ndarray,
                 gamma, scale, shift, use_pallas: bool,
                 vshift=None, vgain=None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """(n, M) kernel matrix of ONE pair (vmapped over the bank).

    ``vshift``/``vgain`` (M, d), when given, evaluate ONE Monte-Carlo
    variant's per-cell perturbed transfers (DESIGN.md §6.2):
    ``gain * curve(dv + mu - vshift)``.  The zero-offset variant subtracts
    an exact 0 and multiplies by an exact 1 around the very same
    ``_bank_cell`` interpolation the nominal path runs — bit-identical.
    """
    if bank.kind == "hw":
        d = int(bank.sv.shape[-1])
        # Per-dimension accumulation: (n, M) temporaries instead of one
        # (n, M, d) tensor — same sequential multiply order as jnp.prod,
        # far less memory traffic.  d <= 5 in hardware.
        acc = None
        for k in range(d):
            dv = scale * (xv[:, k:k + 1] - sv[None, :, k]) + shift
            if vshift is not None:
                dv = dv - vshift[None, :, k]
            k1 = _bank_cell(bank, dv)
            if vgain is not None:
                k1 = k1 * vgain[None, :, k]
            acc = k1 if acc is None else acc * k1
        return acc
    if use_pallas:
        from repro.kernels import ops

        return ops.rbf_matrix(xv, sv, gamma, kind=bank.kind, v_scale=1.0,
                              interpret=interpret)
    return kern.kernel_matrix(bank.kind, xv, sv, gamma)


def _bank_scores(bank: _KernelBank, xv: jnp.ndarray,
                 use_pallas: bool,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """(n, P) decision scores for one kernel bank, kernel + contraction
    fused per pair: the (n, M) kernel tile feeds one (M, 2) GEMM for the
    +/- rails while it is still hot."""

    def one(sv, gamma, scale, shift, cpos, cneg, bpos, bneg, off):
        k = _pair_kernel(bank, xv, sv, gamma, scale, shift, use_pallas,
                         interpret=interpret)
        rails = kern.matmul(k, jnp.stack([cpos, cneg], axis=1))  # (n, 2)
        return (rails[:, 0] + bpos) - (rails[:, 1] + bneg) + off

    return jax.vmap(one, out_axes=1)(
        bank.sv, bank.gamma, bank.scale, bank.shift,
        bank.coef_pos, bank.coef_neg,
        bank.bias_pos, bank.bias_neg, bank.offset)


def _all_scores(x: jnp.ndarray, linear_banks, kernel_banks,
                inv_perm: jnp.ndarray, use_pallas: bool,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (n, d) f32 -> scores (n, P) in lowering (pair-index) order.

    Input quantization is computed once per distinct ADC width and shared
    across banks; the bank columns are concatenated and un-permuted back to
    pair order through ``inv_perm``.
    """
    xq_cache: dict[int, jnp.ndarray] = {}

    def xq(bits: int) -> jnp.ndarray:
        if bits not in xq_cache:
            xq_cache[bits] = x if bits == 0 else quant.quantize_unit(x, bits)
        return xq_cache[bits]

    cols = []
    for bank in linear_banks:
        cols.append(kern.matmul(xq(bank.input_bits), bank.w.T)
                    + bank.b[None, :])
    for bank in kernel_banks:
        cols.append(_bank_scores(bank, xq(bank.input_bits), use_pallas,
                                 interpret=interpret))
    return jnp.concatenate(cols, axis=1)[:, inv_perm]


def _group_specs(
    specs: list,
) -> tuple[list[list[_LinearSpec]], list[list[_KernelSpec]]]:
    """Group lowered specs by datapath (the bank partition)."""
    linear_groups: dict[int, list[_LinearSpec]] = {}
    kernel_groups: dict[tuple, list[_KernelSpec]] = {}
    for s in specs:
        if isinstance(s, _LinearSpec):
            linear_groups.setdefault(s.input_bits, []).append(s)
        else:
            kernel_groups.setdefault(_kernel_group_key(s), []).append(s)
    return list(linear_groups.values()), list(kernel_groups.values())


def _build_banks(specs: list) -> tuple[list[_LinearBank], list[_KernelBank]]:
    """Group lowered specs by datapath into padded stacked banks."""
    linear_groups, kernel_groups = _group_specs(specs)
    return ([_LinearBank.build(g) for g in linear_groups],
            [_KernelBank.build(g) for g in kernel_groups])


def _inverse_perm(linear_banks, kernel_banks, n_total: int,
                  stand_in: Optional[dict] = None) -> jnp.ndarray:
    """Column order after bank concatenation -> lowering order inversion.

    ``stand_in`` maps a lowering column that no bank holds to the lowering
    column it reads instead."""
    stand_in = stand_in or {}
    order = np.concatenate(
        [b.pair_idx for b in linear_banks]
        + [b.pair_idx for b in kernel_banks]).astype(np.int64)
    if order.shape[0] + len(stand_in) != n_total:
        raise ValueError(
            f"{order.shape[0]} lowered columns + {len(stand_in)} stand-ins "
            f"!= {n_total} expected")
    inv = np.empty(n_total, np.int64)
    inv[order] = np.arange(order.shape[0])
    for col, src in stand_in.items():
        inv[col] = inv[src]
    return jnp.asarray(inv)


def _bank_feature_dim(linear_banks, kernel_banks) -> int:
    dims = {int(b.w.shape[1]) for b in linear_banks} | \
        {int(b.sv.shape[2]) for b in kernel_banks}
    if len(dims) > 1:
        raise ValueError(f"inconsistent feature counts across banks: {dims}")
    return dims.pop() if dims else 0


# ---------------------------------------------------------------------------
# Decision encoder: packed truth table or votes matmul
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Decider:
    """Pair bits ``(..., P)`` -> class labels ``(...,)``.

    The packed truth table of ``build_encoder_table`` in the FE regime
    (``P <= MAX_TABLE_BITS``), the equivalent votes matmul + argmax
    (lowest-index tiebreak) beyond it.  Shared by :class:`CompiledMachine`
    and the multi-model :class:`~repro.api.fleet.FleetMachine`, so the
    fleet's per-member decision subgraph is literally the member's own.
    """

    table: Optional[jnp.ndarray]        # (2^P,) packed labels, or None
    bit_weights: Optional[jnp.ndarray]  # (P,) 1 << arange(P), or None
    vote_a: Optional[jnp.ndarray]       # (P, K) votes for class i of pair
    vote_b: Optional[jnp.ndarray]       # (P, K) votes for class j of pair

    @classmethod
    def build(cls, n_classes: int) -> "_Decider":
        pairs = class_pairs(n_classes)
        n_pairs = len(pairs)
        if n_pairs <= MAX_TABLE_BITS:
            return cls(
                table=jnp.asarray(build_encoder_table(n_classes)),
                bit_weights=jnp.asarray(
                    (1 << np.arange(n_pairs)).astype(np.int32)),
                vote_a=None, vote_b=None)
        a = np.zeros((n_pairs, n_classes), np.int32)
        b = np.zeros((n_pairs, n_classes), np.int32)
        for p, (i, j) in enumerate(pairs):
            a[p, i] = 1
            b[p, j] = 1
        return cls(table=None, bit_weights=None,
                   vote_a=jnp.asarray(a), vote_b=jnp.asarray(b))

    def __call__(self, bits: jnp.ndarray) -> jnp.ndarray:
        if self.table is not None:
            return jnp.take(self.table, bits @ self.bit_weights)
        votes = bits @ self.vote_a + (1 - bits) @ self.vote_b
        return jnp.argmax(votes, axis=-1)


# Registered so a decider can cross jit boundaries as an argument (None
# fields are empty subtrees); which of the two paths runs is decided at
# trace time by the table's presence.
jax.tree_util.register_dataclass(
    _Decider,
    data_fields=("table", "bit_weights", "vote_a", "vote_b"),
    meta_fields=())


# ---------------------------------------------------------------------------
# DAG decision front: O(K) pair evaluations per sample (DESIGN.md §11)
# ---------------------------------------------------------------------------

#: Decision fronts a machine can be compiled with.  ``"votes"`` is the
#: seed semantics (every pair evaluated, encoder table / votes argmax);
#: ``"dag"`` is the DDAG elimination front — K-1 pair evaluations per
#: sample, exactly equal to votes wherever a Condorcet winner exists
#: (``repro.core.ovo.decide_dag`` states and proves the contract).
DECIDERS = ("votes", "dag")


def _dag_row_maps(linear_banks, kernel_banks, n_pairs: int):
    """Host-built global-pair -> bank-row gather maps, one per bank.

    For each bank (linear banks first, then kernel banks — the DAG front
    iterates them in the same order) returns ``(rows, mask)``: ``rows
    (P,)`` int32 maps a global pair index to the bank row holding it
    (clamped to 0 where the bank does not own the pair) and ``mask (P,)``
    f32 is 1.0 exactly on the owned pairs.  A sample's per-step score is
    the masked sum over banks, so every pair is scored by precisely the
    datapath that owns it.
    """
    maps = []
    for b in list(linear_banks) + list(kernel_banks):
        rows = np.zeros(n_pairs, np.int32)
        mask = np.zeros(n_pairs, np.float32)
        for r, g in enumerate(np.asarray(b.pair_idx)):
            rows[int(g)] = r
            mask[int(g)] = 1.0
        maps.append((jnp.asarray(rows), jnp.asarray(mask)))
    return maps


def _dag_step_plans(linear_banks, kernel_banks, n_classes: int):
    """Static per-step work plans for the DAG gather front.

    At step ``t`` the carried interval satisfies ``hi - lo == K-1-t``, so
    the only pairs a sample can visit are ``{(j, j + K-1-t) : j <= t}`` —
    a set known at trace time.  For every bank and step this precomputes:

    * ``None`` — the bank owns no reachable pair: skip it entirely (its
      masked contribution would be an exact ``0.0`` for every sample);
    * ``-1`` (linear banks) — participate, nothing to slice;
    * ``m_t > 0`` (kernel banks) — participate, and statically slice the
      support axis to the max TRUE support count over the reachable owned
      pairs.  Padded slots carry zero coefficients, so dropping them
      removes exact ``+0.0`` terms from the score sum — bit-identical
      labels, ``sum_t m_t`` kernel evaluations per sample instead of
      ``(K-1) * M``.

    On mixed Algorithm-1 designs this is a large static win: far-apart
    class pairs (the early, large-gap steps) are typically linear, so
    whole kernel banks drop out of the first steps, and the hard
    small-gap pairs that stay analog rarely all share the bank-wide
    padded ``M``.
    """
    pairs = class_pairs(n_classes)
    idx = {p: i for i, p in enumerate(pairs)}
    n_lin = len(linear_banks)
    owned = []
    for bi, b in enumerate(list(linear_banks) + list(kernel_banks)):
        if bi < n_lin:
            owned.append({int(g): 0 for g in np.asarray(b.pair_idx)})
        else:
            coef = np.abs(np.asarray(b.coef_pos)) \
                + np.abs(np.asarray(b.coef_neg))           # (P, M)
            true_m = (coef != 0.0).sum(axis=1)
            owned.append({int(g): int(mm)
                          for g, mm in zip(np.asarray(b.pair_idx), true_m)})
    plans = []
    for t in range(n_classes - 1):
        gap = n_classes - 1 - t
        reach = [idx[(j, j + gap)] for j in range(t + 1)]
        plan = []
        for bi, o in enumerate(owned):
            ms = [o[p] for p in reach if p in o]
            if not ms:
                plan.append(None)
            elif bi < n_lin:
                plan.append(-1)
            else:
                plan.append(max(max(ms), 1))
        plans.append(tuple(plan))
    return plans


def _gather_pair_scores(p, linear_banks, kernel_banks, row_maps, xq_cache,
                        plan=None):
    """Decision scores of ONE (per-sample dynamic) pair: ``p (n,) -> (n,)``.

    The gather sibling of ``_all_scores``: instead of evaluating every
    bank column, each sample gathers the parameters of the single pair
    ``p[i]`` from the bank that owns it and evaluates just that one
    classifier.  Kernel banks run the per-sample kernel through the SAME
    ``_pair_kernel`` arithmetic as the dense path (``use_pallas=False``
    deliberately: the Pallas tile kernels are per-pair-column programs and
    would degenerate under the per-sample vmap; the jnp lowering is
    bit-identical math).

    ``plan`` (one entry of :func:`_dag_step_plans`) statically skips
    banks that own no reachable pair this step and slices kernel-bank
    gathers to the reachable true support count — both exact.
    """
    total = jnp.zeros(p.shape[0], jnp.float32)
    mi = 0
    for bank in linear_banks:
        rows, mask = row_maps[mi]
        step = None if plan is None else plan[mi]
        mi += 1
        if plan is not None and step is None:
            continue
        r = rows[p]                                        # (n,)
        xv = xq_cache[bank.input_bits]
        s = jnp.sum(xv * bank.w[r], axis=-1) + bank.b[r]
        total = total + mask[p] * s
    for bank in kernel_banks:
        rows, mask = row_maps[mi]
        step = None if plan is None else plan[mi]
        mi += 1
        if plan is not None and step is None:
            continue
        m_t = bank.sv.shape[1] if (plan is None or step == -1) else int(step)
        r = rows[p]

        def one(xi, sv, gamma, scale, shift, cpos, cneg, bpos, bneg, off):
            k = _pair_kernel(bank, xi[None, :], sv, gamma, scale, shift,
                             False)[0]                     # (m_t,)
            return (kern.matmul(k, cpos) + bpos) \
                - (kern.matmul(k, cneg) + bneg) + off

        s = jax.vmap(one)(
            xq_cache[bank.input_bits], bank.sv[:, :m_t][r], bank.gamma[r],
            bank.scale[r], bank.shift[r], bank.coef_pos[:, :m_t][r],
            bank.coef_neg[:, :m_t][r], bank.bias_pos[r], bank.bias_neg[r],
            bank.offset[r])
        total = total + mask[p] * s
    return total


def _dag_labels(x, n_classes: int, pair_matrix, linear_banks, kernel_banks,
                row_maps, step_plans=None):
    """DDAG elimination front: ``x (n, d) -> labels (n,)`` in O(n*K).

    An unrolled loop of K-1 steps carries the per-sample candidate
    interval ``(lo, hi)``; each step evaluates pair ``(lo, hi)`` through
    the gather front and eliminates one endpoint (bit 1 = the lower class
    wins, matching the ``class_pairs`` bit convention and the numpy
    reference ``repro.core.ovo.decide_dag``).  The loop is a trace-time
    Python loop (not ``lax.scan``) because each step runs a DIFFERENT
    static plan from :func:`_dag_step_plans` — banks with no reachable
    pair drop out of the step, kernel gathers slice to the reachable true
    support count.  Total pair evaluations: ``n * (K-1)`` instead of the
    dense path's ``n * K(K-1)/2``, and the kernel-bank work shrinks
    further to ``n * sum_t m_t``.
    """
    xq_cache: dict[int, jnp.ndarray] = {}
    for bank in list(linear_banks) + list(kernel_banks):
        bits = bank.input_bits
        if bits not in xq_cache:
            xq_cache[bits] = x if bits == 0 else quant.quantize_unit(x, bits)
    n = x.shape[0]
    lo = jnp.zeros(n, jnp.int32)
    hi = jnp.full(n, n_classes - 1, jnp.int32)
    for t in range(n_classes - 1):
        p = pair_matrix[lo, hi]                            # (n,)
        plan = None if step_plans is None else step_plans[t]
        s = _gather_pair_scores(p, linear_banks, kernel_banks, row_maps,
                                xq_cache, plan)
        win = s >= 0.0                                     # lower class wins
        lo = jnp.where(win, lo, lo + 1)
        hi = jnp.where(win, hi - 1, hi)
    return lo


# ---------------------------------------------------------------------------
# The compiled machine
# ---------------------------------------------------------------------------


class CompiledMachine:
    """A bank of OvO bit-classifiers lowered to one jit-compiled predict.

    Construct via :func:`compile_machine` (from live classifier objects) or
    :meth:`CompiledMachine.load` (from an ``.npz`` + ``.json`` pair).
    """

    def __init__(
        self,
        n_classes: int,
        linear_banks: list[_LinearBank],
        kernel_banks: list[_KernelBank],
        kernel_map: Optional[list[str]] = None,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        decider: str = "votes",
    ):
        self.n_classes = int(n_classes)
        self._linear_banks = linear_banks
        self._kernel_banks = kernel_banks
        self.n_pairs = sum(len(b.pair_idx) for b in linear_banks) + \
            sum(len(b.pair_idx) for b in kernel_banks)
        expect = len(class_pairs(self.n_classes))
        if self.n_pairs != expect:
            raise ValueError(
                f"{self.n_pairs} lowered pairs for {self.n_classes} classes "
                f"(expected {expect})")
        self.kernel_map = list(kernel_map) if kernel_map is not None else None
        self.n_features = _bank_feature_dim(linear_banks, kernel_banks)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        # None follows the kernels.ops backend default (interpreter off
        # TPU); a bool forces it, so CPU CI can exercise the compiled-mode
        # Pallas path deliberately (DESIGN.md SS7.5).
        self.interpret = interpret

        # Column order after bank concatenation -> pair order inversion.
        self._inv_perm = _inverse_perm(linear_banks, kernel_banks,
                                       self.n_pairs)

        # Decision encoder: packed truth table in the FE regime, votes
        # matmul beyond it (identical semantics, see ovo.decide_votes).
        self._decider = _Decider.build(self.n_classes)

        # Decision front for `predict`: the dense votes path (seed
        # semantics, always compiled — it stays the oracle behind
        # `predict_votes`/`decision_scores`/`predict_bits`), optionally
        # shadowed by the O(K) DAG elimination front.
        if decider not in DECIDERS:
            raise ValueError(
                f"unknown decider {decider!r}; one of {DECIDERS}")
        self.decider = decider
        self._pair_matrix = None
        self._row_maps = None
        self._step_plans = None
        self._labels_dag_jit = None
        if decider == "dag":
            self._pair_matrix = jnp.asarray(
                pair_index_matrix(self.n_classes))
            self._row_maps = _dag_row_maps(linear_banks, kernel_banks,
                                           self.n_pairs)
            self._step_plans = _dag_step_plans(linear_banks, kernel_banks,
                                               self.n_classes)
            self._labels_dag_jit = jax.jit(self._labels_dag)

        self._forward_jit = jax.jit(self._forward)

    # -- construction-time summary -----------------------------------------

    @property
    def n_linear_pairs(self) -> int:
        return sum(len(b.pair_idx) for b in self._linear_banks)

    @property
    def n_kernel_pairs(self) -> int:
        return sum(len(b.pair_idx) for b in self._kernel_banks)

    def describe(self) -> str:
        parts = [f"CompiledMachine(K={self.n_classes}, P={self.n_pairs})"]
        for b in self._linear_banks:
            parts.append(f"  linear bank: {len(b.pair_idx)} pairs, "
                         f"d={b.w.shape[1]}, input_bits={b.input_bits}")
        for b in self._kernel_banks:
            parts.append(f"  {b.kind} bank: {len(b.pair_idx)} pairs, "
                         f"M={b.sv.shape[1]}, d={b.sv.shape[2]}, "
                         f"input_bits={b.input_bits}")
        return "\n".join(parts)

    # -- the single batched forward pass ------------------------------------

    def _forward(self, x: jnp.ndarray):
        """x (n, d) f32 -> (scores (n, P), bits (n, P), labels (n,))."""
        scores = _all_scores(x, self._linear_banks, self._kernel_banks,
                             self._inv_perm, self.use_pallas,
                             interpret=self.interpret)
        bits = (scores >= 0.0).astype(jnp.int32)
        return scores, bits, self._decider(bits)

    def _labels_dag(self, x: jnp.ndarray):
        """x (n, d) f32 -> labels (n,) via the O(K) DAG front."""
        return _dag_labels(x, self.n_classes, self._pair_matrix,
                           self._linear_banks, self._kernel_banks,
                           self._row_maps, self._step_plans)

    # -- host API ------------------------------------------------------------

    def _as_input(self, x: np.ndarray) -> jnp.ndarray:
        x = jnp.asarray(np.asarray(x), jnp.float32)
        if x.ndim != 2 or (self.n_features and x.shape[1] != self.n_features):
            raise ValueError(
                f"expected (n, {self.n_features}) inputs, got shape {x.shape}")
        return x

    def _run(self, x: np.ndarray):
        return self._forward_jit(self._as_input(x))

    def decision_scores(self, x: np.ndarray) -> np.ndarray:
        """Raw per-pair decision scores (n, P) — pre-comparator."""
        return np.asarray(self._run(x)[0])

    def predict_bits(self, x: np.ndarray) -> np.ndarray:
        """Comparator bits (n, P), pair order of ``class_pairs``."""
        return np.asarray(self._run(x)[1])

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Class labels (n,) via the compiled decision front.

        ``decider="votes"`` (default): every pair is evaluated and the
        packed encoder table / votes argmax decides — bit-identical to the
        seed.  ``decider="dag"``: the DDAG elimination front evaluates
        K-1 pairs per sample; equal to the votes labels wherever the vote
        winner is unambiguous (Condorcet), measured via
        :meth:`dag_votes_agreement` elsewhere.
        """
        if self.decider == "dag":
            return np.asarray(self._labels_dag_jit(self._as_input(x)))
        return np.asarray(self._run(x)[2])

    def predict_votes(self, x: np.ndarray) -> np.ndarray:
        """Class labels (n,) via the dense votes path, regardless of the
        compiled ``decider`` — the oracle the DAG front is checked
        against."""
        return np.asarray(self._run(x)[2])

    def dag_votes_agreement(self, x: np.ndarray) -> float:
        """Fraction of samples where the DAG front and the votes oracle
        agree (requires ``decider="dag"``)."""
        if self.decider != "dag":
            raise ValueError("machine was compiled with decider='votes'")
        return float(np.mean(self.predict(x) == self.predict_votes(x)))

    def accuracy(self, x: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(x) == np.asarray(y)))

    score = accuracy

    # -- serialization (npz arrays + json structure) -------------------------

    def save(self, path: str) -> None:
        """Write ``<path>.npz`` (arrays) + ``<path>.json`` (structure)."""
        path = _strip_ext(path)
        arrays, meta_banks = _bank_arrays(
            self._linear_banks, self._kernel_banks)
        meta = {
            "format": "repro.api.CompiledMachine",
            "version": _FORMAT_VERSION,
            "n_classes": self.n_classes,
            "kernel_map": self.kernel_map,
            "decider": self.decider,
            "banks": meta_banks,
        }
        np.savez(path + ".npz", **arrays)
        with open(path + ".json", "w") as f:
            json.dump(meta, f, indent=2)

    @classmethod
    def load(cls, path: str, use_pallas: Optional[bool] = None,
             interpret: Optional[bool] = None,
             decider: Optional[str] = None) -> "CompiledMachine":
        path = _strip_ext(path)
        with open(path + ".json") as f:
            meta = json.load(f)
        if meta.get("format") != "repro.api.CompiledMachine":
            raise ValueError(f"{path}.json is not a CompiledMachine save")
        npz = np.load(path + ".npz")
        linear_banks, kernel_banks = _banks_from_entries(meta["banks"], npz)
        if decider is None:
            decider = meta.get("decider", "votes")
        return cls(meta["n_classes"], linear_banks, kernel_banks,
                   kernel_map=meta.get("kernel_map"), use_pallas=use_pallas,
                   interpret=interpret, decider=decider)


def _strip_ext(path: str) -> str:
    for ext in (".npz", ".json"):
        if path.endswith(ext):
            return path[: -len(ext)]
    return path


def _bank_arrays(linear_banks, kernel_banks, prefix: str = ""
                 ) -> tuple[dict[str, np.ndarray], list[dict]]:
    """Serialize banks to ``{npz key: array}`` + JSON bank entries.

    ``prefix`` namespaces the npz keys so multiple machines (the fleet
    save format, DESIGN.md §9) pack into one archive without collisions.
    """
    arrays: dict[str, np.ndarray] = {}
    meta_banks: list[dict] = []
    for i, b in enumerate(linear_banks):
        bid = f"{prefix}lin{i}"
        arrays[f"{bid}.w"] = np.asarray(b.w)
        arrays[f"{bid}.b"] = np.asarray(b.b)
        arrays[f"{bid}.pair_idx"] = b.pair_idx
        meta_banks.append({"type": "linear", "id": bid,
                           "input_bits": b.input_bits})
    for i, b in enumerate(kernel_banks):
        bid = f"{prefix}ker{i}"
        for name in ("sv", "coef_pos", "coef_neg", "bias_pos", "bias_neg",
                     "offset", "gamma", "scale", "shift"):
            arrays[f"{bid}.{name}"] = np.asarray(getattr(b, name))
        arrays[f"{bid}.pair_idx"] = b.pair_idx
        entry = {"type": "kernel", "id": bid, "kind": b.kind,
                 "input_bits": b.input_bits, "left": b.left,
                 "right": b.right}
        if b.grid is not None:
            arrays[f"{bid}.grid"] = np.asarray(b.grid)
            arrays[f"{bid}.curve"] = np.asarray(b.curve)
        meta_banks.append(entry)
    return arrays, meta_banks


def _banks_from_entries(entries: list[dict], npz
                        ) -> tuple[list[_LinearBank], list[_KernelBank]]:
    """Rebuild bank lists from JSON bank entries + an open npz archive."""
    linear_banks, kernel_banks = [], []
    for entry in entries:
        bid = entry["id"]
        if entry["type"] == "linear":
            linear_banks.append(_LinearBank(
                input_bits=int(entry["input_bits"]),
                pair_idx=npz[f"{bid}.pair_idx"],
                w=jnp.asarray(npz[f"{bid}.w"]),
                b=jnp.asarray(npz[f"{bid}.b"])))
        else:
            has_grid = f"{bid}.grid" in npz
            kernel_banks.append(_KernelBank(
                kind=entry["kind"], input_bits=int(entry["input_bits"]),
                pair_idx=npz[f"{bid}.pair_idx"],
                sv=jnp.asarray(npz[f"{bid}.sv"]),
                coef_pos=jnp.asarray(npz[f"{bid}.coef_pos"]),
                coef_neg=jnp.asarray(npz[f"{bid}.coef_neg"]),
                bias_pos=jnp.asarray(npz[f"{bid}.bias_pos"]),
                bias_neg=jnp.asarray(npz[f"{bid}.bias_neg"]),
                offset=jnp.asarray(npz[f"{bid}.offset"]),
                gamma=jnp.asarray(npz[f"{bid}.gamma"]),
                scale=jnp.asarray(npz[f"{bid}.scale"]),
                shift=jnp.asarray(npz[f"{bid}.shift"]),
                grid=jnp.asarray(npz[f"{bid}.grid"]) if has_grid else None,
                curve=jnp.asarray(npz[f"{bid}.curve"]) if has_grid else None,
                left=float(entry["left"]), right=float(entry["right"]),
                **_grid_fast_path(
                    npz[f"{bid}.grid"] if has_grid else None)))
    return linear_banks, kernel_banks


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def compile_machine(
    machine: MulticlassSVM | Sequence,
    n_classes: Optional[int] = None,
    kernel_map: Optional[list[str]] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    decider: str = "votes",
) -> CompiledMachine:
    """Lower a bank of bit-classifiers to a single batched inference path.

    ``machine`` is either a :class:`~repro.core.ovo.MulticlassSVM` or a
    plain sequence of per-pair classifiers (``DigitalLinearClassifier``,
    ``DigitalRBFClassifier``, ``AnalogBinaryClassifier``, float ``SVMModel``
    or any object exposing a ``.model`` SVMModel) in ``class_pairs`` order;
    in the latter case ``n_classes`` is required.

    The compiled result is numerically equivalent to calling each object's
    ``predict_bits`` and the encoder in turn, but runs as ONE jit-compiled
    device program (see module docstring for the bank layout).
    """
    if isinstance(machine, MulticlassSVM):
        classifiers = list(machine.classifiers)
        n_classes = machine.n_classes
        kernel_map = list(machine.kernel_map)
    else:
        classifiers = list(machine)
        if n_classes is None:
            raise ValueError("n_classes is required for a bare classifier list")

    specs = [_lower_classifier(i, c) for i, c in enumerate(classifiers)]
    linear_banks, kernel_banks = _build_banks(specs)
    return CompiledMachine(n_classes, linear_banks, kernel_banks,
                           kernel_map=kernel_map, use_pallas=use_pallas,
                           interpret=interpret, decider=decider)


# ---------------------------------------------------------------------------
# Candidate machine: assignment-independent per-pair bit tensor (DSE layer 2)
# ---------------------------------------------------------------------------


class CandidateMachine:
    """BOTH per-pair candidates lowered into one jit-compiled pass.

    The kernel-assignment design space (``repro.core.dse``) exploits that
    the comparator bit of each candidate classifier is *assignment-
    independent*: pair ``p``'s linear-digital bit and RBF bit do not change
    when some other pair's assignment flips.  This machine therefore lowers
    the two candidate classifiers of every pair — ``2P`` classifiers in
    total — into the same padded stacked banks as :class:`CompiledMachine`
    and evaluates all of them in ONE jitted forward:

        ``pair_bits(x) -> (n, P, 2)`` int32
        (``[..., 0]`` = linear-digital candidate bit, ``[..., 1]`` = RBF
        candidate bit, pair order of ``class_pairs``)

    Any candidate assignment's machine output is then a pure
    *bit-recombination*: select one bit per pair, feed the decision
    encoder — no classifier is ever re-evaluated per assignment
    (DESIGN.md §5.3).
    """

    def __init__(self, n_classes: int, linear_banks, kernel_banks,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None):
        self.n_classes = int(n_classes)
        self.n_pairs = len(class_pairs(self.n_classes))
        self._linear_banks = linear_banks
        self._kernel_banks = kernel_banks
        self.n_features = _bank_feature_dim(linear_banks, kernel_banks)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.interpret = interpret
        # Lowering indices: candidate 0 of pair p is column p, candidate 1
        # is column P + p; the inverse permutation restores that order.
        self._inv_perm = _inverse_perm(linear_banks, kernel_banks,
                                       2 * self.n_pairs)
        self._forward_jit = jax.jit(self._forward)

    def _forward(self, x: jnp.ndarray):
        """x (n, d) f32 -> (scores (n, P, 2), bits (n, P, 2))."""
        flat = _all_scores(x, self._linear_banks, self._kernel_banks,
                           self._inv_perm, self.use_pallas,
                           interpret=self.interpret)            # (n, 2P)
        scores = jnp.stack(
            [flat[:, : self.n_pairs], flat[:, self.n_pairs:]], axis=-1)
        return scores, (scores >= 0.0).astype(jnp.int32)

    def _run(self, x: np.ndarray):
        x = jnp.asarray(np.asarray(x), jnp.float32)
        if x.ndim != 2 or (self.n_features and x.shape[1] != self.n_features):
            raise ValueError(
                f"expected (n, {self.n_features}) inputs, got shape {x.shape}")
        return self._forward_jit(x)

    def pair_scores(self, x: np.ndarray) -> np.ndarray:
        """Raw candidate decision scores ``(n, P, 2)`` — pre-comparator."""
        return np.asarray(self._run(x)[0])

    def pair_bits(self, x: np.ndarray) -> np.ndarray:
        """Candidate comparator bits ``(n, P, 2)`` in one device pass."""
        return np.asarray(self._run(x)[1])


def compile_candidates(
    candidates: Sequence,
    n_classes: int,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> CandidateMachine:
    """Lower per-pair candidate classifiers to one :class:`CandidateMachine`.

    ``candidates`` is a sequence of ``(linear_clf, rbf_clf)`` per OvO pair
    in ``class_pairs`` order — the same classifier objects the legacy banks
    would hold, so the bit tensor agrees column-for-column with the
    corresponding :class:`CompiledMachine` outputs.
    """
    pairs = class_pairs(n_classes)
    if len(candidates) != len(pairs):
        raise ValueError(
            f"{len(candidates)} candidate pairs for {n_classes} classes "
            f"(expected {len(pairs)})")
    p = len(pairs)
    specs = []
    for i, (lin_clf, rbf_clf) in enumerate(candidates):
        specs.append(_lower_classifier(i, lin_clf))
        specs.append(_lower_classifier(p + i, rbf_clf))
    linear_banks, kernel_banks = _build_banks(specs)
    return CandidateMachine(n_classes, linear_banks, kernel_banks,
                            use_pallas=use_pallas, interpret=interpret)


# ---------------------------------------------------------------------------
# Monte-Carlo machine: the candidate bit tensor under process variation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _VariantSpec:
    """Variant tensors of ONE analog candidate (pre-padding, DESIGN.md §6).

    ``shift``/``gain (V, m, d)`` perturb the per-cell Gaussian transfers,
    ``coef_pos``/``coef_neg (V, m)`` are the per-variant *realised* alpha
    coefficients (the alpha-multiplier mismatch is folded at lowering time,
    exactly as the nominal lowering freezes the nominal alpha path), and
    ``offset (V,)`` the per-variant comparator offset.  Row 0 carries the
    zero-offset instance and reproduces the nominal spec bit for bit.
    """

    pair: int
    shift: np.ndarray
    gain: np.ndarray
    coef_pos: np.ndarray
    coef_neg: np.ndarray
    offset: np.ndarray


def _lower_analog_variants(
    idx: int,
    clf: AnalogBinaryClassifier,
    key: jax.Array,
    n_variants: int,
    include_nominal: bool,
    sigma_scale: float,
) -> _VariantSpec:
    """Sample + reduce mismatch for one deployed analog classifier."""
    variants = clf.sample_variants(
        key, n_variants, include_nominal=include_nominal,
        sigma_scale=sigma_scale)
    t = variant_transfer_params(variants, clf.hw.params)
    # Per-variant alpha path, frozen with the same f32 ops as the nominal
    # lowering: desired alpha -> control voltage -> mismatched realised
    # alpha ((dva - shift) / slope queries the measured sweep).
    dva = clf.hw.alpha_control_voltage(jnp.asarray(clf.alpha_hw, jnp.float32))
    a = _f32(clf.hw.alpha_realized(
        (dva[None, :] - t.alpha_shift) / t.alpha_slope))        # (V, m)
    pos = (clf.support_y > 0)
    return _VariantSpec(
        pair=idx, shift=_f32(t.shift), gain=_f32(t.gain),
        coef_pos=a * pos[None, :], coef_neg=a * (~pos)[None, :],
        offset=_f32(t.comp_offset))


@dataclasses.dataclass
class _BankVariants:
    """Per-bank stacked variant tensors (padded to the bank max M).

    Padded SV slots carry gain 0 AND coefficient 0, so they contribute an
    exact 0 to the rail GEMM for every variant — the same inertness
    argument as the nominal bank padding.
    """

    shift: jnp.ndarray     # (V, P, M, d)
    gain: jnp.ndarray      # (V, P, M, d)
    coef_pos: jnp.ndarray  # (V, P, M)
    coef_neg: jnp.ndarray  # (V, P, M)
    offset: jnp.ndarray    # (V, P)

    @classmethod
    def build(cls, vspecs: list[_VariantSpec], m_max: int) -> "_BankVariants":
        def pad(a: np.ndarray) -> np.ndarray:
            out = np.zeros(a.shape[:1] + (m_max,) + a.shape[2:], np.float32)
            out[:, : a.shape[1]] = a
            return out

        return cls(
            shift=jnp.asarray(np.stack([pad(s.shift) for s in vspecs], 1)),
            gain=jnp.asarray(np.stack([pad(s.gain) for s in vspecs], 1)),
            coef_pos=jnp.asarray(
                np.stack([pad(s.coef_pos) for s in vspecs], 1)),
            coef_neg=jnp.asarray(
                np.stack([pad(s.coef_neg) for s in vspecs], 1)),
            offset=jnp.asarray(np.stack([s.offset for s in vspecs], 1)),
        )

    @property
    def n_variants(self) -> int:
        return int(self.shift.shape[0])


# All-array dataclass: register with field order as the flatten order so
# variant tensors cross jit boundaries as a plain pytree (see the
# _LinearBank/_KernelBank registration note).
jax.tree_util.register_dataclass(
    _BankVariants,
    data_fields=("shift", "gain", "coef_pos", "coef_neg", "offset"),
    meta_fields=())


def _key_data(key: jax.Array) -> np.ndarray:
    """Raw uint32 data of a jax PRNG key — typed or legacy."""
    try:
        return np.asarray(jax.random.key_data(key))
    except TypeError:  # legacy raw uint32 keys
        return np.asarray(key)


#: Bytes the analog variant lanes of one slice may hold in their
#: ``(variants, n, P, M)``-shaped temporaries, and the bytes a lane cell
#: takes of them.  Per cell the lanes keep the s32 segment index, the MXU
#: lookup's f32 pair ``(f0, f1)`` (``core.kernels.curve_pair_mxu``) and
#: the f32 offsets, fractions, values and running product of the inputs
#: live at once: compiled for a TPU v5e at 45 pairs x M = 165 over 128
#: rows, a slice of 7 to 28 variants held 56-64 B a cell of temporaries
#: (and each further variant 48-62 B a cell).  So B variants over n rows
#: ask for 64*B*n*P*M bytes: 412 GB at har12 size (256 variants x 512
#: rows x 66 pairs x M = 745) on a 16 GB chip.  Past this bound the
#: variant axis runs in sequential slices of the largest count that fits.
MC_LANE_BYTES = 1 << 30
_LANE_CELL_BYTES = 64


def _variant_batch(n_rows: int, n_pairs: int, m_max: int) -> int:
    """Variants per slice of the analog lanes under ``MC_LANE_BYTES``."""
    return max(1, MC_LANE_BYTES
               // (_LANE_CELL_BYTES * n_rows * n_pairs * m_max))


def _lane_slices(n_lanes: int, n_rows: int, n_pairs: int,
                 m_max: int) -> int:
    """Sequential slices ``_bank_scores_mc`` runs ``n_lanes`` variants in
    (``lax.map`` slices of ``_variant_batch``): 1 where they are one
    ``vmap``."""
    return -(-n_lanes // _variant_batch(n_rows, n_pairs, m_max))


def _bank_scores_mc(bank: _KernelBank, bv: _BankVariants, xv: jnp.ndarray,
                    use_pallas: bool, include_nominal: bool,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """(V, n, P) decision scores of one analog bank under variation.

    Only the variant-dependent tensors carry the leading V axis; the bank
    constants and the input batch broadcast (closed over / in_axes=None),
    so XLA sees one fused program over the whole (V, P) lane grid.

    With ``include_nominal``, variant 0 does NOT go through the perturbed
    lanes at all: it IS the nominal ``_bank_scores`` subgraph, concatenated
    in front of the ``V - 1`` sampled lanes.  Subtracting a runtime 0 and
    multiplying by a runtime 1 are exact IEEE ops, but their mere presence
    changes XLA's fusion/codegen of the surrounding interpolation chain
    (observed ~4e-6 drift on CPU), so structural reuse of the nominal
    expression is the only way the bit-identity contract survives jit.
    """
    if bank.kind != "hw":
        raise TypeError(
            f"variant lanes require the 'hw' measured-curve kind, got "
            f"{bank.kind!r}")

    def one(sv, gamma, scale, shift, cpos, cneg, bpos, bneg, off,
            vshift, vgain):
        k = _pair_kernel(bank, xv, sv, gamma, scale, shift, use_pallas,
                         vshift=vshift, vgain=vgain, interpret=interpret)
        rails = kern.matmul(k, jnp.stack([cpos, cneg], axis=1))  # (n, 2)
        return (rails[:, 0] + bpos) - (rails[:, 1] + bneg) + off

    def one_variant(vshift, vgain, vcpos, vcneg, voff):
        return jax.vmap(one, out_axes=1)(
            bank.sv, bank.gamma, bank.scale, bank.shift,
            vcpos, vcneg, bank.bias_pos, bank.bias_neg, voff,
            vshift, vgain)

    lo = 1 if include_nominal else 0
    lanes = (bv.shift[lo:], bv.gain[lo:], bv.coef_pos[lo:],
             bv.coef_neg[lo:], bv.offset[lo:])
    batch = _variant_batch(*xv.shape[:1], *bank.sv.shape[:2])
    if lanes[0].shape[0] <= batch:
        var = jax.vmap(one_variant)(*lanes)
    else:
        var = jax.lax.map(lambda a: one_variant(*a), lanes,
                          batch_size=batch)
    if not include_nominal:
        return var
    nom = _bank_scores(bank, xv, use_pallas, interpret=interpret)
    return jnp.concatenate([nom[None], var], axis=0)


def _all_scores_mc(x: jnp.ndarray, linear_banks, kernel_banks,
                   bank_variants, inv_perm: jnp.ndarray, n_variants: int,
                   include_nominal: bool, use_pallas: bool,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (n, d) f32 -> scores (V, n, C) in lowering (pair-index) order.

    Variation-free lanes (linear-digital, digital-RBF) are evaluated ONCE
    and broadcast over the variant axis; only banks with attached
    ``_BankVariants`` vmap over it.

    Device scopes (``jax.named_scope``, metadata only): ``mc.lanes.linear``,
    ``mc.lanes.rbf`` (variation-free kernel banks) and ``mc.lanes.hw``
    (variant lanes, and the merge of all columns onto their ``(V, n, C)``
    grid).
    """
    xq_cache: dict[int, jnp.ndarray] = {}

    def xq(bits: int) -> jnp.ndarray:
        if bits not in xq_cache:
            xq_cache[bits] = x if bits == 0 else quant.quantize_unit(x, bits)
        return xq_cache[bits]

    cols = []
    for bank in linear_banks:
        with jax.named_scope("mc.lanes.linear"):
            c = kern.matmul(xq(bank.input_bits), bank.w.T) + bank.b[None, :]
            cols.append(jnp.broadcast_to(c[None], (n_variants,) + c.shape))
    for bank, bv in zip(kernel_banks, bank_variants):
        if bv is None:
            with jax.named_scope("mc.lanes.rbf"):
                c = _bank_scores(bank, xq(bank.input_bits), use_pallas,
                                 interpret=interpret)
                cols.append(
                    jnp.broadcast_to(c[None], (n_variants,) + c.shape))
        else:
            with jax.named_scope("mc.lanes.hw"):
                cols.append(_bank_scores_mc(bank, bv, xq(bank.input_bits),
                                            use_pallas, include_nominal,
                                            interpret=interpret))
    with jax.named_scope("mc.lanes.hw"):
        return jnp.concatenate(cols, axis=2)[:, :, inv_perm]


class MonteCarloMachine:
    """BOTH candidates of every pair under ``V`` mismatch instances.

    The Monte-Carlo sibling of :class:`CandidateMachine`: the same padded
    stacked banks, but every analog lane is evaluated for ``V`` sampled
    fabricated instances (per-SV-cell Gaussian/alpha/comparator mismatch,
    ``repro.core.analog.VariantSet``) with the variant axis vmapped INSIDE
    the one jitted forward —

        ``pair_bits(x) -> (V, n, P, 2)`` int32

    (candidate axis as in :class:`CandidateMachine`; variant axis leading).
    Digital lanes are variation-free and broadcast.  With the default
    ``include_nominal`` sampling, variant 0 is the zero-offset instance
    and its lanes reuse the literal nominal subgraph (see
    ``_bank_scores_mc``), so its slice is bit-identical to the nominal
    ``CandidateMachine`` scores — the contract
    ``benchmarks/montecarlo.py --assert-nominal`` freezes.
    """

    def __init__(self, n_classes: int, linear_banks, kernel_banks,
                 bank_variants, n_variants: int, include_nominal: bool,
                 sigma_scale: float, key_data: Optional[np.ndarray] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None):
        self.n_classes = int(n_classes)
        self.n_pairs = len(class_pairs(self.n_classes))
        self.n_variants = int(n_variants)
        self.include_nominal = bool(include_nominal)
        self.sigma_scale = float(sigma_scale)
        self.key_data = None if key_data is None else np.asarray(key_data)
        self._linear_banks = linear_banks
        self._kernel_banks = kernel_banks
        self._bank_variants = bank_variants
        self.n_features = _bank_feature_dim(linear_banks, kernel_banks)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.interpret = interpret
        self._inv_perm = _inverse_perm(linear_banks, kernel_banks,
                                       2 * self.n_pairs)
        self._forward_jit = jax.jit(self._forward)

    def _forward(self, x: jnp.ndarray):
        """x (n, d) f32 -> (scores (V, n, P, 2), bits (V, n, P, 2))."""
        flat = _all_scores_mc(x, self._linear_banks, self._kernel_banks,
                              self._bank_variants, self._inv_perm,
                              self.n_variants, self.include_nominal,
                              self.use_pallas, interpret=self.interpret)
        scores = jnp.stack(
            [flat[..., : self.n_pairs], flat[..., self.n_pairs:]], axis=-1)
        return scores, (scores >= 0.0).astype(jnp.int32)

    def _run(self, x: np.ndarray):
        x = jnp.asarray(np.asarray(x), jnp.float32)
        if x.ndim != 2 or (self.n_features and x.shape[1] != self.n_features):
            raise ValueError(
                f"expected (n, {self.n_features}) inputs, got shape {x.shape}")
        return self._forward_jit(x)

    def pair_scores(self, x: np.ndarray) -> np.ndarray:
        """Per-variant candidate decision scores ``(V, n, P, 2)``."""
        return np.asarray(self._run(x)[0])

    def pair_bits(self, x: np.ndarray) -> np.ndarray:
        """Per-variant candidate comparator bits ``(V, n, P, 2)`` — every
        variant of every candidate of every pair in ONE device pass."""
        return np.asarray(self._run(x)[1])


def compile_variants(
    candidates: Sequence,
    n_classes: int,
    key: jax.Array,
    n_variants: int = 64,
    include_nominal: bool = True,
    sigma_scale: float = 1.0,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> MonteCarloMachine:
    """Lower per-pair candidates + sampled process variation to ONE machine.

    ``candidates`` is the same per-pair ``(linear_clf, rbf_clf)`` sequence
    :func:`compile_candidates` takes.  ``key`` is an explicit ``jax.random``
    key (no hidden RNG state): it is split once per pair, so every analog
    candidate's circuit draws independent per-SV-cell mismatch
    (``AnalogBinaryClassifier.sample_variants``).  Non-analog RBF
    candidates (e.g. digital RBF) are accepted and treated as
    variation-free broadcast lanes.

    With ``include_nominal`` (default) variant 0 is the zero-offset
    instance — bit-identical to :func:`compile_candidates` on the same
    candidates — and ``n_variants - 1`` random instances are drawn.
    """
    pairs = class_pairs(n_classes)
    if len(candidates) != len(pairs):
        raise ValueError(
            f"{len(candidates)} candidate pairs for {n_classes} classes "
            f"(expected {len(pairs)})")
    p = len(pairs)
    keys = jax.random.split(key, p)
    specs = []
    vspecs: dict[int, _VariantSpec] = {}
    for i, (lin_clf, rbf_clf) in enumerate(candidates):
        specs.append(_lower_classifier(i, lin_clf))
        specs.append(_lower_classifier(p + i, rbf_clf))
        if isinstance(rbf_clf, AnalogBinaryClassifier):
            vspecs[p + i] = _lower_analog_variants(
                p + i, rbf_clf, keys[i], n_variants, include_nominal,
                sigma_scale)
    linear_groups, kernel_groups = _group_specs(specs)
    linear_banks = [_LinearBank.build(g) for g in linear_groups]
    kernel_banks, bank_variants = [], []
    for g in kernel_groups:
        bank = _KernelBank.build(g)
        kernel_banks.append(bank)
        in_group = [s.pair in vspecs for s in g]
        if not any(in_group):
            bank_variants.append(None)
            continue
        if not all(in_group):  # cannot happen: 'hw' curves group apart
            raise ValueError(
                "bank mixes variant and variant-free lanes; grouping bug")
        bank_variants.append(_BankVariants.build(
            [vspecs[s.pair] for s in g], int(bank.sv.shape[1])))
    return MonteCarloMachine(
        n_classes, linear_banks, kernel_banks, bank_variants,
        n_variants=n_variants, include_nominal=include_nominal,
        sigma_scale=sigma_scale, key_data=_key_data(key),
        use_pallas=use_pallas, interpret=interpret)


# ---------------------------------------------------------------------------
# Streaming Monte-Carlo machine: flat-memory variant pipelining (DESIGN.md §10)
# ---------------------------------------------------------------------------

#: Default variant chunk of the streaming engine.  A config knob like
#: ``dse.MC_CHUNK``: larger chunks amortize dispatch overhead, smaller
#: ones shrink the peak temp footprint; either way ONE program compiles
#: regardless of the total variant count.
MC_STREAM_CHUNK = 256

#: In-graph assignment-axis chunk of the streamed recombination (bounds
#: the ``(B, n, CHUNK)`` codes tensor exactly as ``dse.MC_CHUNK`` bounds
#: the dense sweep's).
_RECOMBINE_CHUNK = 512

STREAM_METHODS = ("iid", "sobol", "stratified", "is")


@dataclasses.dataclass
class _StreamBankConst:
    """Per-analog-bank constants for on-the-fly variant generation.

    Everything the chunk step needs to draw + lower a variant IN-GRAPH,
    on the bank's padded ``(Pb, M)`` slot grid: per-pair mismatch keys,
    the frozen alpha control voltages, rail/validity masks (padded slots
    have both rail masks 0, so their coefficients are exact zeros — the
    same inertness argument as the dense ``_BankVariants`` padding), and
    the per-pair measured alpha sweeps for the in-graph realised-alpha
    interpolation.  Static aux: the shared ``CircuitParams``, the bank's
    slice of the flat QMC block (``u_offset``/``u_width``, padded grid)
    and its TRUE mismatch dimension (unpadded — the ``D`` that enters
    importance-sampling log-weights).
    """

    pair_keys: jax.Array      # (Pb,) typed mismatch keys (fold_in per variant)
    dva: jnp.ndarray          # (Pb, M) alpha control voltages, 0 on pads
    pos_mask: jnp.ndarray     # (Pb, M) f32: valid slot on the + rail
    neg_mask: jnp.ndarray     # (Pb, M) f32: valid slot on the - rail
    slot_valid: jnp.ndarray   # (Pb, M) f32: any valid slot
    alpha_grid: jnp.ndarray   # (Pb, Ga) ascending measured alpha abscissa
    alpha_curve: jnp.ndarray  # (Pb, Ga) measured alpha multiplier
    alpha_left: jnp.ndarray   # (Pb,) clamp below the sweep
    alpha_right: jnp.ndarray  # (Pb,) clamp above the sweep
    params: object = None     # shared CircuitParams (static)
    u_offset: int = 0         # flat QMC block slice start (padded dims)
    u_width: int = 0          # flat QMC block slice width (padded dims)
    true_dim: int = 0         # unpadded mismatch dims across the bank


jax.tree_util.register_dataclass(
    _StreamBankConst,
    data_fields=["pair_keys", "dva", "pos_mask", "neg_mask", "slot_valid",
                 "alpha_grid", "alpha_curve", "alpha_left", "alpha_right"],
    meta_fields=["params", "u_offset", "u_width", "true_dim"])


#: A restricted step's analog pairs run in at most this many size groups,
#: each a bank padded to its own largest M.  A group also costs, besides
#: its cells, about what ``_LANE_GROUP_CELLS`` lane cells of a variant-row
#: cost (its own draw, lookup and rail ops), so a group whose padding
#: saving is smaller is not split off.
_MAX_LANE_GROUPS = 4
_LANE_GROUP_CELLS = 2


@dataclasses.dataclass(eq=False)
class _StreamLanes:
    """The kernel banks one streaming step lowers (DESIGN.md §10.6).

    The candidate lanes (``needed`` None) hold every pair's candidates in
    the banks of :func:`compile_mc_stream`.  Restricted lanes keep the
    variant-free banks and lower only the analog pairs ``needed`` names,
    in size groups.  ``inv_perm`` maps each lowering column to its bank
    column; the analog column of a pair outside ``needed`` reads that
    pair's linear column, so the recombination sees both bits equal.
    """

    kernel_banks: list
    stream_consts: list        # aligned with kernel_banks; None: variant-free
    inv_perm: jnp.ndarray
    needed: Optional[frozenset] = None

    @property
    def grids(self) -> list:
        """Padded ``(Pb, M)`` slot grids of the variant banks."""
        return [tuple(int(n) for n in bank.sv.shape[:2])
                for bank, bc in zip(self.kernel_banks, self.stream_consts)
                if bc is not None]

    @property
    def slots(self) -> int:
        """Analog lane cells one variant-row computes."""
        return sum(p * m for p, m in self.grids)


jax.tree_util.register_dataclass(
    _StreamLanes, data_fields=["kernel_banks", "stream_consts", "inv_perm"],
    meta_fields=["needed"])


def _recombine_acc(bits4, assignments, y, table, weights,
                   s_chunk: int = _RECOMBINE_CHUNK):
    """Streamed bit-recombination: ``bits4 (B, n, P, 2) -> acc (B, S)`` f32.

    The chunk-axis sibling of ``dse._encoder_accuracy``: the packed
    encoder table scores every assignment for every variant of the chunk.
    Beyond ``s_chunk`` assignments the assignment axis runs under an
    in-graph ``lax.map`` (loop-carried buffer, codes tensor bounded at
    ``(B, n, s_chunk)``) — no host round-trips, one compiled program.
    """
    lin = bits4[..., 0]                                    # (B, n, P)
    diff = (bits4[..., 1] - lin) * weights[None, None, :]
    base = lin @ weights                                   # (B, n)
    yy = y[None, :, None]
    s = assignments.shape[0]

    def score(a_block):
        codes = base[..., None] + diff @ a_block.T         # (B, n, C)
        labels = jnp.take(table, codes)
        return jnp.mean((labels == yy).astype(jnp.float32), axis=1)

    if s <= s_chunk:
        return score(assignments)
    pad = -s % s_chunk
    a = assignments
    if pad:
        a = jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])])
    chunks = a.reshape(-1, s_chunk, a.shape[1])
    acc = jax.lax.map(score, chunks)                       # (n_chunks, B, C)
    return jnp.moveaxis(acc, 0, 1).reshape(bits4.shape[0], -1)[:, :s]


class StreamingMCMachine:
    """Tail-yield Monte-Carlo at production signoff scale (DESIGN.md §10).

    Where :class:`MonteCarloMachine` materializes all ``V`` variants —
    banks ``(V, P, M, d)``, bits ``(V, n, P, 2)`` — this machine never
    holds more than ONE fixed-size chunk of ``mc_chunk`` variants:

    1. **Generate**: variant ``v``'s mismatch is drawn in-graph from
       ``fold_in(pair_key, v)`` (``method='iid'``/``'is'``) or from
       coordinate ``v`` of a scrambled-Sobol/Latin-hypercube point set
       (``'sobol'``/``'stratified'``, inverse-CDF transformed in-graph) —
       a pure function of the global index, never of the chunking.
    2. **Score**: the chunk's banks run through the SAME
       ``_all_scores_mc`` lanes as the dense machine (digital lanes
       broadcast), then the packed-encoder recombination
       (``_recombine_acc``) or, past ``MAX_TABLE_BITS``, the pair-chunked
       votes (``dse._votes_accuracy_paired``) scores every assignment.
    3. **Fold**: the ``(B, S)`` chunk accuracies collapse into the
       donated :class:`~repro.core.mcstream.StreamStats` accumulator —
       weighted Welford mean/M2, floor exceedance, extrema, histogram
       sketch — and the chunk's buffers are reused.

    One compiled program serves every ``V`` (the step's shapes depend on
    ``mc_chunk``, never on ``V``), so peak memory is flat from V=64 to
    V=10^6 — the property ``benchmarks/montecarlo.py
    --assert-flat-memory`` gates via XLA ``memory_analysis``.  With
    ``method='is'``, draws are widened by ``is_scale`` and carry
    self-normalized importance weights through the accumulators
    (rare-event tail sharpening; ``finalize`` reports the effective
    sample size the confidence bounds use).  ``stream(mesh=)`` shards
    the chunk's variant axis across a ``launch.mesh.make_variant_mesh``
    with one psum/pmin/pmax merge per chunk.

    Measurement: the step's device ops carry the named scopes ``mc.draw``,
    ``mc.lanes.linear``/``.rbf``/``.hw``, ``mc.recombine`` (with
    ``mc.recombine.table`` or ``mc.recombine.votes`` inside it, the path
    taken, which ``repro.obs.snapshot()`` also counts as
    ``recombine_table_traced``/``recombine_votes_traced`` when the step is
    lowered) and ``mc.fold``; :meth:`stream` marks its host phases with
    profiler spans (``repro.mc.prep``, ``.dispatch``, ``.wait``,
    ``.finalize``, which record nothing unless a profiler is tracing) and
    keeps the work totals ``steps_run``, ``variants_run``,
    ``lane_cells_run``, ``lane_slices_run`` and ``restricted_steps_run``.

    Restricted step (DESIGN.md §10.6): where ``method == 'iid'`` and the
    assignments select a strict subset of the analog candidates,
    :meth:`stream` runs a step that lowers only those analog pairs, in
    size groups (``restrict``, built by :func:`compile_mc_stream`), and
    ``repro.obs.snapshot()`` counts ``mc_lanes_restricted_traced`` as such
    a step is lowered.  Every other stream runs the candidate step.
    """

    def __init__(self, n_classes: int, linear_banks, lanes: _StreamLanes,
                 method: str, mc_chunk: int,
                 sigma_scale: float, is_scale: float,
                 key_data: Optional[np.ndarray] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 restrict=None):
        self.n_classes = int(n_classes)
        self.n_pairs = len(class_pairs(self.n_classes))
        if method not in STREAM_METHODS:
            raise ValueError(
                f"unknown sampling method {method!r}; one of "
                f"{STREAM_METHODS}")
        if mc_chunk < 1:
            raise ValueError(f"mc_chunk must be >= 1, got {mc_chunk}")
        self.method = method
        self.mc_chunk = int(mc_chunk)
        self.sigma_scale = float(sigma_scale)
        self.is_scale = float(is_scale)
        self.key_data = None if key_data is None else np.asarray(key_data)
        self._linear_banks = linear_banks
        self._lanes = lanes
        #: ``needed -> _StreamLanes`` of a restricted step; None: no
        #: restricted step.
        self._restrict = restrict
        self.n_features = _bank_feature_dim(linear_banks, lanes.kernel_banks)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.interpret = interpret
        # Recombination constants: packed encoder table in the FE regime,
        # the pair-chunked votes matmul (dse._votes_accuracy_paired)
        # beyond it — same flat-memory contract either way.
        dec = _Decider.build(self.n_classes)
        self._table, self._weights = dec.table, dec.bit_weights
        self._vote_a, self._vote_b = dec.vote_a, dec.vote_b
        #: Flat mismatch dims over the padded slot grids (the QMC block
        #: width) and over the true circuits (the IS log-weight D).
        self.mismatch_dim = sum(
            c.u_width for c in lanes.stream_consts if c is not None)
        self.true_dim = sum(
            c.true_dim for c in lanes.stream_consts if c is not None)
        self._sampler = None
        if method in ("sobol", "stratified"):
            self._sampler = mcstream.QMCSampler(
                method, self.mismatch_dim, self.key_data)
        self._step_jit = jax.jit(self._step, donate_argnums=(0,))
        self._bits_jit = jax.jit(self._bits)
        self._sharded_steps: dict = {}
        #: Restricted views of this machine, by the analog pairs they
        #: lower (:meth:`_view`).
        self._restricted: dict = {}
        #: Pairs whose analog candidate runs in the variant lanes.
        self._analog_pairs = frozenset(
            int(q) - self.n_pairs
            for bank, bc in zip(lanes.kernel_banks, lanes.stream_consts)
            if bc is not None for q in bank.pair_idx)
        #: Work :meth:`stream` dispatched over the machine's life: chunk
        #: steps, valid variants, analog lane cells computed (every step's
        #: chunk x rows x the slots of the lanes it ran), the sequential
        #: slices the variant lanes of each step ran in (the most of any
        #: bank; 1 a step where the lanes are one ``vmap``), and the steps
        #: that ran restricted lanes.
        self.steps_run = 0
        self.variants_run = 0
        self.lane_cells_run = 0
        self.lane_slices_run = 0
        self.restricted_steps_run = 0

    # -- in-graph chunk generation ------------------------------------------

    def _bank_chunk(self, bc: _StreamBankConst, bank: _KernelBank,
                    v_idx: jnp.ndarray, u: jnp.ndarray):
        """Lower ONE analog bank's variant chunk: draws -> _BankVariants.

        Returns ``(chunk, raw_sumsq (B,))`` where ``raw_sumsq`` is the
        masked squared norm of the UNSCALED standard-normal draws (what
        importance-sampling log-weights integrate; padded slots excluded).
        """
        m_max = int(bc.dva.shape[1])
        d = int(bank.sv.shape[2])
        if self.method in ("iid", "is"):
            def draw(k, idx):
                kg, ka, kc = jax.random.split(jax.random.fold_in(k, idx), 3)
                return (jax.random.normal(kg, (m_max, d, N_GAUSS_OFFSETS)),
                        jax.random.normal(ka, (m_max, N_ALPHA_OFFSETS)),
                        jax.random.normal(kc, ()))

            gz, az, cz = jax.vmap(jax.vmap(draw, in_axes=(0, None)),
                                  in_axes=(None, 0))(bc.pair_keys, v_idx)
        else:
            ub = u[:, bc.u_offset: bc.u_offset + bc.u_width]
            z = mcstream.uniform_to_normal(ub).reshape(
                v_idx.shape[0], len(bc.pair_keys), variant_dim(m_max, d))
            raw = variant_set_from_flat(z, m_max, d, 1.0)
            gz, az, cz = raw.gauss, raw.alpha, raw.comparator
        sumsq = (
            jnp.sum(gz * gz * bc.slot_valid[None, :, :, None, None],
                    axis=(1, 2, 3, 4))
            + jnp.sum(az * az * bc.slot_valid[None, :, :, None],
                      axis=(1, 2, 3))
            + jnp.sum(cz * cz, axis=1))
        scale = self.sigma_scale * (
            self.is_scale if self.method == "is" else 1.0)
        s = jnp.float32(scale)
        vs = VariantSet(gauss=s * gz, alpha=s * az, comparator=s * cz)
        t = variant_transfer_params(vs, bc.params)    # leading (B, Pb)
        # In-graph realised alpha: the SAME frozen-alpha arithmetic as
        # `_lower_analog_variants`, per pair against its measured sweep.
        query = (bc.dva[None] - t.alpha_shift) / t.alpha_slope  # (B, Pb, M)

        def interp_pair(q, g, c, lo, hi):
            return jnp.interp(q, g, c, left=lo, right=hi)

        a = jax.vmap(interp_pair, in_axes=(1, 0, 0, 0, 0), out_axes=1)(
            query, bc.alpha_grid, bc.alpha_curve,
            bc.alpha_left, bc.alpha_right)                      # (B, Pb, M)
        # Padded slots: both rail masks are 0, so their coefficients are
        # exact zeros and the rail GEMM ignores whatever the padded draws
        # did to shift/gain — the dense path's zero-padding, streamed.
        chunk = _BankVariants(
            shift=t.shift, gain=t.gain,
            coef_pos=a * bc.pos_mask[None],
            coef_neg=a * bc.neg_mask[None],
            offset=t.comp_offset)
        return chunk, sumsq

    def _chunk_banks(self, v_idx: jnp.ndarray, u: jnp.ndarray):
        """All banks' variant chunks + the chunk's sampling weights (B,)."""
        bank_variants, sumsq = [], jnp.zeros(v_idx.shape[0], jnp.float32)
        for bank, bc in zip(self._lanes.kernel_banks,
                            self._lanes.stream_consts):
            if bc is None:
                bank_variants.append(None)
                continue
            chunk, ss = self._bank_chunk(bc, bank, v_idx, u)
            bank_variants.append(chunk)
            sumsq = sumsq + ss
        if self.method == "is":
            s = self.is_scale
            # Log-weight CENTERED at its analytic mean under the proposal
            # (E[sumsq] = D): logw - D(log s - (s^2-1)/2) = (s^2-1)/2 *
            # (D - sumsq).  Raw log-weights sit hundreds of nats below
            # zero in realistic mismatch spaces (D in the hundreds), so
            # weights are materialized RELATIVE to the chunk max — always
            # in (0, 1] — and the accumulators carry the scale in
            # StreamStats.log_ref (streaming logsumexp; a fixed clip
            # either zeroes the stream or ties a macroscopic fraction of
            # draws at the clip, silently inflating n_eff).  Padded tail
            # rows have finite log-weights too, so the max needs no
            # validity mask — any consistent scale works, and the
            # weighted sums drop invalid rows downstream.
            logw = (jnp.float32((s * s - 1.0) / 2.0)
                    * (jnp.float32(self.true_dim) - sumsq))
            log_ref = jnp.max(logw)
            w = jnp.exp(logw - log_ref)
        else:
            w = jnp.ones(v_idx.shape[0], jnp.float32)
            log_ref = jnp.zeros((), jnp.float32)
        return bank_variants, w, log_ref

    def _chunk_acc(self, x, v_idx, assignments, y, u):
        """One chunk end to end: draws -> scores -> bits -> acc (B, S)."""
        bits, w, log_ref = self._bits(x, v_idx, u)
        with jax.named_scope("mc.recombine"):
            if self._table is not None:
                with jax.named_scope("mc.recombine.table"):
                    acc = _recombine_acc(
                        obs.mark_lowered(bits, "recombine_table_traced"),
                        assignments, y, self._table, self._weights)
            else:
                with jax.named_scope("mc.recombine.votes"):
                    acc = dse._votes_accuracy_paired(
                        obs.mark_lowered(bits, "recombine_votes_traced"),
                        assignments, y, self._vote_a, self._vote_b)
        return acc, w, log_ref, bits

    def _step(self, state, x, v_idx, valid, floor, assignments, y, u):
        """THE streamed chunk program: state is donated, shapes depend on
        ``mc_chunk`` and the assignment matrix only — one compile per
        machine regardless of the total variant count."""
        acc, w, log_ref, _ = self._chunk_acc(x, v_idx, assignments, y, u)
        with jax.named_scope("mc.fold"):
            return mcstream.update_stream(state, acc, w, valid, floor,
                                          log_ref=log_ref)

    def _scores(self, x, v_idx, u):
        """Chunk scores ``(B, n, P, 2)`` + weights relative to the chunk's
        own log-scale (also returned): the step's draws and lanes."""
        with jax.named_scope("mc.draw"):
            bank_variants, w, log_ref = self._chunk_banks(v_idx, u)
        flat = _all_scores_mc(
            x, self._linear_banks, self._lanes.kernel_banks, bank_variants,
            self._lanes.inv_perm, int(v_idx.shape[0]), False,
            self.use_pallas, interpret=self.interpret)      # (B, n, 2P)
        with jax.named_scope("mc.lanes.hw"):
            if self._lanes.needed is not None:
                flat = obs.mark_lowered(flat, "mc_lanes_restricted_traced")
            scores = jnp.stack(
                [flat[..., : self.n_pairs], flat[..., self.n_pairs:]],
                axis=-1)
        return scores, w, log_ref

    def _bits(self, x, v_idx, u):
        """Chunk bits ``(B, n, P, 2)`` + the weights of :meth:`_scores`;
        un-donated under ``_bits_jit``, the dense oracle."""
        scores, w, log_ref = self._scores(x, v_idx, u)
        with jax.named_scope("mc.lanes.hw"):
            return (scores >= 0.0).astype(jnp.int32), w, log_ref

    # -- sharded step (variant axis over a mesh) -----------------------------

    def _make_sharded_step(self, mesh):
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import VARIANTS_AXIS

        if VARIANTS_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} lack '{VARIANTS_AXIS}'; "
                f"build one with launch.mesh.make_variant_mesh()")
        rep, shd = P(), P(VARIANTS_AXIS)

        def local_step(state, x, v_idx, valid, floor, assignments, y, u):
            # Each device draws + scores its slice of the chunk; the
            # LINEAR aggregates (centered on the replicated running mean)
            # merge with one psum, the extrema with pmin/pmax, and every
            # device applies the identical merge — the state stays
            # replicated without a broadcast.  Per-device log-scales are
            # aligned to their pmax before the psum (factor is the
            # literal 1.0 on every device whenever the scales agree —
            # always, for non-IS methods — so those sums stay bit-exact).
            acc, w, log_ref, _ = self._chunk_acc(x, v_idx, assignments, y, u)
            with jax.named_scope("mc.fold"):
                agg = mcstream.chunk_aggregates(
                    state.mean, acc, w, valid, floor, state.hist.shape[1],
                    log_ref=log_ref)
                ax = VARIANTS_AXIS
                ref = jax.lax.pmax(agg.log_ref, ax)
                f = jnp.where(agg.log_ref == ref, jnp.float32(1.0),
                              jnp.exp(agg.log_ref - ref))
                agg = mcstream.ChunkAgg(
                    n_c=jax.lax.psum(agg.n_c, ax),
                    w_c=jax.lax.psum(f * agg.w_c, ax),
                    w2_c=jax.lax.psum(f * f * agg.w2_c, ax),
                    s1=jax.lax.psum(f * agg.s1, ax),
                    s2=jax.lax.psum(f * agg.s2, ax),
                    exceed=jax.lax.psum(f * agg.exceed, ax),
                    amin=jax.lax.pmin(agg.amin, ax),
                    amax=jax.lax.pmax(agg.amax, ax),
                    hist=jax.lax.psum(f * agg.hist, ax),
                    log_ref=ref)
                return mcstream.merge_stream(state, agg)

        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, rep, shd, shd, rep, rep, rep, shd),
            out_specs=rep, check_vma=False)
        return jax.jit(fn, donate_argnums=(0,))

    def _sharded_step(self, mesh):
        if mesh not in self._sharded_steps:
            self._sharded_steps[mesh] = self._make_sharded_step(mesh)
        return self._sharded_steps[mesh]

    # -- restricted step ------------------------------------------------------

    def _view(self, a: np.ndarray) -> "StreamingMCMachine":
        """The machine whose step runs the ``(S, P)`` assignments ``a``.

        Where ``method == 'iid'`` and ``a`` selects (column = 1) a strict
        subset of the analog candidates, a view of this machine whose
        lanes lower only those pairs: the same code, its own jitted step,
        dense oracle and sharded steps, built once per set of pairs.
        Otherwise the machine itself: every other method lays its draws
        (the ``is`` log-weight, the QMC block) over every candidate slot,
        and a sweep selects every pair.
        """
        if self._restrict is None or self.method != "iid":
            return self
        selected = np.flatnonzero(np.asarray(a).any(axis=0))
        needed = self._analog_pairs.intersection(int(i) for i in selected)
        if needed == self._analog_pairs:
            return self
        if needed not in self._restricted:
            view = copy.copy(self)
            view._lanes = self._restrict(needed)
            view._restrict = None
            view._restricted = {}
            view._sharded_steps = {}
            view._step_jit = jax.jit(view._step, donate_argnums=(0,))
            view._bits_jit = jax.jit(view._bits)
            self._restricted[needed] = view
        return self._restricted[needed]

    def lane_grids(self, assignments=None) -> list:
        """Padded ``(Pb, M)`` slot grids of the variant banks in the step
        that runs ``assignments`` (None: the candidate step)."""
        run = self if assignments is None \
            else self._view(self._assignment_matrix(assignments))
        return run._lanes.grids

    # -- host driver ---------------------------------------------------------

    def _chunk_size(self, mesh=None) -> int:
        b = self.mc_chunk
        if mesh is not None:
            nd = int(np.prod(list(mesh.shape.values())))
            b = -(-b // nd) * nd      # round up to a whole per-device slice
        return b

    def _step_slices(self, b: int, n_rows: int, mesh=None) -> int:
        """Slices of the variant lanes in one step of ``b`` variants over
        ``n_rows`` rows (each device's share of the chunk on a mesh)."""
        if mesh is not None:
            b //= int(np.prod(list(mesh.shape.values())))
        return max((_lane_slices(b, n_rows, *g) for g in self._lanes.grids),
                   default=0)

    def _chunk_inputs(self, start: int, b: int, n_variants: int):
        # Host numpy on purpose: eager jnp ops bake `start` in as a
        # constant and would compile one tiny program per distinct chunk
        # start; numpy arrays cross the jit boundary with zero compiles.
        v_idx = np.arange(start, start + b, dtype=np.int32)
        valid = (np.arange(start, start + b) < n_variants).astype(np.float32)
        if self._sampler is not None:
            u = self._sampler.chunk(start, b)
        else:
            u = np.zeros((b, 0), np.float32)
        return v_idx, valid, u

    def _assignment_matrix(self, assignments) -> np.ndarray:
        a = np.atleast_2d(np.asarray(assignments)).astype(np.int32)
        if a.shape[1] != self.n_pairs:
            raise ValueError(
                f"assignments have {a.shape[1]} pairs, machine has "
                f"{self.n_pairs}")
        return a

    def _prep(self, x, y, assignments):
        x = jnp.asarray(np.asarray(x), jnp.float32)
        if x.ndim != 2 or (self.n_features and x.shape[1] != self.n_features):
            raise ValueError(
                f"expected (n, {self.n_features}) inputs, got shape {x.shape}")
        y = jnp.asarray(np.asarray(y), jnp.int32)
        return x, y, self._assignment_matrix(assignments)

    def stream(self, x, y, assignments, n_variants: int,
               accuracy_floor: float, mesh=None,
               confidence: float = mcstream.DEFAULT_CONFIDENCE,
               ci: str = "wilson") -> dict:
        """Stream ``n_variants`` mismatch instances through the donated
        chunk step and return ``mcstream.finalize``'s statistics dict
        (per-assignment mean/std/worst/yield + binomial bounds + the raw
        histogram sketch under ``"hist"``).

        ``mesh``: a ``make_variant_mesh`` shards each chunk's variant
        axis across devices (chunk size rounds up to a whole per-device
        slice; the validity mask keeps the padded tail inert).

        The step is the restricted one where :meth:`_view` says so, from
        ``assignments`` and the method, before the first dispatch.
        """
        if n_variants < 1:
            raise ValueError(f"n_variants must be >= 1, got {n_variants}")
        with jax.profiler.TraceAnnotation("repro.mc.prep"):
            x, y, a = self._prep(x, y, assignments)
            run = self._view(a)
            a = jnp.asarray(a)
            b = self._chunk_size(mesh)
            step = (run._step_jit if mesh is None
                    else run._sharded_step(mesh))
            floor = jnp.float32(accuracy_floor)
            state = mcstream.init_stream(
                int(a.shape[0]), mcstream.hist_bins(int(x.shape[0])))
        cells = b * int(x.shape[0]) * run._lanes.slots
        slices = run._step_slices(b, int(x.shape[0]), mesh)
        restricted = int(run is not self)
        with jax.profiler.TraceAnnotation("repro.mc.dispatch"):
            for start in range(0, n_variants, b):
                v_idx, valid, u = self._chunk_inputs(start, b, n_variants)
                state = step(state, x, v_idx, valid, floor, a, y, u)
                self.steps_run += 1
                self.variants_run += min(b, n_variants - start)
                self.lane_cells_run += cells
                self.lane_slices_run += slices
                self.restricted_steps_run += restricted
        with jax.profiler.TraceAnnotation("repro.mc.wait"):
            state = jax.block_until_ready(state)
        with jax.profiler.TraceAnnotation("repro.mc.finalize"):
            out = mcstream.finalize(state, confidence, ci)
            out["hist"] = np.asarray(state.hist, np.float64)
        out["n_variants"] = int(n_variants)
        out["method"] = self.method
        out["accuracy_floor"] = float(accuracy_floor)
        return out

    def pair_bits_dense(self, x, v_idx, assignments=None) -> np.ndarray:
        """Dense oracle: the bit tensor ``(B, n, P, 2)`` of the GLOBAL
        variant indices ``v_idx`` — the exact bits the streamed chunks
        fold away.  Small-V parity tests recombine these through the
        dense ``dse.assignment_accuracies_mc`` path and compare against
        the streamed accumulators.  ``assignments``: the bits of the step
        that streams them (None: the candidate step)."""
        if assignments is not None:
            run = self._view(self._assignment_matrix(assignments))
            if run is not self:
                return run.pair_bits_dense(x, v_idx)
        x = jnp.asarray(np.asarray(x), jnp.float32)
        v_idx = np.asarray(v_idx, np.int32)
        if self._sampler is not None:
            if not np.array_equal(
                    v_idx, np.arange(v_idx[0], v_idx[0] + len(v_idx))):
                raise ValueError(
                    "QMC methods need a contiguous v_idx range (the "
                    "low-discrepancy stream is indexed, not keyed)")
            u = jnp.asarray(self._sampler.chunk(int(v_idx[0]), len(v_idx)))
        else:
            u = jnp.zeros((len(v_idx), 0), jnp.float32)
        bits, _, _ = self._bits_jit(x, jnp.asarray(v_idx), u)
        return np.asarray(bits)

    def chunk_weights(self, v_idx) -> np.ndarray:
        """ABSOLUTE (mean-centered) sampling weights of the given global
        variants (1 unless ``method='is'``) — the IS-estimator tests'
        hook.  The in-graph weights are chunk-relative; folding the
        chunk's log-scale back in happens here in host f64, so weights
        from different chunks of one stream are mutually comparable
        (introspection only — huge banks can overflow even f64)."""
        d = self.n_features
        x = jnp.zeros((1, d), jnp.float32)
        v_idx = np.asarray(v_idx, np.int32)
        if self._sampler is not None:
            u = jnp.asarray(self._sampler.chunk(int(v_idx[0]), len(v_idx)))
        else:
            u = jnp.zeros((len(v_idx), 0), jnp.float32)
        _, w, log_ref = self._bits_jit(x, jnp.asarray(v_idx), u)
        return np.asarray(w, np.float64) * np.exp(float(log_ref))

    def step_memory_analysis(self, n_val: int, n_assignments: int = 1,
                             mesh=None, assignments=None):
        """XLA ``memory_analysis`` of the compiled chunk step at the given
        validation/assignment shapes — the object the flat-memory CI gate
        inspects.  Returns None when the backend does not report one."""
        return self.compiled_step(n_val, n_assignments, mesh,
                                  assignments).memory_analysis()

    def compiled_step(self, n_val: int, n_assignments: int = 1, mesh=None,
                      assignments=None):
        """The chunk step compiled at the given validation/assignment
        shapes.  Its ``as_text()`` names each device op and carries the
        op's scope (``mc.draw``, ``mc.lanes.*``, ...) in the HLO metadata
        ``op_name``, which a profiler trace's op events on the TPU do
        not.  ``assignments``: the step :meth:`stream` runs them with
        (their count replaces ``n_assignments``); None: the candidate
        step."""
        if assignments is None:
            run = self
            a = jnp.zeros((int(n_assignments), self.n_pairs), jnp.int32)
        else:
            a = self._assignment_matrix(assignments)
            run = self._view(a)
            a = jnp.asarray(a)
        b = self._chunk_size(mesh)
        x = jnp.zeros((int(n_val), self.n_features), jnp.float32)
        y = jnp.zeros((int(n_val),), jnp.int32)
        state = mcstream.init_stream(
            int(a.shape[0]), mcstream.hist_bins(int(n_val)))
        v_idx, valid, u = self._chunk_inputs(0, b, b)
        step = run._step_jit if mesh is None else run._sharded_step(mesh)
        return step.lower(state, x, v_idx, valid, jnp.float32(0.5), a, y,
                          u).compile()


def compile_mc_stream(
    candidates: Sequence,
    n_classes: int,
    key: jax.Array,
    method: str = "iid",
    mc_chunk: int = MC_STREAM_CHUNK,
    sigma_scale: float = 1.0,
    is_scale: float = 2.0,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> StreamingMCMachine:
    """Lower per-pair candidates to a flat-memory streaming MC machine.

    ``candidates``/``n_classes``/``key`` as :func:`compile_variants` —
    the key is split once per pair exactly the same way, and variant
    ``v`` of pair ``p`` derives from ``fold_in(keys[p], v)``, so the
    stream is a pure function of ``(key, v)``: chunk-size invariant,
    restartable, and shardable.  (The *sequence* of draws differs from
    :func:`compile_variants`'s one-shot ``(V, ...)`` sampling — the
    streamed engine's dense oracle is its own
    :meth:`StreamingMCMachine.pair_bits_dense`, not the old machine.)
    A restricted step (:func:`_restricted_lanes`) keeps each pair's key.

    ``method``: ``'iid'`` Gaussian draws, ``'sobol'`` scrambled Sobol' /
    ``'stratified'`` Latin-hypercube over the reduced mismatch space
    (inverse-CDF to normals in-graph), or ``'is'`` importance sampling
    with draws widened by ``is_scale`` and self-normalized weights
    (DESIGN.md §10.3).  Streaming always samples WITHOUT the nominal
    row — parity with the nominal machine is a tolerance contract via
    the accumulators, not a bit-identity row (the dense machines keep
    that contract).
    """
    pairs = class_pairs(n_classes)
    if len(candidates) != len(pairs):
        raise ValueError(
            f"{len(candidates)} candidate pairs for {n_classes} classes "
            f"(expected {len(pairs)})")
    p = len(pairs)
    keys = jax.random.split(key, p)
    specs = []
    analog_clfs: dict[int, AnalogBinaryClassifier] = {}
    analog_rows: dict[int, int] = {}
    for i, (lin_clf, rbf_clf) in enumerate(candidates):
        specs.append(_lower_classifier(i, lin_clf))
        specs.append(_lower_classifier(p + i, rbf_clf))
        if isinstance(rbf_clf, AnalogBinaryClassifier):
            analog_clfs[p + i] = rbf_clf
            analog_rows[p + i] = i
    # Row i of the one split table belongs to analog pair p+i (the
    # compile_variants convention).  Gather each pair's key here, ONCE,
    # so the per-bank constant builder never re-reads the table.
    analog_keys = {q: keys[i] for q, i in analog_rows.items()}
    linear_groups, kernel_groups = _group_specs(specs)
    linear_banks = [_LinearBank.build(g) for g in linear_groups]
    kernel_banks, stream_consts = [], []
    u_offset = 0
    for g in kernel_groups:
        bank = _KernelBank.build(g)
        kernel_banks.append(bank)
        in_group = [s.pair in analog_clfs for s in g]
        if not any(in_group):
            stream_consts.append(None)
            continue
        if not all(in_group):  # cannot happen: 'hw' curves group apart
            raise ValueError(
                "bank mixes variant and variant-free lanes; grouping bug")
        bc = _stream_bank_const(
            g, analog_clfs, analog_keys, int(bank.sv.shape[1]),
            int(bank.sv.shape[2]), u_offset)
        u_offset += bc.u_width
        stream_consts.append(bc)
    lanes = _StreamLanes(kernel_banks, stream_consts,
                         _inverse_perm(linear_banks, kernel_banks, 2 * p))
    analog_specs = {s.pair: s for s in specs if s.pair in analog_clfs}
    restrict = functools.partial(
        _restricted_lanes, linear_banks,
        [b for b, bc in zip(kernel_banks, stream_consts) if bc is None],
        analog_specs, analog_clfs, analog_keys, p)
    return StreamingMCMachine(
        n_classes, linear_banks, lanes,
        method=method, mc_chunk=mc_chunk, sigma_scale=sigma_scale,
        is_scale=is_scale, key_data=_key_data(key),
        use_pallas=use_pallas, interpret=interpret, restrict=restrict)


def _size_groups(n_support) -> list:
    """Size groups of analog pairs of true M ``n_support``: index arrays
    into it, at most ``_MAX_LANE_GROUPS``, minimising the padded cells
    plus ``_LANE_GROUP_CELLS`` a group."""
    return trainer.size_sorted_groups(
        n_support, _MAX_LANE_GROUPS,
        lambda n, m: n * m + _LANE_GROUP_CELLS, operator.add)


def _restricted_lanes(linear_banks, fixed_banks, analog_specs, analog_clfs,
                      analog_keys, n_pairs: int,
                      needed: frozenset) -> _StreamLanes:
    """Lanes of a step that lowers only the analog pairs ``needed``.

    The variant-free banks ``fixed_banks`` stay as they are.  The needed
    analog specs (``analog_specs`` by lowering column ``n_pairs + i``) are
    sorted by true M and split into at most ``_MAX_LANE_GROUPS`` contiguous
    groups that minimise the padded cells, ``count x group max``, plus
    ``_LANE_GROUP_CELLS`` a group (:func:`_size_groups`); each group is a
    bank padded to its own max.  Each pair keeps its key, and
    its draw at the group's M is the first rows of its draw at the
    candidate M (``jax_threefry_partitionable``), so every true cell gets
    the mismatch the candidate step gives it.  The analog column of an
    analog pair outside ``needed`` reads the pair's linear column.
    """
    kernel_banks = list(fixed_banks)
    stream_consts = [None] * len(kernel_banks)
    _, groups = _group_specs([analog_specs[n_pairs + i]
                              for i in sorted(needed)])
    u_offset = 0
    for g in groups:
        for idx in _size_groups([s.sv.shape[0] for s in g]):
            sub = [g[k] for k in idx]
            bank = _KernelBank.build(sub)
            bc = _stream_bank_const(
                sub, analog_clfs, analog_keys, int(bank.sv.shape[1]),
                int(bank.sv.shape[2]), u_offset)
            u_offset += bc.u_width
            kernel_banks.append(bank)
            stream_consts.append(bc)
    stand_in = {col: col - n_pairs for col in analog_specs
                if col - n_pairs not in needed}
    inv_perm = _inverse_perm(linear_banks, kernel_banks, 2 * n_pairs,
                             stand_in)
    return _StreamLanes(kernel_banks, stream_consts, inv_perm,
                        frozenset(needed))


def _stream_bank_const(group, analog_clfs, analog_keys, m_max: int, d: int,
                       u_offset: int) -> _StreamBankConst:
    """Build one bank's generation constants from its lowered specs."""
    n_pairs_bank = len(group)
    dva = np.zeros((n_pairs_bank, m_max), np.float32)
    pos = np.zeros((n_pairs_bank, m_max), np.float32)
    neg = np.zeros((n_pairs_bank, m_max), np.float32)
    valid = np.zeros((n_pairs_bank, m_max), np.float32)
    grids, curves, lefts, rights, pair_key_list = [], [], [], [], []
    params = None
    true_dim = 0
    for j, spec in enumerate(group):
        clf = analog_clfs[spec.pair]
        if params is None:
            params = clf.hw.params
        elif clf.hw.params != params:
            raise ValueError(
                "analog candidates in one bank carry different "
                "CircuitParams; the streaming generator assumes one "
                "process corner per bank")
        m = clf.n_support
        dva[j, :m] = np.asarray(clf.hw.alpha_control_voltage(
            jnp.asarray(clf.alpha_hw, jnp.float32)), np.float32)
        pos[j, :m] = (clf.support_y > 0).astype(np.float32)
        neg[j, :m] = (clf.support_y <= 0).astype(np.float32)
        valid[j, :m] = 1.0
        order = np.argsort(clf.hw.dva_grid)
        grids.append(np.asarray(clf.hw.dva_grid, np.float32)[order])
        curves.append(np.asarray(clf.hw.alpha_curve, np.float32)[order])
        lefts.append(curves[-1][0])
        rights.append(curves[-1][-1])
        pair_key_list.append(analog_keys[spec.pair])
        true_dim += variant_dim(m, clf.n_features)
    if len({g.shape[0] for g in grids}) != 1:
        raise ValueError("analog alpha sweeps in one bank differ in length")
    return _StreamBankConst(
        pair_keys=jnp.stack(pair_key_list),
        dva=jnp.asarray(dva), pos_mask=jnp.asarray(pos),
        neg_mask=jnp.asarray(neg), slot_valid=jnp.asarray(valid),
        alpha_grid=jnp.asarray(np.stack(grids)),
        alpha_curve=jnp.asarray(np.stack(curves)),
        alpha_left=jnp.asarray(np.asarray(lefts, np.float32)),
        alpha_right=jnp.asarray(np.asarray(rights, np.float32)),
        params=params, u_offset=u_offset,
        u_width=n_pairs_bank * variant_dim(m_max, d), true_dim=true_dim)
