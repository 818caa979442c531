"""One-vs-One multiclass SVM with encoder decision logic (paper Sec. II-A, III-C).

A K-class problem decomposes into K(K-1)/2 binary classifiers, one per
unordered class pair (c_i, c_j), i < j.  Each produces ONE bit:

    bit == 1  ->  the pair's FIRST class (c_i) wins
    bit == 0  ->  the pair's SECOND class (c_j) wins

Decision-making is an *encoder* (paper Fig. 1): the bit vector is mapped
directly to a class label, replacing counter+argmax circuitry.  Behaviorally
the encoder realises vote counting with a lowest-index tiebreak; we provide
both the behavioral decision (`decide_votes`, jit-able) and an explicit
truth-table builder (`build_encoder_table`, used by the hardware cost model
to size the encoder and by tests to prove encoder == votes).

The module also contains the *deployed* digital classifiers — the bespoke
fixed-point realizations whose outputs feed the encoder:

  * ``DigitalLinearClassifier``  — 4-bit ADC inputs x quantized hardwired
    weights, adder tree, bias, sign (paper Fig. 3).
  * ``DigitalRBFClassifier``     — the all-digital RBF baseline the paper
    compares against (quantized SVs/alphas, exact exp in fixed point).

Analog RBF classifiers (``repro.core.analog.AnalogBinaryClassifier``) plug in
through the same ``predict_bits`` protocol: analog-in, digital-out.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Protocol, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core import svm as svm_mod
from repro.core.svm import SVMModel
from repro.core import kernels as kern


def class_pairs(n_classes: int) -> list[tuple[int, int]]:
    """All OvO pairs (i, j), i < j — line 1 of Algorithm 1."""
    return list(itertools.combinations(range(n_classes), 2))


# ---------------------------------------------------------------------------
# Decision logic
# ---------------------------------------------------------------------------


def votes_from_bits(bits: np.ndarray, n_classes: int) -> np.ndarray:
    """bits (..., P) -> votes (..., K).  Pure counting semantics."""
    pairs = class_pairs(n_classes)
    votes = np.zeros(bits.shape[:-1] + (n_classes,), np.int32)
    for p, (i, j) in enumerate(pairs):
        votes[..., i] += bits[..., p]
        votes[..., j] += 1 - bits[..., p]
    return votes


def decide_votes(bits: np.ndarray, n_classes: int) -> np.ndarray:
    """Majority vote with lowest-index tiebreak (the encoder's semantics)."""
    return np.argmax(votes_from_bits(bits, n_classes), axis=-1)


def build_encoder_table(n_classes: int) -> np.ndarray:
    """Explicit truth table of the decision encoder: 2^P entries -> class id.

    This is the combinational function the paper hardwires (Fig. 1).  Entry
    index packs the pair bits little-endian (pair p is bit p).  Used by the
    cost model (literal counting) and by the encoder==votes equivalence test.
    Only practical for K <= 5 (P <= 10, 1024 entries) — exactly the FE regime.
    """
    pairs = class_pairs(n_classes)
    n_bits = len(pairs)
    table = np.zeros((1 << n_bits,), np.int32)
    for code in range(1 << n_bits):
        bits = np.array([(code >> p) & 1 for p in range(n_bits)], np.int32)
        table[code] = decide_votes(bits, n_classes)
    return table


def decide_encoder(bits: np.ndarray, table: np.ndarray) -> np.ndarray:
    """Run the hardware encoder: pack bits -> index the truth table."""
    n_bits = bits.shape[-1]
    weights = (1 << np.arange(n_bits)).astype(np.int64)
    codes = (bits.astype(np.int64) @ weights)
    return table[codes]


#: Packed-encoder regime bound: past P pair bits the 2^P truth table is
#: unbuildable, and every consumer (MulticlassSVM, the compiled machines,
#: the DSE) must route through votes or the DAG front instead.  Kept here —
#: the OvO layer — so ``api.compiled.MAX_TABLE_BITS`` and the streaming MC
#: engine share one definition of "the FE regime".
MAX_TABLE_BITS = 12


def pair_index_matrix(n_classes: int) -> np.ndarray:
    """(K, K) int32: ``[i, j] -> p`` with ``class_pairs(K)[p] == (i, j)``
    for i < j (symmetric; the diagonal is self-pairs and stays 0 — never a
    legal lookup).  Closed form of the ``itertools.combinations`` order:
    ``p = i*K - i*(i+1)/2 + (j - i - 1)``.
    """
    k = int(n_classes)
    m = np.zeros((k, k), np.int32)
    for p, (i, j) in enumerate(class_pairs(k)):
        m[i, j] = p
        m[j, i] = p
    return m


def decide_dag(bits: np.ndarray, n_classes: int) -> np.ndarray:
    """DDAG elimination decision (Platt et al.): host-side reference.

    Maintains a candidate interval ``[lo, hi]`` (initially the full class
    range) and, for exactly K-1 steps, consults the single pair classifier
    ``(lo, hi)``: bit == 1 means the pair's FIRST class (``lo``) wins, so
    ``hi`` is eliminated (``hi -= 1``); bit == 0 eliminates ``lo``
    (``lo += 1``).  After K-1 steps ``lo == hi`` is the label — only
    O(K) of the P = K(K-1)/2 bits are ever consulted.

    **Agreement contract** (tested in ``tests/test_dag.py``): whenever some
    class wins ALL K-1 of its pairs (a Condorcet winner — it then holds
    K-1 votes while every other class lost at least one pair and holds at
    most K-2), the DAG returns exactly ``decide_votes``'s answer: that
    class can never be eliminated (every pair involving it points its
    way), and it is the unique vote argmax, so the lowest-index tiebreak
    never fires.  Without a Condorcet winner (vote cycles, ties) the two
    fronts may differ: votes resolves by total count + lowest index, the
    DAG by its elimination path.  That residual disagreement is a measured
    quantity (reported per dataset in BENCH_9.json), not a silent one.
    """
    bits = np.asarray(bits)
    k = int(n_classes)
    pm = pair_index_matrix(k)
    lead = bits.shape[:-1]
    lo = np.zeros(lead, np.int64)
    hi = np.full(lead, k - 1, np.int64)
    for _ in range(k - 1):
        b = np.take_along_axis(bits, pm[lo, hi][..., None], axis=-1)[..., 0]
        hi = np.where(b == 1, hi - 1, hi)
        lo = np.where(b == 1, lo, lo + 1)
    return lo


def condorcet_mask(bits: np.ndarray, n_classes: int) -> np.ndarray:
    """Boolean mask of samples whose vote winner is unambiguous (some class
    won all K-1 of its pairs) — exactly where votes == DAG is guaranteed."""
    votes = votes_from_bits(bits, n_classes)
    return votes.max(axis=-1) == n_classes - 1


# ---------------------------------------------------------------------------
# Deployed digital classifiers (bit-producing, quantized datapaths)
# ---------------------------------------------------------------------------


class BitClassifier(Protocol):
    def predict_bits(self, x: np.ndarray) -> np.ndarray: ...


class FloatBitClassifier:
    """Adapter: float SVMModel -> 1-bit OvO output (c_i wins iff f >= 0)."""

    def __init__(self, model: SVMModel):
        self.model = model

    def predict_bits(self, x: np.ndarray) -> np.ndarray:
        return (svm_mod.decision_function(self.model, x) >= 0.0).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class DigitalLinearClassifier:
    """Bespoke fully-parallel linear datapath (paper Fig. 3).

    ``w_q``/``b_q`` are the *dequantized* fixed-point constants hardwired in
    the multipliers; inputs pass through the ``input_bits`` ADC model.
    """

    w_q: np.ndarray          # (d,)
    b_q: float
    w_fp: quant.FixedPoint   # weight fixed-point format
    input_bits: int = 4

    @classmethod
    def deploy(
        cls, model: SVMModel, weight_bits: int = 8, input_bits: int = 4
    ) -> "DigitalLinearClassifier":
        if model.kind != "linear" or model.w is None:
            raise ValueError("only linear classifiers are deployed digitally")
        wb = np.concatenate([model.w, [model.bias]])
        wq, fp = quant.quantize_tensor(wb, weight_bits)
        return cls(w_q=wq[:-1], b_q=float(wq[-1]), w_fp=fp, input_bits=input_bits)

    def decision(self, x: np.ndarray) -> np.ndarray:
        xq = np.asarray(quant.quantize_unit(np.asarray(x), self.input_bits))
        return xq @ self.w_q + self.b_q

    def predict_bits(self, x: np.ndarray) -> np.ndarray:
        return (self.decision(x) >= 0.0).astype(np.int32)

    # -- hooks for the hardware cost model ---------------------------------
    def weight_codes(self) -> np.ndarray:
        return np.asarray(self.w_fp.codes(np.append(self.w_q, self.b_q)))

    @property
    def n_features(self) -> int:
        return int(self.w_q.shape[0])


@dataclasses.dataclass(frozen=True)
class DigitalRBFClassifier:
    """All-digital RBF baseline (paper Table II 'RBF (digital)').

    Support vectors and dual coefficients quantized "to ensure sufficient
    precision" (8-bit), inputs 4-bit; distance, exp and MACs computed exactly
    in fixed point (the digital exp unit is exact to output LSB).
    """

    support_x: np.ndarray    # (m, d) quantized
    coef: np.ndarray         # (m,) quantized alpha_j * y_j
    bias: float
    gamma: float
    sv_fp: quant.FixedPoint
    coef_fp: quant.FixedPoint
    input_bits: int = 4

    @classmethod
    def deploy(
        cls, model: SVMModel, sv_bits: int = 8, coef_bits: int = 8,
        input_bits: int = 4,
    ) -> "DigitalRBFClassifier":
        if model.kind != "rbf":
            raise ValueError("expected an RBF model")
        svq, sv_fp = quant.quantize_tensor(model.support_x, sv_bits)
        coef = model.alpha * model.support_y
        coefq, coef_fp = quant.quantize_tensor(
            np.concatenate([coef, [model.bias]]), coef_bits
        )
        return cls(
            support_x=svq, coef=coefq[:-1], bias=float(coefq[-1]),
            gamma=model.gamma, sv_fp=sv_fp, coef_fp=coef_fp,
            input_bits=input_bits,
        )

    def decision(self, x: np.ndarray) -> np.ndarray:
        xq = jnp.asarray(quant.quantize_unit(np.asarray(x), self.input_bits))
        k = kern.rbf_kernel(
            xq.astype(jnp.float32), jnp.asarray(self.support_x, jnp.float32),
            jnp.float32(self.gamma),
        )
        coef = jnp.asarray(self.coef, jnp.float32)
        return np.asarray(kern.matmul(k, coef)) + self.bias

    def predict_bits(self, x: np.ndarray) -> np.ndarray:
        return (self.decision(x) >= 0.0).astype(np.int32)

    @property
    def n_support(self) -> int:
        return int(self.support_x.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.support_x.shape[1])


# ---------------------------------------------------------------------------
# The full multiclass machine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MulticlassSVM:
    """K-class OvO SVM: a bank of bit classifiers + the decision encoder."""

    n_classes: int
    classifiers: Sequence[BitClassifier]   # one per class_pairs(n_classes)
    kernel_map: Sequence[str]              # 'linear' | 'rbf' per pair

    def __post_init__(self):
        assert len(self.classifiers) == len(class_pairs(self.n_classes))
        # The 2^P packed table only exists in the FE regime; past it the
        # machine decides by the equivalent vote counting (decide_votes) —
        # building the table at K=12 (P=66) would be a 2^66 blowup.
        self._table = (build_encoder_table(self.n_classes)
                       if len(class_pairs(self.n_classes)) <= MAX_TABLE_BITS
                       else None)

    def predict_bits(self, x: np.ndarray) -> np.ndarray:
        return np.stack([c.predict_bits(x) for c in self.classifiers], axis=-1)

    def predict(self, x: np.ndarray) -> np.ndarray:
        bits = self.predict_bits(x)
        if self._table is None:
            return decide_votes(bits, self.n_classes)
        return decide_encoder(bits, self._table)

    def accuracy(self, x: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(x) == np.asarray(y)))

    @property
    def n_rbf(self) -> int:
        return sum(k == "rbf" for k in self.kernel_map)

    @property
    def n_linear(self) -> int:
        return sum(k == "linear" for k in self.kernel_map)
