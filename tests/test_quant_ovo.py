"""Quantization + OvO/encoder tests (paper Sec. III-C, V-A2).

The property tests use hypothesis when it is installed; on a bare
environment they fall back to a fixed set of representative examples so
`python -m pytest -x -q` still collects and runs.
"""
import numpy as np
import pytest

from _compat import property_test

from repro.core import ovo, quant


# -- quantization -----------------------------------------------------------


@property_test(
    fixed_examples=[([0.0, 1.0, 0.5], 4), ([-2.0, 3.0, 0.3, 0.7], 2),
                    ([0.123, 0.456, 0.789], 8), ([1e-9, 1.0 - 1e-9], 6)],
    strategies=lambda st: (
        st.lists(st.floats(-2.0, 3.0), min_size=1, max_size=40),
        st.integers(2, 8)),
)
def test_quantize_unit_bounds_and_idempotence(vals, bits):
    x = np.asarray(vals)
    q = np.asarray(quant.quantize_unit(x, bits))
    assert np.all(q >= 0) and np.all(q <= 1)
    # idempotence: re-quantizing is a fixed point
    np.testing.assert_allclose(np.asarray(quant.quantize_unit(q, bits)), q,
                               atol=1e-12)
    # max error bound for in-range values
    inr = (x >= 0) & (x <= 1)
    if inr.any():
        lsb = 1.0 / ((1 << bits) - 1)
        # + f32 ulp slack: the ADC model computes in float32
        assert np.max(np.abs(q[inr] - x[inr])) <= lsb / 2 + 1e-6


@property_test(
    fixed_examples=[([-100.0, 100.0, 0.0], 4), ([0.001, -0.002, 0.5], 8),
                    ([99.9, -99.9, 1.0, -1.0], 12), ([3.14159, -2.71828], 6)],
    strategies=lambda st: (
        st.lists(st.floats(-100, 100), min_size=1, max_size=30),
        st.integers(4, 12)),
)
def test_fixed_point_bound(vals, bits):
    x = np.asarray(vals, np.float64)
    xq, fp = quant.quantize_tensor(x, bits)
    if np.max(np.abs(x)) > 0:
        # error bounded by half an LSB at the chosen scale (f32 slack; the
        # subnormal-amax case clamps the scale and rounds tiny x to 0)
        bound = max(fp.scale / 2, np.max(np.abs(x)) * 1e-6) + 1e-12
        assert np.max(np.abs(xq - x)) <= bound


def test_fixed_point_bound_subnormal_amax():
    """A float64 subnormal amax (found by the property test above) clamps
    to the finest scale instead of overflowing."""
    assert quant.best_frac_bits(np.array([2.2250738585e-313]), 4) == 126
    xq, _ = quant.quantize_tensor(np.array([2.2250738585e-313, -1e-320]), 4)
    np.testing.assert_array_equal(xq, 0.0)


def test_csd_and_hardware_class():
    assert quant.weight_hardware_class(0) == "zero"
    for p in (1, 2, 4, 64):
        assert quant.weight_hardware_class(p) == "pow2"
        assert quant.weight_hardware_class(-p) == "pow2"
    assert quant.weight_hardware_class(3) == "general"
    # CSD: 7 = 8 - 1 -> 2 digits; 5 = 4 + 1 -> 2; 21 = 16+4+1 -> 3
    assert quant.csd_nonzero_digits(7) == 2
    assert quant.csd_nonzero_digits(5) == 2
    assert quant.csd_nonzero_digits(21) == 3
    assert quant.csd_nonzero_digits(1) == 1


def _minimal_signed_digits(c: int, _memo={0: 0, 1: 1}) -> int:
    """Brute-force minimal number of non-zero signed digits representing
    ``c`` as sum of +/- powers of two (the quantity CSD is minimal for).

    Recursion: even c needs exactly what c/2 needs (shift); odd c must
    spend one digit at bit 0, either +1 (leaving c-1) or -1 (leaving c+1);
    both residues halve to strictly smaller values for c >= 3.
    """
    c = abs(int(c))
    if c not in _memo:
        if c % 2 == 0:
            _memo[c] = _minimal_signed_digits(c // 2)
        else:
            _memo[c] = 1 + min(_minimal_signed_digits((c - 1) // 2),
                               _minimal_signed_digits((c + 1) // 2))
    return _memo[c]


def test_csd_digits_minimal_for_all_8bit_codes():
    """``csd_nonzero_digits`` equals the brute-force minimal signed-digit
    count for every 8-bit weight code (the cost model's adder count per
    bespoke constant multiplier rests on this)."""
    for c in range(-255, 256):
        assert quant.csd_nonzero_digits(c) == _minimal_signed_digits(c), c


def test_weight_hardware_class_all_8bit_codes():
    """zero/pow2 codes are exactly the multiplier-free classes: zero is
    code 0, pow2 is a single signed digit at a non-trivial magnitude."""
    for c in range(-255, 256):
        cls = quant.weight_hardware_class(c)
        if c == 0:
            assert cls == "zero"
        elif _minimal_signed_digits(c) == 1:
            # one signed digit <=> |c| is a power of two
            assert cls == "pow2", c
            assert abs(c) & (abs(c) - 1) == 0
        else:
            assert cls == "general", c


# -- OvO encoder ------------------------------------------------------------


@pytest.mark.parametrize("k", [3, 4, 5])
def test_encoder_equals_votes_exhaustive(k):
    """The hardwired encoder (Fig. 1) == majority voting w/ tiebreak, for
    EVERY possible bit pattern (exhaustive truth-table check)."""
    table = ovo.build_encoder_table(k)
    n_bits = len(ovo.class_pairs(k))
    codes = np.arange(1 << n_bits)
    bits = ((codes[:, None] >> np.arange(n_bits)[None]) & 1).astype(np.int32)
    np.testing.assert_array_equal(
        ovo.decide_encoder(bits, table), ovo.decide_votes(bits, k))


def test_unanimous_winner():
    """If one class wins all its pairwise games it must be predicted."""
    k = 4
    pairs = ovo.class_pairs(k)
    for c in range(k):
        bits = np.zeros((len(pairs),), np.int32)
        for p, (i, j) in enumerate(pairs):
            if i == c:
                bits[p] = 1
            elif j == c:
                bits[p] = 0
            else:
                bits[p] = np.random.RandomState(c * 7 + p).randint(2)
        assert ovo.decide_votes(bits, k) == c


def test_digital_linear_classifier_quantized_path():
    rng = np.random.RandomState(0)
    from repro.core import svm as svm_mod
    x = rng.rand(100, 4)
    y = np.where(x @ np.array([1.0, -2.0, 0.5, 0.0]) + 0.3 > 0, 1.0, -1.0)
    m = svm_mod.train_binary(x, y, "linear", c=10.0, n_epochs=200)
    clf = ovo.DigitalLinearClassifier.deploy(m, weight_bits=8, input_bits=4)
    bits = clf.predict_bits(x)
    agree = np.mean(bits == (svm_mod.decision_function(m, x) >= 0))
    assert agree >= 0.9  # 4-bit ADC costs a little accuracy, not much


def test_digital_rbf_classifier_matches_float():
    rng = np.random.RandomState(1)
    from repro.core import svm as svm_mod
    x = rng.rand(120, 3)
    y = np.where(((x - 0.5) ** 2).sum(1) < 0.1, 1.0, -1.0)
    m = svm_mod.train_binary(x, y, "rbf", gamma=8.0, c=10.0, n_epochs=200)
    clf = ovo.DigitalRBFClassifier.deploy(m)
    agree = np.mean(clf.predict_bits(x)
                    == (svm_mod.decision_function(m, x) >= 0))
    assert agree >= 0.93
