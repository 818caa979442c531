"""Fixed-point quantization for the digital datapath (paper Sec. V-A2).

The paper's digital side is bespoke fixed-point hardware:
  * sensory inputs are uniformly quantized to 4-bit by the ADC,
  * linear-classifier weights/biases are quantized per [12] "to preserve
    accuracy" (we implement the standard bespoke flow: symmetric per-weight
    fixed-point with a shared power-of-two scale chosen to minimise the
    decision-function perturbation),
  * digital-RBF support vectors / dual coefficients are quantized "to ensure
    sufficient precision" (8-bit in our model).

Everything here is pure JAX so quantized inference can be jitted/vmapped and
property-tested with hypothesis (bounds, idempotence, monotonicity).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Uniform affine quantization in [0, 1] — the ADC model
# ---------------------------------------------------------------------------


def quantize_unit(x, bits: int = 4):
    """Uniformly quantize values in [0, 1] to ``bits`` (ADC of Fig. 1).

    Returns the *dequantized* (reconstructed) value, i.e. what the digital
    datapath actually computes with.  Values outside [0, 1] saturate, like a
    real ADC against its reference rails.
    """
    levels = (1 << bits) - 1
    xq = jnp.round(jnp.clip(x, 0.0, 1.0) * levels)
    return xq / levels


def quantize_unit_codes(x, bits: int = 4):
    """Integer ADC codes in [0, 2^bits - 1]."""
    levels = (1 << bits) - 1
    return jnp.round(jnp.clip(x, 0.0, 1.0) * levels).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Symmetric fixed-point for weights / support vectors / coefficients
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FixedPoint:
    """Symmetric fixed-point code: value = code * 2^-frac_bits, |code| < 2^(bits-1)."""

    bits: int
    frac_bits: int

    @property
    def scale(self) -> float:
        return 2.0 ** (-self.frac_bits)

    def quantize(self, x):
        qmax = (1 << (self.bits - 1)) - 1
        code = jnp.clip(jnp.round(jnp.asarray(x) / self.scale), -qmax, qmax)
        return code * self.scale

    def codes(self, x):
        qmax = (1 << (self.bits - 1)) - 1
        return jnp.clip(
            jnp.round(jnp.asarray(x) / self.scale), -qmax, qmax
        ).astype(jnp.int32)


def best_frac_bits(x: np.ndarray, bits: int) -> int:
    """Pick frac_bits so the largest |x| just fits (bespoke per-classifier scale)."""
    amax = float(np.max(np.abs(x))) if np.size(x) else 1.0
    if amax <= 0:
        return bits - 1
    qmax = (1 << (bits - 1)) - 1
    # need qmax * 2^-frac >= amax  =>  frac <= log2(qmax / amax);
    # clamped to the float32-safe exponent range (codes are computed in
    # f32 on device — extreme scales would under/overflow there).
    # A subnormal amax overflows qmax / amax to inf: clip before int().
    frac = np.floor(np.log2(qmax / amax) + 1e-9)
    return int(np.clip(frac, -(126 - bits), 126))


def quantize_tensor(x: np.ndarray, bits: int) -> tuple[np.ndarray, FixedPoint]:
    fp = FixedPoint(bits=bits, frac_bits=best_frac_bits(x, bits))
    return np.asarray(fp.quantize(x)), fp


# ---------------------------------------------------------------------------
# Bespoke-hardware weight analysis (drives the cost model of hwcost.py)
# ---------------------------------------------------------------------------


def csd_nonzero_digits(code: int) -> int:
    """Number of non-zero digits in the canonical signed digit form of ``code``.

    A bespoke constant multiplier costs one adder per CSD non-zero digit minus
    one; zero / power-of-two weights cost NO multiplier at all — this is
    exactly the effect the paper observes on Balance ("digital linear
    component converged to zero or power of 2 weights").
    """
    c = abs(int(code))
    count = 0
    while c:
        if c & 1:
            # canonical recoding: runs of 1s become +/- pair
            if (c & 3) == 3:
                c += 1  # use a -1 digit
            count += 1
        c >>= 1
    return count


def weight_hardware_class(code: int) -> str:
    """'zero' | 'pow2' | 'general' — cost classes of a hardwired weight."""
    c = abs(int(code))
    if c == 0:
        return "zero"
    if (c & (c - 1)) == 0:
        return "pow2"
    return "general"
