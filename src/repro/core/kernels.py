"""Kernel functions for the mixed-kernel SVM (paper Eq. 2-6).

Three kernel families:
  * linear      — K(x, z) = x.z                              (digital domain)
  * rbf         — K(x, z) = exp(-gamma ||x - z||^2)          (ideal Gaussian)
  * sech2 (hw)  — the hardware transfer of the cascaded subthreshold
                  differential pairs, Eq. (4):
                      I_out/I_in = 1/((1+e^{-x})(1+e^{x})) = (1/4) sech^2(x/2)
                  with x = dv / (n * V_T).  Near the origin this matches the
                  Gaussian with gamma0 = 1 / (4 n^2 V_T^2)  (Eq. 5).

All kernels operate on the squared-distance decomposition
``||x - z||^2 = ||x||^2 + ||z||^2 - 2 x.z`` so the dominant term is a matmul
(MXU-friendly); the Pallas kernel in ``repro.kernels.rbf`` implements the
tiled version and is validated against these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs

#: Precision of every f32 contraction on the SVM path, the Pallas tiles
#: and their jnp oracles included.  The TPU's default for f32 is one bf16
#: MXU pass (~1e-2 relative on a d = 5 dot), far outside the
#: comparator-tie epsilon (DESIGN.md §1.4); ``HIGHEST`` restores f32.  On
#: the CPU it changes nothing.
PRECISION = jax.lax.Precision.HIGHEST

# Thermal voltage at 300 K (V) and typical IGZO subthreshold slope factor.
V_T: float = 0.02585
N_SLOPE: float = 1.38


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at :data:`PRECISION`: every contraction of the SVM path
    goes here."""
    return jnp.matmul(a, b, precision=PRECISION)


def pairwise_sq_dists(x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """||x_i - z_j||^2 for x:(n,d), z:(m,d) -> (n,m), matmul-dominant form."""
    xx = jnp.sum(x * x, axis=-1)[:, None]
    zz = jnp.sum(z * z, axis=-1)[None, :]
    xz = matmul(x, z.T)
    return jnp.maximum(xx + zz - 2.0 * xz, 0.0)


def linear_kernel(x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """K(x, z) = x.z  (paper Sec. II-A)."""
    return matmul(x, z.T)


def rbf_kernel(x: jnp.ndarray, z: jnp.ndarray, gamma: jnp.ndarray) -> jnp.ndarray:
    """Ideal Gaussian RBF kernel, Eq. (2)."""
    return jnp.exp(-gamma * pairwise_sq_dists(x, z))


def gamma_subthreshold(n: float = N_SLOPE, v_t: float = V_T) -> float:
    """gamma0 of the un-scaled hardware cell, Eq. (5): 1 / (4 n^2 V_T^2)."""
    return 1.0 / (4.0 * n * n * v_t * v_t)


def sech2_cell(dv: jnp.ndarray, n: float = N_SLOPE, v_t: float = V_T) -> jnp.ndarray:
    """Single-dimension hardware Gaussian cell transfer I_out/I_in, Eq. (4).

    Normalised so that sech2_cell(0) == 1 (the 1/4 peak factor and the 1/4^D
    product attenuation of Eq. (6) cancel in the decision function because the
    comparator only sees the *difference* of rail currents; absolute current
    scale is carried by the bias current in the behavioural model).
    """
    x = dv / (n * v_t)
    # sech^2(x/2) == 4 / (2 + e^x + e^-x); write it in the cascaded-pair form
    # of Eq. (4) times 4 for the normalisation described above.
    return 4.0 / ((1.0 + jnp.exp(-x)) * (1.0 + jnp.exp(x)))


def sech2_kernel(
    x: jnp.ndarray,
    z: jnp.ndarray,
    gamma: jnp.ndarray,
    v_scale: float = 1.0,
    n: float = N_SLOPE,
    v_t: float = V_T,
) -> jnp.ndarray:
    """Hardware separable kernel, Eq. (6) + input scaling of Eq. (8).

    Features are mapped to voltages by ``dv = v_scale * (x_d - z_d)`` and the
    requested ``gamma`` (in feature units) is realised by scaling the input
    relative to the native cell gamma:  s = sqrt(gamma / gamma0_feature)
    where gamma0_feature = gamma0_volts * v_scale^2.
    """
    gamma0_feat = gamma_subthreshold(n, v_t) * v_scale * v_scale
    s = jnp.sqrt(gamma / gamma0_feat)
    # (n, m, d) differences; D <= 5 in hardware so this stays tiny for the
    # paper's workloads.  The product across dimensions is Eq. (6).
    dv = v_scale * (x[:, None, :] - z[None, :, :]) * s
    return jnp.prod(sech2_cell(dv, n, v_t), axis=-1)


# ---------------------------------------------------------------------------
# Uniform-grid interpolation (measured transfer curves)
# ---------------------------------------------------------------------------


def _grid_is_uniform(grid, rel_tol: float = 1e-3) -> bool:
    """True when ``grid`` is a cast linspace (the DC-sweep abscissa)."""
    import numpy as np

    steps = np.diff(np.asarray(grid, np.float64))
    if steps.size == 0 or np.any(steps <= 0):
        return False
    mean = steps.mean()
    return bool(np.max(np.abs(steps - mean)) <= rel_tol * abs(mean))


def curve_pair_gather(curve, i):
    """``(curve[i], curve[i + 1])`` by two XLA gathers: the reference
    lookup, and the one every platform but the TPU runs."""
    i = obs.mark_lowered(i, "lut_gather_traced")
    return jnp.take(curve, i), jnp.take(curve, i + 1)


def _bf16_head(x):
    """``x`` (f32) with its low 16 bits cleared: its leading 8 significant
    bits, a value bf16 holds exactly (truncated, not rounded)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _bf16_split(x):
    """f32 ``x`` as bf16 ``(hi, mid, lo)`` with ``(hi + mid) + lo == x``
    exactly in f32 (DESIGN.md §6.3): each part takes the next 8 of the 24
    significant bits, and every step is a bit mask or an exact f32
    subtraction, which no excess-precision rewrite can shorten."""
    hi = _bf16_head(x)
    r = x - hi
    mid = _bf16_head(r)
    lo = r - mid
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def curve_pair_mxu(curve, i):
    """``(curve[i], curve[i + 1])`` without a gather: the bf16 one-hot of
    ``i`` contracted on the MXU against a ``(n_seg, 6)`` bf16 table
    ``[f0_hi, f1_hi, f0_mid, f1_mid, f0_lo, f1_lo]`` in one ``dot_general``
    over every leading axis, accumulated in f32.  Each output is one
    product ``1 x part`` among zeros, so the parts come back exactly and
    their f32 sum is the curve value, bit for bit, for every finite entry
    of magnitude 0 or at least 2**-103 (below that a part can fall under
    bf16's normal range, which the MXU flushes to zero; a -0.0 entry
    reads +0.0).  On a TPU v5e the gather it replaces ran at ~1.2e8
    values/s."""
    n_seg = curve.shape[0] - 1
    i = obs.mark_lowered(i, "lut_mxu_traced")
    c = curve.astype(jnp.float32)
    parts = zip(_bf16_split(c[:-1]), _bf16_split(c[1:]))
    table = jnp.stack([p for pair in parts for p in pair], axis=-1)
    onehot = (i[..., None] == jnp.arange(n_seg, dtype=i.dtype)).astype(
        jnp.bfloat16)
    g = jnp.einsum("...s,sk->...k", onehot, table,
                   preferred_element_type=jnp.float32)
    # Summed over the parts in any order, f0 and f1 stay exact: the parts
    # hold disjoint bits of one value, so every partial sum fits in f32.
    # As one reduction XLA keeps it inside the contraction's fusion.
    f = g.reshape(g.shape[:-1] + (3, 2)).sum(axis=-2)
    return f[..., 0], f[..., 1]


def _uniform_interp(v, curve, lo, hi, left, right, inv_step):
    """``jnp.interp`` on a uniform ascending grid: O(1) bin location.

    The DC-sweep abscissa is a linspace, so the segment index and the
    interpolation fraction come from one multiply (``u = (v-lo)*inv_step``)
    instead of a per-query binary search, and only the two bracketing curve
    values are looked up: on the TPU by :func:`curve_pair_mxu`, elsewhere
    by :func:`curve_pair_gather`, chosen by the lowering platform; both
    return the same bits.  The result tracks ``jnp.interp`` to ~1e-6 (the
    fraction's f32 rounding times the max segment slope; same order as the
    eager-vs-jit fusion noise the compiled path already carries);
    out-of-range queries clamp to ``left``/``right`` exactly like the
    behavioral model's ``kernel_1d``.
    """
    n_seg = curve.shape[0] - 1
    u = (v - lo) * inv_step
    i = jnp.clip(jnp.floor(u).astype(jnp.int32), 0, n_seg - 1)
    t = u - i.astype(jnp.float32)
    with jax.named_scope("hw.lut"):
        f0, f1 = jax.lax.platform_dependent(
            curve, i, tpu=curve_pair_mxu, default=curve_pair_gather)
    f = f0 + t * (f1 - f0)
    f = jnp.where(v < lo, left, f)
    f = jnp.where(v > hi, right, f)
    return f


def measured_cell(v, grid, curve, left, right, uniform: bool, inv_step):
    """ONE measured-transfer-curve cell evaluation, shared by every
    consumer of a calibrated analog sweep (the nominal compiled machine,
    the Monte-Carlo variant lanes, hardware-in-the-loop training).

    ``uniform`` is a static Python bool: a linspace abscissa takes the
    O(1) ``_uniform_interp`` fast path, anything else falls back to
    ``jnp.interp``.  Keeping this a single function is part of the
    nominal-equivalence contract (DESIGN.md §6.3): the variant path runs
    the *same* interpolation code as the nominal path, so a zero-offset
    variant cannot drift from it.
    """
    if uniform:
        return _uniform_interp(v, curve, grid[0], grid[-1], left, right,
                               inv_step)
    return jnp.interp(v, grid, curve, left=left, right=right)


def _grid_fast_path(grid) -> dict:
    """{'uniform_grid': bool, 'inv_step': float} for a sweep abscissa."""
    import numpy as np

    if grid is None or not _grid_is_uniform(grid):
        return {"uniform_grid": False, "inv_step": 0.0}
    g = np.asarray(grid, np.float64)
    return {"uniform_grid": True,
            "inv_step": float((g.shape[0] - 1) / (g[-1] - g[0]))}


def kernel_matrix(
    kind, x: jnp.ndarray, z: jnp.ndarray, gamma: jnp.ndarray | float = 1.0
) -> jnp.ndarray:
    """Dispatch on kernel kind; ``kind`` may also be a callable
    (x, z, gamma) -> K, e.g. the calibrated analog behavioral model for
    hardware-in-the-loop training."""
    if callable(kind):
        return kind(x, z, jnp.asarray(gamma))
    if kind == "linear":
        return linear_kernel(x, z)
    if kind == "rbf":
        return rbf_kernel(x, z, jnp.asarray(gamma))
    if kind == "sech2":
        return sech2_kernel(x, z, jnp.asarray(gamma))
    raise ValueError(f"unknown kernel kind: {kind!r}")
