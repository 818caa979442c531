"""Analog RBF classifier: circuit surrogate + behavioral model (paper III-B, IV-A).

Two layers, mirroring the paper's methodology exactly:

1. ``CircuitParams`` + the ``*_circuit`` functions — a transistor-level
   *surrogate simulator* standing in for Cadence Spectre.  It evaluates the
   actual subthreshold device equations of the FlexIC cells (exponential I-V
   with slope factor n, threshold mismatch, mirror ratio error, finite input
   range) rather than the ideal math.  DC sweeps of this surrogate play the
   role of the paper's SPICE sweeps.

2. ``AnalogRBFModel`` — the high-level *behavioral model* of Sec. IV-A: the
   measured transfer curve is kept as sampled data, an ideal Gaussian
   ``A0 exp(-gamma0 (dv - mu)^2)`` is fitted to it (Eq. 7) to extract gamma0,
   kernel widths gamma* are realised by input scaling s = sqrt(gamma*/gamma0)
   (Eq. 8), and the alpha multiplier is a logistic fitted as (x0, s) with the
   software-side inverse mapping  dV_alpha = x0 + s ln(1/alpha - 1)  (Eq. 9).

``AnalogBinaryClassifier`` deploys a trained RBF ``SVMModel`` onto this
hardware model: alpha normalisation into the (0,1) multiplier range, signed
accumulation of per-SV currents on +/- rails, and a comparator producing the
1-bit digital output (analog-in digital-out — no ADC).

Monte-Carlo variation (DESIGN.md §6): printed/flexible devices carry large
process variation, so a single nominal behavioral model under-reports the
deployed accuracy distribution.  ``VariantSet`` holds per-SV-cell mismatch
draws for ``V`` fabricated instances (4 Gaussian-cell offsets + 2 alpha-
multiplier offsets per cell, plus one comparator offset per instance);
``variant_transfer_params`` reduces the raw draws to per-cell perturbations
of the *measured* transfer curves (a horizontal threshold shift, a gain
factor, an alpha control-voltage shift/slope scale, a comparator offset),
so the zero-offset variant evaluates the exact same interpolation the
nominal path runs — bit-identical by construction.  ``AnalogRBFModel``,
``AnalogBinaryClassifier`` and ``VariantSet`` are registered pytrees, so
the variant axis vmaps end-to-end through one compiled program
(``repro.api.compiled.compile_variants``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernels as kern
from repro.core.kernels import N_SLOPE, V_T
from repro.core.svm import SVMModel

# --------------------------------------------------------------------------
# Circuit surrogate ("SPICE")
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CircuitParams:
    """Process/bias parameters of the FlexIC subthreshold cells."""

    n: float = N_SLOPE            # subthreshold slope factor
    v_t: float = V_T              # thermal voltage (V)
    i_bias: float = 150e-9        # kernel chain bias current I_in (A)
    v_supply: float = 1.0         # analog supply (V), regulated from 1.5 V
    v_range: float = 0.40         # usable differential input range (V)
    sigma_vth: float = 3.0e-3     # per-device threshold mismatch (V)
    mirror_err: float = 0.02      # readout mirror ratio error (rel.)
    lambda_ds: float = 0.01       # residual V_DS sensitivity (rel.)
    comparator_offset: float = 1.0e-10  # comparator input offset (A)
    comparator_sigma: float = 1.0e-10   # comparator offset mismatch (A, 1-sigma)


def _pair_fraction(x: jnp.ndarray) -> jnp.ndarray:
    """Subthreshold differential-pair current split: I1/I_tail."""
    return 1.0 / (1.0 + jnp.exp(-x))


def gaussian_cell_circuit(
    dv: jnp.ndarray,
    p: CircuitParams,
    offsets: Optional[jnp.ndarray] = None,  # (4,) vth offsets + mirror/ds errs
) -> jnp.ndarray:
    """I_out/I_in of one Gaussian cell (Q1..Q6 of Fig. 2) with non-idealities.

    Ideal limit (offsets = 0): Eq. (4),
      I_out/I_in = 1 / ((1+e^-x)(1+e^x)) = (1/4) sech^2(x/2),  x = dv/(n V_T).
    """
    if offsets is None:
        offsets = jnp.zeros((4,))
    nvt = p.n * p.v_t
    dvc = jnp.clip(dv, -p.v_range, p.v_range)  # input rails
    x = (dvc - offsets[0] * p.sigma_vth) / nvt
    x2 = (dvc - offsets[1] * p.sigma_vth) / nvt
    f1 = _pair_fraction(x)            # (Q1, Q2) pair
    f2 = 1.0 - _pair_fraction(x2)     # cascaded complementary (Q3, Q4) pair
    mirror = 1.0 + offsets[2] * p.mirror_err      # Q6/Q4 readout ratioing
    vds_mod = 1.0 + offsets[3] * p.lambda_ds      # weak V_DS dependence
    return f1 * f2 * mirror * vds_mod


def alpha_multiplier_circuit(
    dva: jnp.ndarray,
    p: CircuitParams,
    offsets: Optional[jnp.ndarray] = None,  # (2,) vth offset, slope error
) -> jnp.ndarray:
    """I_out/I_in of the alpha multiplier: logistic in the control voltage."""
    if offsets is None:
        offsets = jnp.zeros((2,))
    nvt = p.n * p.v_t * (1.0 + offsets[1] * 0.02)
    return 1.0 / (1.0 + jnp.exp((dva - offsets[0] * p.sigma_vth) / nvt))


def dc_sweep_gaussian(
    p: CircuitParams, key: Optional[jax.Array] = None, n_points: int = 257
) -> tuple[np.ndarray, np.ndarray]:
    """DC sweep of the Gaussian cell: (dv, I_out/I_in). Plays SPICE's role."""
    dv = jnp.linspace(-p.v_range, p.v_range, n_points)
    offsets = jax.random.normal(key, (4,)) if key is not None else jnp.zeros((4,))
    out = gaussian_cell_circuit(dv, p, offsets)
    return np.asarray(dv), np.asarray(out)


def dc_sweep_alpha(
    p: CircuitParams, key: Optional[jax.Array] = None, n_points: int = 257
) -> tuple[np.ndarray, np.ndarray]:
    dva = jnp.linspace(-0.25, 0.25, n_points)
    offsets = jax.random.normal(key, (2,)) if key is not None else jnp.zeros((2,))
    return np.asarray(dva), np.asarray(alpha_multiplier_circuit(dva, p, offsets))


# --------------------------------------------------------------------------
# Monte-Carlo process variation (DESIGN.md §6)
# --------------------------------------------------------------------------

#: Raw mismatch draws per 1-D Gaussian cell (two pair vth offsets, mirror
#: ratio error, V_DS modulation) and per alpha multiplier (vth offset,
#: slope error) — the same offset vectors the circuit surrogate consumes.
N_GAUSS_OFFSETS = 4
N_ALPHA_OFFSETS = 2


@dataclasses.dataclass(frozen=True)
class VariantSet:
    """Standard-normal mismatch draws for ``V`` instances of one classifier.

    Shapes: ``gauss (V, m, d, 4)`` — per SV x feature Gaussian cell,
    ``alpha (V, m, 2)`` — per-SV alpha multiplier, ``comparator (V,)`` —
    one comparator per instance.  Row 0 is the all-zero *nominal* instance
    when sampled with ``include_nominal=True`` (the default everywhere):
    its evaluation is bit-identical to the un-varied path.
    """

    gauss: jnp.ndarray
    alpha: jnp.ndarray
    comparator: jnp.ndarray

    @property
    def n_variants(self) -> int:
        return int(self.gauss.shape[0])

    @property
    def n_support(self) -> int:
        return int(self.gauss.shape[1])

    @property
    def n_features(self) -> int:
        return int(self.gauss.shape[2])

    def iter_chunks(self, chunk_size: int):
        """Yield ``(start, VariantSet)`` slices of at most ``chunk_size``
        variants — the host-side streaming view of a materialized set.

        The tail chunk keeps its natural (smaller) length; callers that
        need one compiled shape pad it themselves (the streaming machine
        never materializes a ``VariantSet`` this large in the first place
        — it draws chunks on the fly with :func:`sample_variant_chunk`).
        """
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        for start in range(0, self.n_variants, chunk_size):
            sl = slice(start, start + chunk_size)
            yield start, VariantSet(gauss=self.gauss[sl],
                                    alpha=self.alpha[sl],
                                    comparator=self.comparator[sl])


jax.tree_util.register_dataclass(
    VariantSet, data_fields=["gauss", "alpha", "comparator"], meta_fields=[])


def variant_dim(n_support: int, n_features: int) -> int:
    """Flat mismatch dimension of ONE classifier circuit instance:
    ``m*d`` Gaussian cells x 4 offsets + ``m`` alpha multipliers x 2
    offsets + 1 comparator offset.  The per-pair slice width of the
    QMC/importance-sampling uniform block (DESIGN.md §10)."""
    return (n_support * n_features * N_GAUSS_OFFSETS
            + n_support * N_ALPHA_OFFSETS + 1)


def variant_set_from_flat(
    z: jnp.ndarray, n_support: int, n_features: int,
    sigma_scale: float = 1.0,
) -> VariantSet:
    """Reshape flat standard-normal draws ``z (..., D)`` into a
    :class:`VariantSet` with the same leading dims.

    ``D = variant_dim(n_support, n_features)``; the layout is
    ``[gauss (m*d*4) | alpha (m*2) | comparator (1)]``.  This is how the
    QMC path turns one scrambled-Sobol row into a variant: every mismatch
    dimension owns a fixed coordinate of the low-discrepancy point set.
    """
    m, d = int(n_support), int(n_features)
    ng = m * d * N_GAUSS_OFFSETS
    na = m * N_ALPHA_OFFSETS
    if z.shape[-1] != ng + na + 1:
        raise ValueError(
            f"flat mismatch block has {z.shape[-1]} dims, expected "
            f"{ng + na + 1} for m={m}, d={d}")
    lead = z.shape[:-1]
    s = jnp.float32(sigma_scale)
    return VariantSet(
        gauss=s * z[..., :ng].reshape(lead + (m, d, N_GAUSS_OFFSETS)),
        alpha=s * z[..., ng:ng + na].reshape(lead + (m, N_ALPHA_OFFSETS)),
        comparator=s * z[..., ng + na])


def sample_variant_chunk(
    key: jax.Array,
    v_idx: jnp.ndarray,
    n_support: int,
    n_features: int,
    sigma_scale: float = 1.0,
) -> VariantSet:
    """Draw mismatch for the *global* variant indices ``v_idx (B,)`` only.

    The streaming generation contract (DESIGN.md §10): variant ``v``'s
    offsets are a pure function of ``(key, v)`` via
    ``fold_in(key, v) -> split(3)`` — never of the chunk size or position —
    so a V=10^6 run never materializes more than one chunk of draws and
    re-chunking the same key reproduces the identical stream.  ``fold_in``
    derives a fresh key per index (it does not consume ``key``); the
    3-way split mirrors :func:`sample_variant_offsets`'s independent
    gauss/alpha/comparator streams.  Traceable: ``v_idx`` may be a traced
    int array inside the streaming machine's jitted chunk step.
    """
    s = jnp.float32(sigma_scale)

    def draw(idx):
        kg, ka, kc = jax.random.split(jax.random.fold_in(key, idx), 3)
        return VariantSet(
            gauss=s * jax.random.normal(
                kg, (n_support, n_features, N_GAUSS_OFFSETS)),
            alpha=s * jax.random.normal(
                ka, (n_support, N_ALPHA_OFFSETS)),
            comparator=s * jax.random.normal(kc, ()))

    return jax.vmap(draw)(jnp.asarray(v_idx))


def sample_variant_offsets(
    key: jax.Array,
    n_variants: int,
    n_support: int,
    n_features: int,
    include_nominal: bool = True,
    sigma_scale: float = 1.0,
) -> VariantSet:
    """Draw mismatch offsets for ``n_variants`` fabricated instances.

    ``key`` is an explicit ``jax.random`` key — there is no hidden global
    RNG state anywhere in the Monte-Carlo path.  ``sigma_scale`` multiplies
    the standard-normal draws, i.e. scales every process sigma
    (``sigma_vth``, ``mirror_err``, ``lambda_ds``, ``comparator_sigma``)
    jointly — the knob behind yield-vs-sigma sweeps.  With
    ``include_nominal`` (default) row 0 is the zero-offset instance, so
    ``n_variants`` counts it and ``n_variants - 1`` random instances are
    drawn.
    """
    if n_variants < 1 + int(include_nominal):
        raise ValueError(
            f"n_variants={n_variants} too small (include_nominal="
            f"{include_nominal})")
    v = n_variants - 1 if include_nominal else n_variants
    kg, ka, kc = jax.random.split(key, 3)
    s = jnp.float32(sigma_scale)
    gauss = s * jax.random.normal(kg, (v, n_support, n_features,
                                       N_GAUSS_OFFSETS))
    alpha = s * jax.random.normal(ka, (v, n_support, N_ALPHA_OFFSETS))
    comparator = s * jax.random.normal(kc, (v,))
    if include_nominal:
        gauss = jnp.concatenate([jnp.zeros_like(gauss[:1]), gauss])
        alpha = jnp.concatenate([jnp.zeros_like(alpha[:1]), alpha])
        comparator = jnp.concatenate(
            [jnp.zeros_like(comparator[:1]), comparator])
    return VariantSet(gauss=gauss, alpha=alpha, comparator=comparator)


@dataclasses.dataclass(frozen=True)
class VariantTransferParams:
    """Per-cell perturbations of the measured transfers for ``V`` instances.

    The raw circuit offsets of a :class:`VariantSet` are reduced to the
    four quantities a *calibrated* instance's transfer actually moves by
    (see DESIGN.md §6.2 for the derivation from the surrogate equations):

    * ``shift (V, m, d)``   — Gaussian-cell bell center shift (V): the
      common-mode vth offset of the two differential pairs,
    * ``gain (V, m, d)``    — cell peak gain: mirror-ratio and V_DS errors
      times the peak attenuation ``4 sig(-e)(1 - sig(e))`` of the
      *differential* vth offset ``e`` between the two pairs,
    * ``alpha_shift (V, m)`` / ``alpha_slope (V, m)`` — alpha-multiplier
      control-voltage offset and logistic slope scale,
    * ``comp_offset (V,)``  — comparator offset in units of I_in.

    All-zero draws reduce to shift 0, gain 1, slope 1 and the nominal
    comparator offset *exactly* (0.5 and 1.0 are exact in f32), so the
    nominal variant's arithmetic is bit-identical to the un-varied path.
    """

    shift: jnp.ndarray
    gain: jnp.ndarray
    alpha_shift: jnp.ndarray
    alpha_slope: jnp.ndarray
    comp_offset: jnp.ndarray


jax.tree_util.register_dataclass(
    VariantTransferParams,
    data_fields=["shift", "gain", "alpha_shift", "alpha_slope",
                 "comp_offset"],
    meta_fields=[])


def variant_transfer_params(
    v: VariantSet, p: CircuitParams
) -> VariantTransferParams:
    """Reduce raw mismatch draws to measured-transfer perturbations."""
    nvt = p.n * p.v_t
    g = v.gauss
    shift = (0.5 * (g[..., 0] + g[..., 1])) * p.sigma_vth
    diff = (0.5 * (g[..., 0] - g[..., 1])) * (p.sigma_vth / nvt)
    peak = 4.0 * _pair_fraction(-diff) * (1.0 - _pair_fraction(diff))
    gain = (peak
            * (1.0 + g[..., 2] * p.mirror_err)
            * (1.0 + g[..., 3] * p.lambda_ds))
    alpha_shift = v.alpha[..., 0] * p.sigma_vth
    alpha_slope = 1.0 + v.alpha[..., 1] * 0.02
    # Nominal offset divided in f64 first so variant 0 carries the exact
    # f32 cast of the same number the nominal lowering stores.
    comp_offset = (p.comparator_offset / p.i_bias
                   + v.comparator * (p.comparator_sigma / p.i_bias))
    return VariantTransferParams(
        shift=shift, gain=gain, alpha_shift=alpha_shift,
        alpha_slope=alpha_slope, comp_offset=comp_offset)


# --------------------------------------------------------------------------
# Fits (Sec. IV-A): ideal Gaussian (Eq. 7) and logistic (Eq. 9)
# --------------------------------------------------------------------------


def fit_gaussian(dv: np.ndarray, i_out: np.ndarray) -> tuple[float, float, float]:
    """Weighted LS fit of A0 exp(-g0 (dv-mu)^2) -> (A0, gamma0, mu).

    log I = a + b dv + c dv^2 with weights I^2 (emphasises the bell's core,
    where Eq. 5's Taylor matching holds), then gamma0 = -c, mu = b/(2 gamma0).
    """
    i = np.clip(np.asarray(i_out, np.float64), 1e-12, None)
    w = i * i
    v = np.asarray(dv, np.float64)
    basis = np.stack([np.ones_like(v), v, v * v], axis=1)
    wb = basis * w[:, None]
    coef = np.linalg.solve(basis.T @ wb, wb.T @ np.log(i))
    a, b, c = coef
    gamma0 = max(-c, 1e-9)
    mu = b / (2.0 * gamma0)
    a0 = float(np.exp(a + gamma0 * mu * mu))
    return a0, float(gamma0), float(mu)


def fit_logistic(dva: np.ndarray, ratio: np.ndarray) -> tuple[float, float]:
    """Fit  dV_alpha = x0 + s * ln(1/ratio - 1)  (Eq. 9) -> (x0, s)."""
    r = np.asarray(ratio, np.float64)
    keep = (r > 1e-4) & (r < 1.0 - 1e-4)
    z = np.log(1.0 / r[keep] - 1.0)
    v = np.asarray(dva, np.float64)[keep]
    s, x0 = np.polyfit(z, v, 1)
    return float(x0), float(s)


def nrmse(ref: np.ndarray, meas: np.ndarray) -> float:
    ref = np.asarray(ref, np.float64)
    meas = np.asarray(meas, np.float64)
    rng = float(ref.max() - ref.min()) or 1.0
    return float(np.sqrt(np.mean((ref - meas) ** 2)) / rng)


def pearson_r(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.corrcoef(a, b)[0, 1])


# --------------------------------------------------------------------------
# Behavioral model (Sec. IV-A) and hardware-deployed classifier
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AnalogRBFModel:
    """High-level behavioral model of one fabricated analog RBF core."""

    params: CircuitParams
    dv_grid: np.ndarray          # measured sweep abscissa (V)
    kernel_curve: np.ndarray     # measured I_out/I_in, normalised to peak 1
    a0: float                    # fitted Gaussian amplitude (Eq. 7)
    gamma0: float                # fitted gamma0 (1/V^2)
    mu: float                    # fitted center offset (V)
    alpha_x0: float              # logistic fit (Eq. 9)
    alpha_s: float
    dva_grid: np.ndarray         # measured alpha-sweep abscissa (V)
    alpha_curve: np.ndarray      # measured alpha multiplier ratio
    v_scale: float = 0.5         # feature-unit -> volt mapping

    @classmethod
    def from_circuit(
        cls,
        p: CircuitParams = CircuitParams(),
        key: Optional[jax.Array] = None,
        v_scale: float = 0.5,
    ) -> "AnalogRBFModel":
        """Calibrate the behavioral model from surrogate-SPICE DC sweeps.

        ``key`` seeds the fabricated instance's mismatch draws; the key is
        split so the Gaussian-cell and alpha-multiplier sweeps see
        *independent* offsets (reusing one key for two different draws
        silently correlates the two circuits).
        """
        kg = ka = None
        if key is not None:
            kg, ka = jax.random.split(key)
        dv, curve = dc_sweep_gaussian(p, kg)
        a0, g0, mu = fit_gaussian(dv, curve)
        dva, ratio = dc_sweep_alpha(p, ka)
        x0, s = fit_logistic(dva, ratio)
        return cls(
            params=p, dv_grid=dv, kernel_curve=curve / curve.max(),
            a0=a0, gamma0=g0, mu=mu, alpha_x0=x0, alpha_s=s,
            dva_grid=dva, alpha_curve=ratio, v_scale=v_scale,
        )

    # -- kernel ------------------------------------------------------------
    def gamma0_feature(self) -> float:
        """Fitted cell gamma expressed in (normalised-feature)^-2 units."""
        return self.gamma0 * self.v_scale * self.v_scale

    def input_scale(self, gamma_star) -> jnp.ndarray:
        """Eq. (8): s_gamma = sqrt(gamma*/gamma0).  jnp so it traces under
        vmap'd hyper-parameter grids during hardware-in-the-loop training."""
        return jnp.sqrt(jnp.asarray(gamma_star) / self.gamma0_feature())

    def kernel_1d(self, dv_volts: jnp.ndarray) -> jnp.ndarray:
        """Interpolate the measured transfer curve (paper: 'use the SPICE
        data together with the fitted gamma0').

        The fitted center offset ``mu`` (threshold-mismatch shift, Eq. 7) is
        compensated here: a fabricated core is calibrated so its bell peaks
        at zero differential input, which is exactly what fitting mu enables.
        """
        return jnp.interp(
            dv_volts + self.mu,
            jnp.asarray(self.dv_grid), jnp.asarray(self.kernel_curve),
            left=float(self.kernel_curve[0]), right=float(self.kernel_curve[-1]),
        )

    def kernel_1d_variants(
        self, dv_volts: jnp.ndarray, shift: jnp.ndarray, gain: jnp.ndarray
    ) -> jnp.ndarray:
        """Per-cell measured transfer under mismatch (DESIGN.md §6.2):

            ``gain * curve(dv + mu - shift)``

        ``dv_volts`` broadcasts against the per-cell ``shift``/``gain`` of
        :func:`variant_transfer_params` (typically ``dv (n, m, d)`` against
        ``(V, 1, m, d)`` for the ``(V, n, m, d)`` variant tensor).  With
        zero offsets this is ``curve(dv + mu) * 1.0`` — the exact
        :meth:`kernel_1d` arithmetic, bit for bit.
        """
        return gain * jnp.interp(
            dv_volts + self.mu - shift,
            jnp.asarray(self.dv_grid), jnp.asarray(self.kernel_curve),
            left=float(self.kernel_curve[0]), right=float(self.kernel_curve[-1]),
        )

    def kernel_response_variants(
        self,
        x: jnp.ndarray,
        sv: jnp.ndarray,
        gamma_star,
        shift: jnp.ndarray,
        gain: jnp.ndarray,
    ) -> jnp.ndarray:
        """Separable kernel of ``V`` mismatched instances: ``(V, n, m)``.

        ``x (n, d)``, ``sv (m, d)``, ``shift``/``gain (V, m, d)`` — every
        one of the ``V * m * d`` Gaussian cells evaluates its own perturbed
        transfer, vectorized over the whole ``(V, m)`` grid instead of the
        one shared 1-D curve of :meth:`kernel_response`.
        """
        s = self.input_scale(gamma_star)
        dv = self.v_scale * s * (x[:, None, :] - sv[None, :, :])  # (n, m, d)
        k = self.kernel_1d_variants(
            dv[None], shift[:, None], gain[:, None])              # (V, n, m, d)
        return jnp.prod(k, axis=-1)

    def kernel_response(
        self, x: jnp.ndarray, sv: jnp.ndarray, gamma_star
    ) -> jnp.ndarray:
        """Separable D-dim kernel (Eq. 6 + Eq. 8): x (n,d), sv (m,d) -> (n,m).

        This IS the paper's high-level behavioral model, and it is also the
        kernel used to TRAIN analog-bound classifiers (hardware-in-the-loop
        co-optimization) — so the deployed circuit computes with the exact
        kernel it was trained with.
        """
        s = self.input_scale(gamma_star)
        dv = self.v_scale * s * (x[:, None, :] - sv[None, :, :])
        return jnp.prod(self.kernel_1d(dv), axis=-1)

    # -- alpha multiplier ----------------------------------------------------
    def alpha_control_voltage(self, alpha: jnp.ndarray) -> jnp.ndarray:
        """Software mapping Eq. (9): desired alpha -> control differential."""
        a = jnp.clip(alpha, 1e-4, 1.0 - 1e-4)
        return self.alpha_x0 + self.alpha_s * jnp.log(1.0 / a - 1.0)

    def alpha_realized(self, dva: jnp.ndarray) -> jnp.ndarray:
        """Alpha the circuit actually realises for a control voltage —
        interpolated from the *measured* sweep of this fabricated instance
        (the same instance the logistic was fitted to)."""
        grid = jnp.asarray(self.dva_grid)
        curve = jnp.asarray(self.alpha_curve)
        order = jnp.argsort(grid)  # interp needs ascending x
        return jnp.interp(
            dva, grid[order], curve[order],
            left=float(curve[np.argmin(self.dva_grid)]),
            right=float(curve[np.argmax(self.dva_grid)]),
        )


@dataclasses.dataclass(frozen=True)
class AnalogBinaryClassifier:
    """A trained RBF SVM deployed on the analog hardware model (Sec. III-B)."""

    hw: AnalogRBFModel
    support_x: np.ndarray   # (m, d) hardwired SV bias voltages
    support_y: np.ndarray   # (m,) rail routing
    alpha_hw: np.ndarray    # (m,) normalised to (0, 1)
    bias_hw: float          # constant rail current (units of I_in)
    gamma_star: float

    @classmethod
    def deploy(
        cls,
        model: SVMModel,
        hw: AnalogRBFModel,
        alpha_floor_rel: float = 1.0 / 256.0,
    ) -> "AnalogBinaryClassifier":
        """Deploy an RBF-family SVM onto the analog hardware model.

        ``alpha_floor_rel`` prunes support vectors whose normalised dual
        coefficient falls below the alpha-control DAC resolution (8-bit by
        default): such alphas are indistinguishable from switch leakage in
        the fabricated circuit, so their cells are simply not instantiated.
        The pruned mass is bounded by m * floor, keeping the decision
        function perturbation below comparator resolution.
        """
        if model.kind not in ("rbf", "sech2", "hw"):
            raise ValueError("only RBF-family classifiers are deployed in analog")
        alpha = np.asarray(model.alpha, np.float64)
        amax = float(alpha.max()) if alpha.size else 1.0
        keep = np.flatnonzero(alpha >= alpha_floor_rel * amax)
        # Positive rescale (sign-invariant): alphas into the multiplier's (0,1).
        scale = amax * 1.05
        return cls(
            hw=hw,
            support_x=model.support_x[keep],
            support_y=model.support_y[keep],
            alpha_hw=alpha[keep] / scale,
            bias_hw=float(model.bias / scale),
            gamma_star=float(model.gamma),
        )

    def rail_currents(self, x: np.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(I_plus, I_minus) per input row, in units of I_in."""
        xj = jnp.asarray(x, jnp.float32)
        k = self.hw.kernel_response(
            xj, jnp.asarray(self.support_x, jnp.float32), self.gamma_star
        )  # (n, m)
        # Alpha path: desired -> control voltage (Eq. 9) -> realised (circuit).
        dva = self.hw.alpha_control_voltage(jnp.asarray(self.alpha_hw, jnp.float32))
        a = self.hw.alpha_realized(dva)
        cur = k * a[None, :]
        pos = jnp.asarray(self.support_y > 0, jnp.float32)
        i_plus = kern.matmul(cur, pos) + jnp.maximum(self.bias_hw, 0.0)
        i_minus = kern.matmul(cur, 1.0 - pos) + jnp.maximum(-self.bias_hw, 0.0)
        return i_plus, i_minus

    def predict_bits(self, x: np.ndarray) -> np.ndarray:
        """Comparator output: 1 if the + rail wins (class i of the pair)."""
        i_plus, i_minus = self.rail_currents(x)
        off = self.hw.params.comparator_offset / self.hw.params.i_bias
        return np.asarray(i_plus - i_minus + off >= 0.0, np.int32)

    # -- Monte-Carlo variation (DESIGN.md §6) --------------------------------

    def sample_variants(
        self,
        key: jax.Array,
        n_variants: int,
        include_nominal: bool = True,
        sigma_scale: float = 1.0,
    ) -> VariantSet:
        """Draw per-SV-cell mismatch for ``n_variants`` instances of THIS
        classifier's circuit (its ``m x d`` Gaussian cells, ``m`` alpha
        multipliers and one comparator)."""
        return sample_variant_offsets(
            key, n_variants, self.n_support, self.n_features,
            include_nominal=include_nominal, sigma_scale=sigma_scale)

    def decision_mc(self, x: np.ndarray, variants: VariantSet) -> jnp.ndarray:
        """Comparator input ``I+ - I- + offset`` per variant: ``(V, n)``.

        Every instance evaluates its own perturbed per-cell transfers
        (Gaussian cells AND alpha multipliers AND comparator) vectorized
        over the ``(V, m)`` grid; the zero-offset row reproduces the
        nominal :meth:`rail_currents`/:meth:`predict_bits` arithmetic
        bit for bit.
        """
        t = variant_transfer_params(variants, self.hw.params)
        xj = jnp.asarray(x, jnp.float32)
        k = self.hw.kernel_response_variants(
            xj, jnp.asarray(self.support_x, jnp.float32), self.gamma_star,
            t.shift, t.gain)                                      # (V, n, m)
        dva = self.hw.alpha_control_voltage(
            jnp.asarray(self.alpha_hw, jnp.float32))              # (m,)
        a = self.hw.alpha_realized(
            (dva[None, :] - t.alpha_shift) / t.alpha_slope)       # (V, m)
        cur = k * a[:, None, :]
        pos = jnp.asarray(self.support_y > 0, jnp.float32)
        neg = 1.0 - pos
        # Rail accumulation per variant with the exact nominal (n, m)@(m,)
        # matvec shapes: batched/reshaped contractions reduce m in a
        # different order on CPU (observed 1-ulp drift), which would break
        # the nominal-variant bit-identity contract.  This is the reference
        # path — the compiled MonteCarloMachine is the throughput path.
        bias_p = jnp.maximum(self.bias_hw, 0.0)
        bias_n = jnp.maximum(-self.bias_hw, 0.0)
        rows = [(kern.matmul(cur[i], pos) + bias_p)
                - (kern.matmul(cur[i], neg) + bias_n)
                for i in range(cur.shape[0])]
        return jnp.stack(rows) + t.comp_offset[:, None]

    def predict_bits_mc(
        self, x: np.ndarray, variants: VariantSet
    ) -> np.ndarray:
        """Per-variant comparator bits ``(V, n)`` int32."""
        return np.asarray(self.decision_mc(x, variants) >= 0.0, np.int32)

    @property
    def n_support(self) -> int:
        return int(self.support_x.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.support_x.shape[1])


# --------------------------------------------------------------------------
# Pytree registration: the behavioral model and the deployed classifier are
# batchable JAX containers (array/scalar fields are leaves; the frozen
# CircuitParams rides along as static aux data), so a stacked model vmaps
# over a leading variant/instance axis end-to-end.
# --------------------------------------------------------------------------

_RBF_MODEL_LEAVES = ("dv_grid", "kernel_curve", "a0", "gamma0", "mu",
                     "alpha_x0", "alpha_s", "dva_grid", "alpha_curve",
                     "v_scale")
_CLF_LEAVES = ("hw", "support_x", "support_y", "alpha_hw", "bias_hw",
               "gamma_star")


def _rbf_model_flatten(m: "AnalogRBFModel"):
    return tuple(getattr(m, f) for f in _RBF_MODEL_LEAVES), m.params


def _rbf_model_unflatten(params: CircuitParams, leaves) -> "AnalogRBFModel":
    return AnalogRBFModel(params, *leaves)


def _clf_flatten(c: "AnalogBinaryClassifier"):
    return tuple(getattr(c, f) for f in _CLF_LEAVES), None


def _clf_unflatten(_, leaves) -> "AnalogBinaryClassifier":
    return AnalogBinaryClassifier(*leaves)


jax.tree_util.register_pytree_node(
    AnalogRBFModel, _rbf_model_flatten, _rbf_model_unflatten)
jax.tree_util.register_pytree_node(
    AnalogBinaryClassifier, _clf_flatten, _clf_unflatten)
