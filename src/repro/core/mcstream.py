"""Streaming Monte-Carlo accumulators: flat memory from V=64 to V=10^6.

PR 4's variant axis materializes the full ``(V, n, P, 2)`` bit tensor and
a dense ``(V, S)`` accuracy grid — fine at V ~ 64, impossible at the
ppm-level tail-yield scale a fab signs off on.  This module holds the
in-graph accumulator algebra that replaces the dense grid (DESIGN.md §10):
a chunk of variants is generated, scored and *folded into fixed-shape
state*, then its buffers are reused for the next chunk.  Peak memory is a
function of the chunk size only, never of ``V``.

Contracts
---------
* :class:`StreamStats` — the donated accumulator pytree.  All in-graph
  fields are f32: counts are exact in f32 up to 2^24 (> 10^6 variants),
  and the mean/M2 recursion below keeps the second moment stable without
  f64 (which the F64-IN-JIT analyzer rule bans inside jit).
* :func:`chunk_aggregates` reduces one ``(B, S)`` accuracy chunk to
  per-chunk sums *relative to the running mean* (``state.mean`` is the
  centering point, so the raw-moment cancellation stays benign), all of
  them LINEAR in the variant axis — which is exactly what makes the
  multi-device leg a plain ``psum``/``pmin``/``pmax`` over a
  ``shard_map`` variant axis (``launch.mesh.make_variant_mesh``).
* :func:`merge_stream` is Chan's parallel Welford merge of one aggregate
  into the running state.  ``update_stream`` = aggregates + merge, the
  single-host path.
* Weights: every accumulator is *weighted* (``w = 1`` for iid/QMC
  sampling; self-normalized importance weights for ``method='is'``).
  ``finalize`` converts weighted sums to self-normalized estimates and
  reports the effective sample size ``n_eff = (Σw)² / Σw²`` — the n that
  enters the Wilson/Clopper-Pearson yield bounds, so IS runs cannot claim
  iid-sized confidence.
* Invalid slots (the tail chunk's padding) enter with ``valid = 0`` and
  contribute exactly nothing — one compiled program serves every V.
* The quantile sketch is a fixed-grid histogram over [0, 1]: accuracies
  live on the lattice ``k / n_val``, so with ``n_bins = n_val + 1``
  (up to :data:`MAX_HIST_BINS`) the sketch is *exact*, not approximate.

Host-side helpers: Wilson / Clopper-Pearson binomial bounds and the
scrambled-Sobol / Latin-hypercube chunk samplers (:class:`QMCSampler`),
both seeded deterministically from stored jax key data.  Sobol chunks are generated
with ``fast_forward`` so draw ``v`` depends only on the *global* variant
index — the streamed sequence is invariant to the chunk size, mirroring
the ``fold_in``-keyed iid draws.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernels as kern

#: Default two-sided confidence level of the yield interval.
DEFAULT_CONFIDENCE = 0.95

#: Histogram-sketch resolution cap.  Accuracies on n_val <= 1024 samples
#: are resolved exactly (bin lattice == accuracy lattice); beyond that the
#: quantile error is bounded by half a bin width, 1/2048.
MAX_HIST_BINS = 1025

_TINY = jnp.float32(1e-30)


# ---------------------------------------------------------------------------
# The accumulator pytree
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamStats:
    """Fixed-shape running statistics over the variant axis (S columns).

    ``count``/``w_sum``/``w2_sum`` are scalars (the weight stream is
    shared by all S assignments); ``mean``/``m2``/``exceed``/``amin``/
    ``amax`` are ``(S,)``; ``hist`` is the ``(S, n_bins)`` fixed-grid
    sketch.  The pytree is DONATED through the streaming chunk step:
    state in, state out, buffers reused across every chunk.
    """

    count: jnp.ndarray    # () f32 — number of valid variants folded in
    w_sum: jnp.ndarray    # () f32 — Σ w over valid variants
    w2_sum: jnp.ndarray   # () f32 — Σ w² over valid variants
    mean: jnp.ndarray     # (S,) f32 — weighted running mean
    m2: jnp.ndarray       # (S,) f32 — weighted Σ w (x - mean)²
    exceed: jnp.ndarray   # (S,) f32 — Σ w · [x >= floor]
    amin: jnp.ndarray     # (S,) f32 — min accuracy seen (+inf when empty)
    amax: jnp.ndarray     # (S,) f32 — max accuracy seen (-inf when empty)
    hist: jnp.ndarray     # (S, n_bins) f32 — weighted fixed-grid counts
    log_ref: jnp.ndarray  # () f32 — log-scale of every weighted sum
    # All weighted sums are stored RELATIVE to exp(log_ref) (a streaming
    # logsumexp): importance-sampling log-weights in high-dimensional
    # mismatch spaces routinely sit hundreds of nats from zero, where a
    # fixed f32 exp either underflows every weight to an exact zero or
    # clips a macroscopic fraction of draws into an artificial tie —
    # both silently corrupt n_eff.  The reference only ever grows
    # (running max); every self-normalized statistic is a ratio of sums
    # at the same scale, so exp(log_ref) cancels in `finalize`.


jax.tree_util.register_dataclass(
    StreamStats,
    data_fields=["count", "w_sum", "w2_sum", "mean", "m2", "exceed",
                 "amin", "amax", "hist", "log_ref"],
    meta_fields=[])


@dataclasses.dataclass
class ChunkAgg:
    """One chunk reduced to mergeable sums (centered on the running mean).

    Every field except ``amin``/``amax`` is a plain sum over the chunk's
    variant rows, so a sharded chunk merges with ``psum`` (and ``pmin``/
    ``pmax`` for the extrema) before one replicated :func:`merge_stream`.
    """

    n_c: jnp.ndarray      # () f32 — valid rows in the chunk
    w_c: jnp.ndarray      # () f32 — Σ w
    w2_c: jnp.ndarray     # () f32 — Σ w²
    s1: jnp.ndarray       # (S,) f32 — Σ w (x - center)
    s2: jnp.ndarray       # (S,) f32 — Σ w (x - center)²
    exceed: jnp.ndarray   # (S,) f32
    amin: jnp.ndarray     # (S,) f32
    amax: jnp.ndarray     # (S,) f32
    hist: jnp.ndarray     # (S, n_bins) f32
    log_ref: jnp.ndarray  # () f32 — log-scale of this chunk's sums


jax.tree_util.register_dataclass(
    ChunkAgg,
    data_fields=["n_c", "w_c", "w2_c", "s1", "s2", "exceed", "amin",
                 "amax", "hist", "log_ref"],
    meta_fields=[])


def hist_bins(n_val: int) -> int:
    """Sketch resolution for a validation set of ``n_val`` rows: the
    accuracy lattice size ``n_val + 1``, capped at :data:`MAX_HIST_BINS`."""
    return min(int(n_val) + 1, MAX_HIST_BINS)


def init_stream(n_assignments: int, n_bins: int) -> StreamStats:
    """All-zero state (extrema at +/- inf) for ``S`` assignment columns."""
    s = int(n_assignments)
    # Each leaf gets its own freshly-allocated buffer: the streaming step
    # donates the whole state pytree, and XLA rejects donating one buffer
    # through two arguments (`f(donate(a), donate(a))`).
    def z():
        return jnp.zeros((s,), jnp.float32) + jnp.float32(0)

    return StreamStats(
        count=jnp.zeros((), jnp.float32) + 0,
        w_sum=jnp.zeros((), jnp.float32) + 0,
        w2_sum=jnp.zeros((), jnp.float32) + 0,
        mean=z(), m2=z(), exceed=z(),
        amin=jnp.full((s,), jnp.inf, jnp.float32),
        amax=jnp.full((s,), -jnp.inf, jnp.float32),
        hist=jnp.zeros((s, int(n_bins)), jnp.float32) + 0,
        log_ref=jnp.full((), -jnp.inf, jnp.float32) + 0)


def chunk_aggregates(center: jnp.ndarray, acc: jnp.ndarray, w: jnp.ndarray,
                     valid: jnp.ndarray, floor: jnp.ndarray,
                     n_bins: int, log_ref=None) -> ChunkAgg:
    """Reduce one accuracy chunk ``acc (B, S)`` to mergeable sums.

    ``w``/``valid`` are ``(B,)`` f32; rows with ``valid = 0`` contribute
    exactly nothing (the tail-chunk padding contract).  ``center (S,)`` is
    the running mean the moments are taken around — after the first chunk
    it tracks the data, so the ``s2 - s1²/W`` cancellation in
    :func:`merge_stream` operates on small residuals.  ``log_ref`` is the
    log-scale the caller computed ``w`` at (importance sampling passes
    ``max(logw)`` over the chunk so ``w`` sits in ``(0, 1]``); ``None``
    means absolute weights (scale 0).
    """
    wv = w * valid                                    # (B,)
    dc = acc - center[None, :]                        # (B, S)
    inf = jnp.float32(jnp.inf)
    masked_lo = jnp.where(valid[:, None] > 0, acc, inf)
    masked_hi = jnp.where(valid[:, None] > 0, acc, -inf)
    bins = jnp.clip(jnp.round(acc * (n_bins - 1)).astype(jnp.int32),
                    0, n_bins - 1)                    # (B, S)
    s = acc.shape[1]
    hist = jnp.zeros((s, n_bins), jnp.float32)
    hist = hist.at[jnp.arange(s)[None, :], bins].add(
        jnp.broadcast_to(wv[:, None], bins.shape))
    if log_ref is None:
        log_ref = jnp.zeros((), jnp.float32)
    return ChunkAgg(
        n_c=jnp.sum(valid), w_c=jnp.sum(wv), w2_c=jnp.sum(wv * wv),
        s1=kern.matmul(wv, dc), s2=kern.matmul(wv, dc * dc),
        exceed=kern.matmul(wv, (acc >= floor).astype(jnp.float32)),
        amin=jnp.min(masked_lo, axis=0), amax=jnp.max(masked_hi, axis=0),
        hist=hist, log_ref=jnp.asarray(log_ref, jnp.float32))


def merge_stream(state: StreamStats, agg: ChunkAgg) -> StreamStats:
    """Chan's parallel merge of one (possibly psum-reduced) aggregate.

    The aggregate's moments are centered on ``state.mean``; with
    ``delta = s1 / w_c`` (the chunk mean minus the running mean) the
    chunk's own M2 is ``s2 - s1 · delta`` and the classic update applies.
    Empty chunks (``w_c = 0``) are exact no-ops.

    Both sides carry a log-scale; the merged state lives at the larger
    one and the *other* side's sums are multiplied down by the ratio
    (never up — no overflow).  When the scales already agree — every
    non-IS method pins them to 0 — the factors are the literal 1.0 and
    each product is bit-exact, so the unweighted paths are unchanged.
    The equal-scale branch also guards the empty ``-inf - -inf = nan``.
    """
    ref = jnp.maximum(state.log_ref, agg.log_ref)
    fs = jnp.where(state.log_ref == ref, jnp.float32(1.0),
                   jnp.exp(state.log_ref - ref))
    fc = jnp.where(agg.log_ref == ref, jnp.float32(1.0),
                   jnp.exp(agg.log_ref - ref))
    w_old = fs * state.w_sum
    w_c = fc * agg.w_c
    w_new = w_old + w_c
    delta = agg.s1 / jnp.maximum(agg.w_c, _TINY)          # (S,) scale-free
    m2_chunk = agg.s2 - agg.s1 * delta
    r = w_c / jnp.maximum(w_new, _TINY)
    return StreamStats(
        count=state.count + agg.n_c,
        w_sum=w_new,
        w2_sum=fs * fs * state.w2_sum + fc * fc * agg.w2_c,
        mean=state.mean + delta * r,
        m2=fs * state.m2 + fc * m2_chunk + delta * delta * w_old * r,
        exceed=fs * state.exceed + fc * agg.exceed,
        amin=jnp.minimum(state.amin, agg.amin),
        amax=jnp.maximum(state.amax, agg.amax),
        hist=fs * state.hist + fc * agg.hist,
        log_ref=ref)


def update_stream(state: StreamStats, acc: jnp.ndarray, w: jnp.ndarray,
                  valid: jnp.ndarray, floor: jnp.ndarray,
                  log_ref=None) -> StreamStats:
    """Single-host chunk update: aggregates + merge, all in-graph."""
    n_bins = state.hist.shape[1]
    return merge_stream(
        state, chunk_aggregates(state.mean, acc, w, valid, floor, n_bins,
                                log_ref=log_ref))


# ---------------------------------------------------------------------------
# Binomial confidence bounds (host side, f64)
# ---------------------------------------------------------------------------


def wilson_bounds(p: np.ndarray, n: np.ndarray,
                  confidence: float = DEFAULT_CONFIDENCE
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Two-sided Wilson score interval for a binomial proportion.

    Closed-form, well-behaved at p = 0 and p = 1 (where the naive Wald
    interval collapses to zero width — the "yield 0.03 ± everything"
    failure mode this PR closes).  ``n`` may be non-integer: the caller
    passes the *effective* sample size of a weighted stream.
    """
    p = np.asarray(p, np.float64)
    n = np.maximum(np.asarray(n, np.float64), 1e-12)
    z = float(_norm_ppf(0.5 + confidence / 2.0))
    z2 = z * z
    denom = 1.0 + z2 / n
    center = (p + z2 / (2.0 * n)) / denom
    half = z * np.sqrt(p * (1.0 - p) / n + z2 / (4.0 * n * n)) / denom
    # The interval holds p exactly; the clip to p only undoes rounding
    # (at p = 1, center + half can land one ulp below 1).
    return np.clip(center - half, 0.0, p), np.clip(center + half, p, 1.0)


def clopper_pearson_bounds(p: np.ndarray, n: np.ndarray,
                           confidence: float = DEFAULT_CONFIDENCE
                           ) -> tuple[np.ndarray, np.ndarray]:
    """Exact (conservative) Clopper-Pearson interval via the beta quantile.

    Non-integer ``k = p n`` (weighted streams) is supported: the beta
    quantile is continuous in its shape parameters.
    """
    from scipy.stats import beta

    p = np.asarray(p, np.float64)
    n = np.maximum(np.asarray(n, np.float64), 1e-12)
    k = np.clip(p * n, 0.0, n)
    alpha = 1.0 - confidence
    with np.errstate(invalid="ignore"):
        lo = beta.ppf(alpha / 2.0, k, n - k + 1.0)
        hi = beta.ppf(1.0 - alpha / 2.0, k + 1.0, n - k)
    lo = np.where(k <= 0.0, 0.0, lo)
    hi = np.where(k >= n, 1.0, hi)
    return np.clip(np.nan_to_num(lo, nan=0.0), 0.0, 1.0), \
        np.clip(np.nan_to_num(hi, nan=1.0), 0.0, 1.0)


def _norm_ppf(q: float) -> float:
    """Standard-normal quantile."""
    from scipy.stats import norm

    return float(norm.ppf(q))


def hist_quantiles(hist: np.ndarray, qs) -> np.ndarray:
    """Type-1 quantiles from the fixed-grid sketch.

    ``hist (S, n_bins)`` weighted counts -> ``(len(qs), S)`` accuracy
    values on the bin lattice.  Exact when the bin lattice contains the
    accuracy lattice (``n_bins = n_val + 1``).
    """
    hist = np.asarray(hist, np.float64)
    qs = np.atleast_1d(np.asarray(qs, np.float64))
    n_bins = hist.shape[1]
    total = np.maximum(hist.sum(axis=1, keepdims=True), 1e-300)
    cdf = np.cumsum(hist, axis=1) / total                   # (S, n_bins)
    out = np.empty((qs.shape[0], hist.shape[0]), np.float64)
    grid = np.arange(n_bins, dtype=np.float64) / (n_bins - 1)
    for i, q in enumerate(qs):
        # Threshold floored above zero so q = 0 returns the MINIMUM (the
        # first bin with any mass), not the empty left tail of the cdf.
        thr = max(min(max(q, 0.0), 1.0) - 1e-12, 1e-300)
        idx = np.argmax(cdf >= thr, axis=1)
        out[i] = grid[idx]
    return out


def finalize(state: StreamStats, confidence: float = DEFAULT_CONFIDENCE,
             ci: str = "wilson") -> dict:
    """Weighted sums -> per-assignment statistics dict (host f64).

    Keys mirror ``dse.mc_statistics`` (``mean``/``std``/``worst``/
    ``yield``) and add ``best``, ``yield_lo``/``yield_hi`` (two-sided
    binomial bounds at ``confidence``, over the *effective* sample size),
    ``count``, ``n_eff`` and the interval config.  ``ci`` selects
    ``'wilson'`` (closed-form score interval) or ``'clopper-pearson'``
    (exact beta quantiles).
    """
    w = max(float(state.w_sum), 1e-300)
    w2 = max(float(state.w2_sum), 1e-300)
    count = float(state.count)
    n_eff = w * w / w2 if count > 0 else 0.0
    mean = np.asarray(state.mean, np.float64)
    var = np.maximum(np.asarray(state.m2, np.float64), 0.0) / w
    p = np.clip(np.asarray(state.exceed, np.float64) / w, 0.0, 1.0)
    if ci == "clopper-pearson":
        lo, hi = clopper_pearson_bounds(p, n_eff, confidence)
    elif ci == "wilson":
        lo, hi = wilson_bounds(p, n_eff, confidence)
    else:
        raise ValueError(f"unknown ci method {ci!r}; "
                         "use 'wilson' or 'clopper-pearson'")
    amin = np.asarray(state.amin, np.float64)
    amax = np.asarray(state.amax, np.float64)
    return {
        "mean": mean,
        "std": np.sqrt(var),
        "worst": np.where(np.isfinite(amin), amin, np.nan),
        "best": np.where(np.isfinite(amax), amax, np.nan),
        "yield": p,
        "yield_lo": lo,
        "yield_hi": hi,
        "count": count,
        "n_eff": n_eff,
        "confidence": float(confidence),
        "ci": ci,
    }


# ---------------------------------------------------------------------------
# Quasi-Monte-Carlo chunk samplers (host side)
# ---------------------------------------------------------------------------

#: scipy's Sobol direction-number table tops out at this dimension.
SOBOL_MAX_DIM = 21201


class QMCSampler:
    """Deterministic uniform chunks over the reduced mismatch space.

    ``method='sobol'``: scrambled Sobol', rebuilt per chunk and
    ``fast_forward``-ed to the chunk's global start index — draw ``v``
    depends only on ``v`` (chunk-size invariant, exactly like the
    ``fold_in``-keyed iid stream) and on the scramble seed derived from
    the stored jax key data.

    ``method='stratified'``: per-chunk Latin hypercube (each chunk is a
    stratified design on its own; the stream is deterministic in
    ``(key, chunk start)`` but NOT chunk-size invariant — documented
    trade-off for dimensions beyond the Sobol table).
    """

    def __init__(self, method: str, dim: int, key_data) -> None:
        if method not in ("sobol", "stratified"):
            raise ValueError(f"unknown QMC method {method!r}")
        if dim <= 0:
            raise ValueError("QMC sampling needs at least one mismatch dim")
        if method == "sobol" and dim > SOBOL_MAX_DIM:
            raise ValueError(
                f"mismatch space has {dim} dims > Sobol table limit "
                f"{SOBOL_MAX_DIM}; use method='stratified' or 'iid'")
        self.method = method
        self.dim = int(dim)
        kd = np.asarray(key_data, np.uint32).ravel()
        # Fold the key words into one 63-bit scramble seed.
        seed = 0
        for word in kd.tolist():
            seed = (seed * 1000003 + int(word)) % (2 ** 63 - 1)
        self.seed = int(seed)

    def chunk(self, start: int, size: int) -> np.ndarray:
        """Uniform ``(size, dim)`` f32 draws for global variants
        ``start .. start + size - 1``."""
        from scipy.stats import qmc

        if self.method == "sobol":
            eng = qmc.Sobol(d=self.dim, scramble=True, seed=self.seed)
            if start:
                eng.fast_forward(int(start))
            u = eng.random(int(size))
        else:
            eng = qmc.LatinHypercube(
                d=self.dim, seed=self.seed + 2 * int(start) + 1)
            u = eng.random(int(size))
        return np.asarray(u, np.float32)


def uniform_to_normal(u: jnp.ndarray) -> jnp.ndarray:
    """In-graph inverse-CDF transform, clipped away from {0, 1} so the
    tails stay finite in f32."""
    eps = jnp.float32(1e-7)
    return jax.scipy.special.ndtri(jnp.clip(u, eps, 1.0 - eps))
