"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

These are the semantic ground truth: simple, obviously-correct
implementations used by tests (assert_allclose vs the kernels in
interpret mode) and as the fallback compute path on platforms without
Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.kernels import PRECISION


# ---------------------------------------------------------------------------
# RBF kernel matrix (the paper's hot loop)
# ---------------------------------------------------------------------------


def rbf_matrix(x: jnp.ndarray, z: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """K[i, j] = exp(-gamma * ||x_i - z_j||^2);  x: (n, d), z: (m, d)."""
    d2 = (
        jnp.sum(x * x, -1)[:, None]
        + jnp.sum(z * z, -1)[None, :]
        - 2.0 * jnp.matmul(x, z.T, precision=PRECISION)
    )
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def sech2_matrix(
    x: jnp.ndarray, z: jnp.ndarray, gamma: float,
    n_slope: float = 1.38, v_t: float = 0.02585, v_scale: float = 0.5,
) -> jnp.ndarray:
    """Hardware separable kernel (Eq. 6): product of per-dim sech2 cells."""
    gamma0 = 1.0 / (4.0 * n_slope**2 * v_t**2) * v_scale**2
    s = jnp.sqrt(gamma / gamma0)
    dv = v_scale * s * (x[:, None, :] - z[None, :, :]) / (n_slope * v_t)
    cell = 4.0 / ((1.0 + jnp.exp(-dv)) * (1.0 + jnp.exp(dv)))
    return jnp.prod(cell, axis=-1)


# ---------------------------------------------------------------------------
# Dual coordinate ascent over solver lanes (the training hot loop)
# ---------------------------------------------------------------------------


def dual_ascent_blocked(
    kp: jnp.ndarray,      # (n, n) Gram WITH bias folded in (K + 1)
    y: jnp.ndarray,       # (n,) labels in {-1, +1}
    c_box: jnp.ndarray,   # (n,) per-sample box (0 masks a sample out)
    n_epochs: int,
    block: int = 16,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked Gauss-Seidel dual ascent on a MATERIALIZED Gram matrix.

    The semantic twin of the fused Pallas solver
    (``repro.kernels.solver.dual_ascent_lanes_pallas``): the coordinate
    update sequence is identical to
    ``repro.core.trainer.dual_coordinate_ascent_blocked`` — same block
    visit order, fresh per-block margins via one GEMM — which stays the
    oracle of record.  Returns ``(alpha, f)`` with the final margins
    ``f = K' @ (alpha * y)`` appended (the Pallas kernel emits both).
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
        rows = jax.lax.dynamic_slice(kp, (j0, 0), (block, n_pad))
        kbb = jax.lax.dynamic_slice(rows, (0, j0), (block, block))
        yb = jax.lax.dynamic_slice(y, (j0,), (block,))
        cb = jax.lax.dynamic_slice(c_box, (j0,), (block,))
        qb = jax.lax.dynamic_slice(qdiag, (j0,), (block,))
        ab = jax.lax.dynamic_slice(alpha, (j0,), (block,))
        fb = jnp.matmul(rows, alpha * y, precision=PRECISION)

        def coord(i, carry):
            ab, fb = carry
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
    f = jnp.matmul(kp, alpha * y, precision=PRECISION)
    return alpha[:n], f[:n]


def solve_lanes(
    x: jnp.ndarray,       # (P, n, d) per-pair inputs
    y: jnp.ndarray,       # (P, n)
    c_box: jnp.ndarray,   # (P, L, n) gamma-independent box lanes
    gamma: jnp.ndarray,   # (P, G)
    kind: str = "rbf",
    n_epochs: int = 200,
    block: int = 16,
    n_slope: float = 1.38,
    v_t: float = 0.02585,
    v_scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-jnp lanes oracle: materialized per-(pair, gamma) Gram + the
    blocked update sequence, vmapped over (P, G, L).  Returns ``(alpha,
    f)``, each (P, G, L, n) — exactly the fused solver's outputs, with
    the Gram built once per (pair, gamma) and shared across the C x fold
    lanes that close over it (the XLA baseline the Pallas kernel trades
    HBM traffic against)."""

    def kmat(xp, g):
        if kind == "linear":
            k = jnp.matmul(xp, xp.T, precision=PRECISION)
        elif kind == "rbf":
            k = rbf_matrix(xp, xp, g)
        elif kind == "sech2":
            k = sech2_matrix(xp, xp, g, n_slope, v_t, v_scale)
        else:
            raise ValueError(f"no lanes oracle for kernel kind {kind!r}")
        return k + 1.0  # bias-as-feature

    def per_pair(xp, yp, cl, gg):
        def per_gamma(g):
            kp = kmat(xp, g)
            return jax.vmap(
                lambda cb: dual_ascent_blocked(kp, yp, cb, n_epochs, block)
            )(cl)
        return jax.vmap(per_gamma)(gg)

    return jax.vmap(per_pair)(x, y, c_box, gamma)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(
    q: jnp.ndarray,          # (b, hq, sq, dh)
    k: jnp.ndarray,          # (b, hkv, skv, dh)
    v: jnp.ndarray,          # (b, hkv, skv, dh)
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Plain GQA attention with optional causal/sliding-window masking.

    ``q_offset`` positions the query block within the kv sequence (for
    decode: sq == 1, q_offset == cache length - 1).
    """
    b, hq, sq, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, dh)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) / jnp.sqrt(float(dh))
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(b, hq, sq, dh)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality), chunk-free sequential reference
# ---------------------------------------------------------------------------


def ssd(
    x: jnp.ndarray,      # (b, s, h, dh)     inputs (already gated/projected)
    a: jnp.ndarray,      # (b, s, h)         log-decay per step (a = -softplus)
    bmat: jnp.ndarray,   # (b, s, g, ds)     input->state projection ("B")
    cmat: jnp.ndarray,   # (b, s, g, ds)     state->output projection ("C")
    init_state: jnp.ndarray | None = None,  # (b, h, dh, ds)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential scan reference of SSD:  S_t = exp(a_t) S_{t-1} + x_t B_t^T.

    Heads h are grouped over state groups g (h % g == 0), mirroring GQA.
    Returns (y, final_state) with y: (b, s, h, dh).
    """
    b, s, h, dh = x.shape
    g = bmat.shape[2]
    rep = h // g
    bm = jnp.repeat(bmat, rep, axis=2)  # (b, s, h, ds)
    cm = jnp.repeat(cmat, rep, axis=2)
    ds = bm.shape[-1]
    s0 = init_state if init_state is not None else jnp.zeros((b, h, dh, ds), x.dtype)

    def step(state, t):
        xt, at, bt, ct = t
        state = jnp.exp(at)[..., None, None] * state + xt[..., None] * bt[:, :, None, :]
        yt = jnp.einsum("bhds,bhs->bhd", state, ct)
        return state, yt

    xs = (
        jnp.moveaxis(x, 1, 0), jnp.moveaxis(a, 1, 0),
        jnp.moveaxis(bm, 1, 0), jnp.moveaxis(cm, 1, 0),
    )
    final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), final
