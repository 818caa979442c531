"""Pallas TPU kernel for the RBF kernel-matrix hot loop (DESIGN.md §3.1).

The paper's analog circuit evaluates K(x, s) = exp(-gamma ||x - s||^2) one
support vector at a time via cascaded current-mode cells.  On TPU the same
separable kernel is restructured so the dominant term is an MXU matmul:

    ||x - z||^2 = ||x||^2 + ||z||^2 - 2 x . z

An (bm x bn) tile of K plus its (bm x d) / (bn x d) operand slabs live in
VMEM; the exp (VPU) fuses into the same kernel so K never round-trips to
HBM between the distance and the nonlinearity.  The hardware sech2 variant
(`sech2_mm`) evaluates the cascaded-pair transfer exactly (Eq. 4) in
log-space, accumulated across dimensions (Eq. 6) — blocking replaces the
analog current chain.

Grid: (n/bm, m/bn); each program writes one output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import PRECISION


def linear_tile(x: jnp.ndarray, z: jnp.ndarray, gamma=None) -> jnp.ndarray:
    """One (bm, bn) linear-kernel tile x @ z.T on the MXU; gamma ignored."""
    return jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32
    )


def rbf_tile(x: jnp.ndarray, z: jnp.ndarray, gamma) -> jnp.ndarray:
    """One (bm, bn) RBF tile: distance via MXU matmul + fused exp.

    Pure-value tile body shared by the kernel-matrix grid below and the
    fused training solver (``repro.kernels.solver``), which evaluates Gram
    tiles on the fly instead of materializing the full matrix.
    """
    xx = jnp.sum(x * x, axis=-1, keepdims=True)          # (bm, 1)
    zz = jnp.sum(z * z, axis=-1, keepdims=True).T        # (1, bn)
    xz = linear_tile(x, z)                               # MXU
    d2 = jnp.maximum(xx + zz - 2.0 * xz, 0.0)
    return jnp.exp(-gamma * d2)


def sech2_tile(x: jnp.ndarray, z: jnp.ndarray, gamma, *,
               n_slope: float, v_t: float, v_scale: float) -> jnp.ndarray:
    """One (bm, bn) tile of the hardware kernel: log-space product (Eq. 6)."""
    gamma0 = 1.0 / (4.0 * n_slope**2 * v_t**2) * v_scale**2
    s = jnp.sqrt(gamma / gamma0) * v_scale / (n_slope * v_t)
    acc = jnp.zeros((x.shape[0], z.shape[0]), jnp.float32)
    for k in range(x.shape[1]):  # d <= 5 in the paper's hardware; unrolled
        dv = (x[:, k:k + 1] - z[:, k:k + 1].T) * s
        # log cell = log 4 - log(1+e^-dv) - log(1+e^dv); stable softplus form
        acc += jnp.log(4.0) - jax.nn.softplus(-dv) - jax.nn.softplus(dv)
    return jnp.exp(acc)


def tile_body(kind: str, n_slope: float = 1.38, v_t: float = 0.02585,
              v_scale: float = 1.0):
    """Resolve a pure-value tile body ``(x, z, gamma) -> (bm, bn)``.

    The shared dispatch for every consumer of the fused tile math: the
    kernel-matrix grid here and the dual-ascent solver grid
    (``repro.kernels.solver``).  Note the v_scale default of 1.0 matches
    ``core.kernels.sech2_kernel`` (feature-unit gamma); the kernel-matrix
    entry point below keeps its historical 0.5 default.
    """
    if kind == "linear":
        return linear_tile
    if kind == "rbf":
        return rbf_tile
    if kind == "sech2":
        return functools.partial(sech2_tile, n_slope=n_slope, v_t=v_t,
                                 v_scale=v_scale)
    raise ValueError(f"no tile body for kernel kind {kind!r}")


def _rbf_kernel(x_ref, z_ref, g_ref, o_ref):
    """One (bm, bn) tile: distance via MXU matmul + fused exp."""
    x = x_ref[...].astype(jnp.float32)          # (bm, d)
    z = z_ref[...].astype(jnp.float32)          # (bn, d)
    o_ref[...] = rbf_tile(x, z, g_ref[0, 0]).astype(o_ref.dtype)


def _sech2_kernel(x_ref, z_ref, g_ref, o_ref, *,
                  n_slope: float, v_t: float, v_scale: float):
    """One (bm, bn) tile of the hardware kernel: log-space product (Eq. 6)."""
    x = x_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    o_ref[...] = sech2_tile(x, z, g_ref[0, 0], n_slope=n_slope, v_t=v_t,
                            v_scale=v_scale).astype(o_ref.dtype)


def _pad_to(a: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "bm", "bn", "interpret", "n_slope", "v_t", "v_scale"),
)
def kernel_matrix_pallas(
    x: jnp.ndarray,           # (n, d)
    z: jnp.ndarray,           # (m, d)
    gamma,
    kind: str = "rbf",        # 'rbf' | 'sech2'
    bm: int = 128,
    bn: int = 128,
    interpret: bool = True,
    n_slope: float = 1.38,
    v_t: float = 0.02585,
    v_scale: float = 0.5,
) -> jnp.ndarray:
    """Tiled kernel matrix K: (n, m).  Pads to block multiples, slices back."""
    n, d = x.shape
    m = z.shape[0]
    xp = _pad_to(x, bm, 0)
    zp = _pad_to(z, bn, 0)
    g = jnp.reshape(jnp.asarray(gamma, jnp.float32), (1, 1))
    grid = (xp.shape[0] // bm, zp.shape[0] // bn)

    if kind == "rbf":
        body = _rbf_kernel
    elif kind == "sech2":
        body = functools.partial(
            _sech2_kernel, n_slope=n_slope, v_t=v_t, v_scale=v_scale
        )
    else:
        raise ValueError(kind)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # gamma: a (1, 1) scalar
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], zp.shape[0]), jnp.float32),
        interpret=interpret,
    )(xp, zp, g)
    return out[:n, :m]
