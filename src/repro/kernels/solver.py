"""Fused Pallas training solver: lane-resident dual coordinate ascent with
on-the-fly Gram tiles (DESIGN.md §7).

Algorithm 1's compute is ``dual_coordinate_ascent_blocked`` swept over all
solver *lanes* — OvO pair x CV fold x (C, gamma) grid cell.  Under the
XLA vmap formulation every lane's (n_max, n_max) Gram matrix K' is
materialized in HBM and its row blocks are re-read once per block per
epoch, so the sweep is HBM-bound.  This kernel inverts the trade: the
grid iterates over lanes, each program keeps its lane's state — ``alpha``,
the (n_max, d) inputs, labels and C-box — resident in VMEM for the whole
epoch loop, and *recomputes* each (block, n_max) Gram row slab on the fly
from the inputs with the very same tile bodies the kernel-matrix grid
uses (``repro.kernels.rbf.tile_body``).  The (lanes, n_max, n_max) Gram
tensor is never materialized anywhere: O(n^2) HBM traffic per lane-epoch
becomes O(n*d) VMEM-resident FLOPs, a trade that favors compute-rich
hardware by orders of magnitude for the paper's d <= 32 workloads.

Update-sequence contract
------------------------
The coordinate update sequence is IDENTICAL to
``repro.core.trainer.dual_coordinate_ascent_blocked`` (the oracle): same
block visit order, fresh per-block margins from one GEMM against the
current alphas, Gauss-Seidel inside the block against the diagonal
(block, block) tile.  Only the Gram values' provenance differs (tile
recompute vs materialized matrix), so alphas agree to f32 round-off.
Masked samples (``c_box = 0``) remain exact no-ops, which keeps trailing
padding rows inert exactly as in the blocked solver.

Lane layout
-----------
``x (P, n, d)`` / ``y (P, n)`` are per-*pair*; ``gamma (P, G)`` spans the
width grid; ``c_box (P, L, n)`` spans the gamma-independent C x fold
lanes (the box already folds the train-mask and validity in).  The grid
is ``(P, G)``: one program per (pair, gamma) solves all L lanes at once,
since they share every Gram slab.  Inside it samples run along sublanes
and lanes along the vector lanes — ``c`` and ``alpha`` are (n, L) — so
each coordinate block is an aligned (block, L) sublane slab and every
block operand meets the TPU's (8, 128) tiling as a full trailing
dimension; gamma is read from SMEM.  Row-major iteration revisits the
same pair block for all its G programs, so the pair inputs stay hot.
Outputs are ``alpha (P, G, L, n)`` and the final margins ``f (P, G, L,
n)`` (``f_j = sum_i K'_ji alpha_i y_i``), computed by one extra fused
pass over the row slabs so CV validation never needs the Gram either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import PRECISION
from repro.kernels.rbf import tile_body

#: Coordinate-block size; matches ``repro.core.trainer.SOLVER_BLOCK``.
DEFAULT_BLOCK = 16


def _row(v, hit):
    """Row ``i`` of a (block, k) array as (1, k), selected by the one-hot
    sublane mask ``hit``: one nonzero term per sum, so the pick is exact."""
    return jnp.sum(jnp.where(hit, v, 0.0), axis=0, keepdims=True)


def _solver_kernel(g_ref, x_ref, y_ref, c_ref, alpha_ref, f_ref, *,
                   block: int, n_epochs: int, tile):
    """One (pair, gamma): every C x fold lane of it, VMEM-resident.

    Samples run along sublanes and lanes along the vector lanes, so each
    coordinate block is an aligned (block, L) sublane slab and the
    Gauss-Seidel step is one masked row update shared by all L lanes.
    """
    gamma = g_ref[pl.program_id(0), pl.program_id(1)]
    yv = y_ref[0]                     # (n_pad, 1)
    n_pad = yv.shape[0]
    n_blocks = n_pad // block
    sub = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def slab(j0):
        """Rows j0:j0+block of x, and the fresh Gram row slab
        K'[j0:j0+block, :] + bias with its (block, block) diagonal tile."""
        xb = x_ref[0, pl.ds(j0, block), :]
        rows = tile(xb, x_ref[0], gamma) + 1.0       # bias-as-feature
        return rows, tile(xb, xb, gamma) + 1.0

    def margins(rows):
        """(block, L) margins rows @ (alpha * y) from the current alphas."""
        return jax.lax.dot_general(
            rows, alpha_ref[0, 0] * yv, (((1,), (0,)), ((), ())),
            precision=PRECISION, preferred_element_type=jnp.float32)

    def block_body(b, carry):
        j0 = pl.multiple_of(b * block, block)
        rows, kbb = slab(j0)
        # The oracle's qdiag values: K'(x_i, x_i), same tile math.
        qb = jnp.clip(jnp.sum(jnp.where(sub == lane, kbb, 0.0), axis=1,
                              keepdims=True), 1e-12, None)  # (block, 1)
        yb = y_ref[0, pl.ds(j0, block), :]             # (block, 1)
        cb = c_ref[0, pl.ds(j0, block), :]             # (block, L)
        ab = alpha_ref[0, 0, pl.ds(j0, block), :]      # (block, L)
        fb = margins(rows)                             # (block, L)

        def coord(i, carry):
            ab, fb = carry
            hit = sub == i
            y_i, q_i = _row(yb, hit), _row(qb, hit)
            a_i = _row(ab, hit)
            g = 1.0 - y_i * _row(fb, hit)
            a_new = jnp.clip(a_i + g / q_i, 0.0, _row(cb, hit))
            dlt = a_new - a_i
            col = jnp.sum(jnp.where(lane == i, kbb, 0.0), axis=1,
                          keepdims=True)               # kbb[:, i]
            return jnp.where(hit, a_new, ab), fb + dlt * y_i * col

        ab, _ = jax.lax.fori_loop(0, block, coord, (ab, fb))
        alpha_ref[0, 0, pl.ds(j0, block), :] = ab
        return carry

    alpha_ref[...] = jnp.zeros(alpha_ref.shape, alpha_ref.dtype)
    for_blocks = functools.partial(jax.lax.fori_loop, 0, n_blocks)
    jax.lax.fori_loop(0, n_epochs,
                      lambda _, c: for_blocks(block_body, c), 0)

    # Final margins f = K' @ (alpha * y), one more fused pass over the
    # row slabs — CV validation consumes f directly, Gram-free.
    def final_block(b, carry):
        j0 = pl.multiple_of(b * block, block)
        f_ref[0, 0, pl.ds(j0, block), :] = margins(slab(j0)[0])
        return carry

    for_blocks(final_block, 0)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "n_epochs", "block", "interpret",
                     "n_slope", "v_t", "v_scale"),
)
def dual_ascent_lanes_pallas(
    x: jnp.ndarray,       # (P, n, d) per-pair inputs
    y: jnp.ndarray,       # (P, n) labels in {-1, +1}
    c_box: jnp.ndarray,   # (P, L, n) per-lane box (0 masks a sample out)
    gamma: jnp.ndarray,   # (P, G) kernel widths
    kind: str = "rbf",    # 'linear' | 'rbf' | 'sech2'
    n_epochs: int = 200,
    block: int = DEFAULT_BLOCK,
    interpret: bool = True,
    n_slope: float = 1.38,
    v_t: float = 0.02585,
    v_scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Solve every (pair, gamma, C-lane) in one fused grid.

    Returns ``(alpha, f)``, each ``(P, G, L, n)``.  ``v_scale`` defaults
    to 1.0 — feature-unit gamma, matching ``core.kernels.sech2_kernel``.
    """
    p, n, d = x.shape
    g_count = gamma.shape[1]
    l_count = c_box.shape[1]
    blk = int(min(block, n))
    # Sublane slabs start at multiples of the block: keep it a multiple of
    # 8 (the f32 sublane tile) so every slab is aligned.
    blk = -(-blk // 8) * 8
    n_pad = -(-n // blk) * blk
    if n_pad != n:
        # Padding rows are inert: zero box ==> alpha frozen at 0 ==> they
        # contribute exact zeros to every margin contraction.
        x = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, n_pad - n)), constant_values=1.0)
        c_box = jnp.pad(c_box, ((0, 0), (0, 0), (0, n_pad - n)))
    tile = tile_body(kind, n_slope=n_slope, v_t=v_t, v_scale=v_scale)
    body = functools.partial(_solver_kernel, block=blk,
                             n_epochs=int(n_epochs), tile=tile)
    out_shape = jax.ShapeDtypeStruct((p, g_count, n_pad, l_count),
                                     jnp.float32)
    out_spec = pl.BlockSpec((1, 1, n_pad, l_count),
                            lambda i, j: (i, j, 0, 0))
    alpha, f = pl.pallas_call(
        body,
        grid=(p, g_count),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # gamma (P, G)
            pl.BlockSpec((1, n_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, n_pad, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, n_pad, l_count), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
    )(jnp.asarray(gamma, jnp.float32), x, y[..., None],
      jnp.swapaxes(c_box, 1, 2))
    alpha = jnp.swapaxes(alpha, 2, 3)[..., :n]
    f = jnp.swapaxes(f, 2, 3)[..., :n]
    return alpha, f
