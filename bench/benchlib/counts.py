"""Operations and bytes the algorithm needs, from a cell's shapes.

A kernel's roofline share is ``max(ops / peak FLOP/s, bytes / peak B/s)``
over its measured device time.  The counts here are of the work the
algorithm *needs*, whatever implements it: padding, candidates the result
does not use and repeated passes are not charged.  A later change that
stops computing them raises the share honestly, and cannot push it past
100 %.

Only floating-point operations are counted as ops (integer index and
random-bit arithmetic is not).  Bytes are what a perfect kernel would have
to move through HBM: its inputs once and its outputs once.
"""
from __future__ import annotations

F32 = 4

# -- analog Monte-Carlo signoff --------------------------------------------
#
# One analog pair with ``m`` support-vector cells over ``d`` inputs, per
# variant and validation row (DESIGN.md §6.2, the measured-transfer model):
#   per cell and input:  dv = s*(x - sv) + mu - shift      4 ops
#                        uniform-grid interp: index and
#                        fraction (2), lerp (3)            5 ops
#                        times the cell's gain              1 op
#                        product over inputs                1 op
#   per cell:            rail multiply-accumulate           2 ops
#   per pair and row:    rails, bias, comparator offset     4 ops
# Per variant and analog cell, independent of the rows:
#   per cell and input:  bell shift (3), differential offset (3),
#                        peak gain (8), mirror and V_DS (4)  18 ops
#                        turning 4 uniform draws into
#                        normals (inverse CDF, ~20 each)    4 x 20 ops
#   per cell:            alpha shift/slope (4), realised
#                        alpha interp (5), 2 draws (2 x 20) 49 ops
# A digital linear pair per row: 4-bit input quantisation is shared by
# all pairs (4 ops per input, once per row); the dot product and bias
# are 2d + 1 ops.  Votes recombination per variant and row: select,
# two vote adds per pair (3P), argmax over K, label compare (1).  Fold
# per variant: Welford update, extrema, floor test, histogram (~12).
CELL_INPUT_OPS = 4 + 5 + 1 + 1
CELL_OPS = 2
PAIR_ROW_OPS = 4
DRAW_INPUT_OPS = 18 + 4 * 20
DRAW_CELL_OPS = 4 + 5 + 2 * 20
FOLD_OPS = 12


def signoff_step(kernel_map, m_true, d: int, n_rows: int, n_variants: int,
                 n_classes: int) -> tuple[float, float]:
    """(ops, bytes) of scoring ``n_variants`` variants of ONE assignment
    over ``n_rows`` rows and folding them.

    ``kernel_map[p]`` is ``"rbf"`` where pair ``p`` is analog in the
    signed-off assignment, ``m_true[p]`` that pair's analog support count
    after pruning.  Pairs deployed linear contribute nothing through
    ``m_true``: their analog candidates are not part of this result.
    """
    n_pairs = len(kernel_map)
    ops = 0.0
    byt = 0.0
    for p, kind in enumerate(kernel_map):
        if kind == "rbf":
            m = int(m_true[p])
            ops += n_variants * n_rows * (m * d * CELL_INPUT_OPS
                                          + m * CELL_OPS + PAIR_ROW_OPS)
            ops += n_variants * m * (d * DRAW_INPUT_OPS + DRAW_CELL_OPS)
            # support vectors, alpha control voltages and rail masks
            byt += m * (d + 2) * F32
        else:
            ops += n_variants * n_rows * (2 * d + 1)
            byt += (d + 1) * F32
    ops += n_variants * n_rows * 4 * d
    ops += n_variants * n_rows * (3 * n_pairs + n_classes + 1)
    ops += n_variants * FOLD_OPS
    # rows, labels, two measured curves of 257 points, the fold state
    byt += n_rows * (d + 1) * F32 + 2 * 2 * 257 * F32 + 1024 * F32
    return ops, byt


def roofline_share(ops: float, byt: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Percent of the chip's roofline that ``ops``/``byt`` in ``seconds``
    reach, and which bound (``compute`` or ``memory``) it is under."""
    t_c = ops / peaks["flops_per_s"]
    t_m = byt / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, \
        ("compute" if t_c >= t_m else "memory")
