"""The work of the comb-direct histogram kernel (``lgbm_hist``), from
its shapes, for a roofline share: the MXU operations and the HBM bytes
of ``rows`` row visits.

The kernel is a nibble one-hot contraction (``ops/pallas/hist_kernel2``
of the program): per row and feature group one ``[M, N]`` product of
``M = g x b / 16`` bin-high lanes by ``N = g x 16 x 2`` bin-low x
channel lanes, ``g`` features a group (``128 // (b / 16)``, at most 16).
A comb past two planes' worth of groups is swept in tiles of one
128-lane plane: a tile reads its own plane and the one the values lie
in, ``128 / g`` groups each.  Counted here: the contraction's
``2 x M x N`` per row and group, and 512 B a plane read per row - what
the algorithm needs, not the one-hot expansions, the masked rows of a
block or the garbage groups past the last column, which are the
kernel's own overheads.
"""
from __future__ import annotations

LANE = 128
PLANE_BYTES = LANE * 4


def group_geometry(padded_bins: int):
    """(g, M, N) of the accumulator at ``padded_bins`` bins a column."""
    b_hi = max(int(padded_bins) // 16, 1)
    g = max(min(LANE // b_hi, 16), 1)
    return g, g * b_hi, g * 16 * 2


def hist_flops(rows: float, tiles: int, padded_bins: int) -> float:
    """MXU operations of ``rows`` row visits of a histogram swept in
    ``tiles`` one-plane tiles."""
    g, m, n = group_geometry(padded_bins)
    return 2.0 * rows * tiles * (LANE // g) * m * n


def hist_bytes(rows: float, tiles: int) -> float:
    """HBM bytes the same visits read: two planes a tile."""
    return float(rows) * 2 * tiles * PLANE_BYTES
