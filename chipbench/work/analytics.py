"""Operations and needed bytes of the paper's statistics kernels per row.

Needed bytes are the row as the data holds it: ``d`` float32 features and
one float32 target or int32 label, ``(d + 1) * 4`` bytes, never the
128-lane padded layout a kernel may read.
"""
from __future__ import annotations


def row_bytes(d: int) -> int:
    return (d + 1) * 4


def linreg_stats(d: int, rows: int) -> tuple[float, float]:
    """Z^T Z of Z = [X | y]: (operations, bytes)."""
    return 2.0 * (d + 1) ** 2 * rows, float(row_bytes(d) * rows)


def nb_stats(d: int, rows: int) -> tuple[float, float]:
    """Per-class count, sum and sum of squares: one add per count, an add
    per sum and a multiply-add per square, per feature."""
    return (1.0 + 3.0 * d) * rows, float(row_bytes(d) * rows)


def logreg_sgd(d: int, rows: int) -> tuple[float, float]:
    """One SGD epoch: x.w, the sigmoid's error and the gradient x^T g per
    row (2d + 2d multiply-adds, counted as 4d + 4 operations)."""
    return (4.0 * d + 4.0) * rows, float(row_bytes(d) * rows)


KERNELS = {"linreg": linreg_stats, "gaussian_nb": nb_stats,
           "logreg": logreg_sgd}
