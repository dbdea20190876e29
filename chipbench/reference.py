"""The plain reference the benchmark judges the QR engine against.

Nothing here imports the program.  The R factor of a matrix is unique up
to the signs of its rows, so both sides are scaled to a non-negative
diagonal before they are compared.

  * :func:`reference_r` is R of the whole matrix in float64: the Cholesky
    factor of its Gram matrix AᵀA.  Its error is about κ(A)² · 2^-53 of
    R's size, under 1e-12 for the matrices of the benchmark (a standard
    normal 2^20 x 100 matrix has κ ≈ 1.02; it agreed with numpy's
    Householder QR to 1.1e-15 at 2^18 x 100), and LAPACK's Householder
    QR of a 2^20-row matrix took several times as long.
  * :func:`rel_err` is the normwise error max|R - R_ref| / max(1, max|R_ref|):
    a backward-stable QR promises its error relative to the size of A, and
    at 2^20 rows the diagonal of R reaches ~1e3, where one float32 ulp is
    already 1.2e-4, so an elementwise measure would ask for a few ulps of
    the largest entry.

What each variant promises of the ranks' validity is in
``chipbench/promises/<variant>.py``.
"""
from __future__ import annotations

import numpy as np


def posdiag(r: np.ndarray) -> np.ndarray:
    """Rows scaled so that the diagonal is non-negative."""
    s = np.where(np.diagonal(r) < 0, -1.0, 1.0)
    return r * s[:, None]


def reference_r(a: np.ndarray) -> np.ndarray:
    """R of the (m, n) matrix in float64, with a positive diagonal."""
    a = np.asarray(a, np.float64)
    return np.linalg.cholesky(a.T @ a).T


def rel_err(r, r_ref: np.ndarray) -> float:
    """Normwise error of one rank's R against the reference R."""
    r = posdiag(np.asarray(r, np.float64))
    return float(np.abs(r - r_ref).max() / max(1.0, np.abs(r_ref).max()))
