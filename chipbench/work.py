"""The work of one QR factorization, from its shape alone.

Whatever computes the R factor (Householder, CholeskyQR2, Pallas or XLA),
the factorization of an m x n matrix needs at least the operations of a
Householder QR that forms R only, 2mn^2 - 2n^3/3, and has to read A once
and write R once.  Divided over the chips of a cell, that gives the least
time the cell's chips could take: the larger of operations over peak
operations per second and bytes over peak memory bandwidth.
"""
from __future__ import annotations

import dataclasses

from chipbench.peaks import peaks


def qr_flops(m: int, n: int) -> float:
    """Operations of a Householder QR that forms R only."""
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0


def qr_bytes(m: int, n: int, itemsize: int, chips: int = 1) -> float:
    """Bytes one chip moves at least: its share of A read once, R written."""
    return (m * n / chips + n * n) * itemsize


@dataclasses.dataclass(frozen=True)
class LeastTime:
    """The least time of one factorization on one chip of the cell."""

    compute_s: float
    memory_s: float

    @property
    def seconds(self) -> float:
        return max(self.compute_s, self.memory_s)

    @property
    def bound(self) -> str:
        return "memory" if self.memory_s >= self.compute_s else "compute"


def least_time(m: int, n: int, itemsize: int, chips: int, device_kind: str) -> LeastTime:
    """Least time of the QR of an m x n matrix split over ``chips``."""
    pk = peaks(device_kind)
    return LeastTime(
        compute_s=qr_flops(m, n) / chips / pk["flops_per_s"],
        memory_s=qr_bytes(m, n, itemsize, chips) / pk["hbm_bytes_per_s"],
    )
