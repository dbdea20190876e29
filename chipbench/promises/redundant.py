"""What the redundant butterfly promises under fail-stop deaths, derived
from the deaths alone, without the program's plan.

``valid(p, deaths)``: the ranks that end one reduction with a correct R.
``deaths`` maps a rank to the exchange at whose entry it dies.  At
exchange k every rank swaps its R with rank ``i ^ 2**k`` and combines the
two; a rank that is dead, or that receives from a dead or invalid
partner, holds no correct R from then on.

``ranks_with_r(valid, blocked)``: the ranks whose R is compared.  A
single-panel TSQR promises R on its valid ranks; the blocked driver
promises it on every rank, the dead ones restored from replicas, and
reports as valid the ranks that no death reached.
"""
import numpy as np


def valid(p: int, deaths: dict) -> np.ndarray:
    ok = np.ones(p, bool)
    for k in range(p.bit_length() - 1):
        ok &= np.array([deaths.get(i, k + 1) > k for i in range(p)])
        ok = ok & ok[np.arange(p) ^ (1 << k)]
    return ok


def ranks_with_r(ok: np.ndarray, blocked: bool) -> np.ndarray:
    return np.arange(len(ok)) if blocked else np.flatnonzero(ok)
