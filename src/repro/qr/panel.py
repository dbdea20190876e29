"""Engine-agnostic panel factorization for the QR pipeline layer.

This is the panel-local half of the fault-tolerant TSQR, extracted from
``repro.core.tsqr`` so that both QR workloads share it:

  * the tall-and-skinny entry points (:mod:`repro.qr.tsqr`) factor one
    panel — the whole matrix;
  * the right-looking blocked driver (:mod:`repro.qr.blocked`) factors one
    panel per column block of a general m×n matrix.

A :class:`PanelFactorizer` bundles the two panel-local policies — which
local QR runs before the butterfly (``local_qr``) and how many
CholeskyQR-style re-orthonormalization passes polish the explicit Q
(``reorth``) — and exposes them against the generic collective engine:
``reduce_r`` runs any :class:`~repro.collective.plan.Plan` with the QR
combiner on any :class:`~repro.collective.comm.Comm` backend, so the same
factorizer executes on ``SimComm`` and ``ShardMapComm`` under every fault
variant.  Nothing here knows about meshes, fault specs, or column blocking.

The combine is ``QR([R_lo; R_hi])`` ordered by the level bit of the *block*
index so every member of a block computes an identical R (making the
butterfly a true all-reduce — every survivor ends with the same final R,
which lets Q be formed locally as ``A R⁻¹`` without a backward tree pass).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp

from repro.collective.combiners import (
    QRCombiner,
    StackedCombiner,
    SumCombiner,
    posdiag as _posdiag,
    qr_r,
)
from repro.collective.comm import Comm
from repro.collective.engine import execute_plan, ft_allreduce
from repro.collective.plan import Plan
from repro.kernels import dispatch as _dispatch
from repro.kernels.backend import F32_PRECISION

__all__ = [
    "CQR2PallasR",
    "FUSED_PANEL_COMBINER",
    "PanelFactorizer",
    "chol_r",
    "form_q",
    "local_qr_fns",
    "resolve_local_qr",
]


# ---------------------------------------------------------------------------
# Local QR building blocks
# ---------------------------------------------------------------------------

def qr_r_jnp(a):
    """Householder QR, R factor only (LAPACK on CPU, QR-decomp HLO on TPU)."""
    return qr_r(a)


def qr_r_cqr2(a):
    """CholeskyQR2 R factor — the MXU-native local QR (see kernels/).

    Rides the fused 2-sweep R-only pipeline: the butterfly only carries R,
    so no tall intermediate is ever materialized (the seed computed the full
    4-sweep factorization and discarded Q).
    """
    from repro.kernels import ops as kops

    return kops.cholesky_qr2_r(a)


@dataclasses.dataclass(frozen=True)
class CQR2PallasR:
    """CholeskyQR2 R factor on the Pallas kernels.  ``interpret`` reaches
    every ``pl.pallas_call`` (``None`` auto-detects the backend).  A frozen
    dataclass, so two equal instances hash alike and share jit caches."""

    interpret: bool | None = None

    def __call__(self, a):
        from repro.kernels import ops as kops

        return kops.cholesky_qr2_r(a, use_pallas=True, interpret=self.interpret)


qr_r_cqr2_pallas = CQR2PallasR()

local_qr_fns: dict[str, Callable] = {
    "jnp": qr_r_jnp,
    "cqr2": qr_r_cqr2,
    "cqr2_pallas": qr_r_cqr2_pallas,
}


def resolve_local_qr(local_qr: str | Callable,
                     interpret: bool | None = None) -> Callable:
    """The local QR callable for ``local_qr``; ``interpret`` is threaded to
    the Pallas kernels of ``"cqr2_pallas"``."""
    if local_qr == "cqr2_pallas":
        return CQR2PallasR(interpret)
    return local_qr_fns[local_qr] if isinstance(local_qr, str) else local_qr


def chol_r(g):
    """Upper-triangular R from a panel Gram matrix (CholeskyQR local R).

    The blocked driver's zero-extra-sweep local factorization: the panel's
    Gram arrives for free from the previous trailing update's lookahead
    accumulator, so the local R costs one (b, b) Cholesky and no panel read.
    κ(panel)² enters the Gram — certified for κ ≲ 1/√ε like CholeskyQR.
    """
    return _posdiag(jnp.swapaxes(jnp.linalg.cholesky(g), -1, -2))


def _identity(x):
    return x


# The blocked driver's one-butterfly-per-panel payload (DESIGN.md §10):
# leaf 0 is the panel's local R (QR combine), leaf 1 the local cross
# products A_panelᵀ A_trail (sum combine).  Module-level so every jit/LRU
# cache keyed on the combiner shares one hashable instance.
FUSED_PANEL_COMBINER = StackedCombiner(
    (QRCombiner(local_qr=_identity), SumCombiner())
)


# ---------------------------------------------------------------------------
# Q formation (QR-specific; the reduction rides the generic engine)
# ---------------------------------------------------------------------------

def form_q(a_blocks, r, comm: Comm, reorth: int = 1):
    """Q = A·R⁻¹ locally (every survivor holds the same final R), followed by
    ``reorth`` CholeskyQR-style re-orthonormalization passes whose Gram
    reduction rides the fault-tolerant butterfly (``gram_sum`` combiner).

    Returns ``(q, r)`` with ``r`` updated so ``Q = A·r⁻¹`` still holds after
    the polish passes.  Requires every rank to hold a correct ``r`` (an
    all-valid plan, or replicas fetched first): Q spans *all* row-blocks, so
    a permanently-lost block makes the global Q undefined.
    """
    import jax.scipy.linalg as jsl

    def solve_r(q_in, rr):
        # q = a @ rr^{-1}  ==  solve rr^T y = a^T  (rr upper → rr^T lower)
        y = jsl.solve_triangular(
            jnp.swapaxes(rr, -1, -2), jnp.swapaxes(q_in, -1, -2), lower=True
        )
        return jnp.swapaxes(y, -1, -2)

    with _dispatch.span(_dispatch.FORM_Q, reorth=reorth):
        q = solve_r(a_blocks, r)
        for _ in range(reorth):
            g = jnp.matmul(jnp.swapaxes(q, -1, -2), q,
                           precision=F32_PRECISION)
            g_sum, _ = ft_allreduce(g, comm, op="gram_sum")
            r2 = _posdiag(jnp.swapaxes(jnp.linalg.cholesky(g_sum), -1, -2))
            q = solve_r(q, r2)
            r = _posdiag(jnp.matmul(r2, r, precision=F32_PRECISION))
        return q, r


# ---------------------------------------------------------------------------
# The factorizer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PanelFactorizer:
    """Panel-local policy bundle: local QR choice + reorthogonalization.

    ``local_qr`` — key into :data:`local_qr_fns` or a callable mapping a
    (…, m, n) panel to its (…, n, n) R factor; runs as the butterfly's
    ``prepare`` step.  ``reorth`` — CholeskyQR polish passes in
    :meth:`form_q` (each one Gram all-reduce over the same butterfly).
    ``interpret`` — the Pallas backend flag of a ``"cqr2_pallas"`` local QR.
    """

    local_qr: str | Callable = "jnp"
    reorth: int = 1
    interpret: bool | None = None

    def local_fn(self) -> Callable:
        return resolve_local_qr(self.local_qr, self.interpret)

    def combiner(self) -> QRCombiner:
        return QRCombiner(self.local_fn())

    def reduce_r(self, a_panel, comm: Comm, plan: Plan, *, fast=None):
        """Butterfly-reduce the panel to its global R: local QR (the QR
        combiner's ``prepare``, run here so that its span is its own) then
        ``QR([R_lo; R_hi])`` per level.  Returns ``(r, valid)``."""
        with _dispatch.span(_dispatch.LOCAL_R, ranks=comm.n_ranks,
                            rows=a_panel.shape[-2], cols=a_panel.shape[-1]):
            r_local = self.combiner().tree_prepare(a_panel)
        return self.reduce_r_prepared(r_local, comm, plan, fast=fast)

    def reduce_r_prepared(self, r_local, comm: Comm, plan: Plan, *, fast=None):
        """Same reduction, but the local R factors are already computed
        (the blocked driver derives them from the lookahead Gram)."""
        return execute_plan(
            r_local, comm, plan, QRCombiner(local_qr=_identity), fast=fast
        )

    def reduce_panel_fused(
        self, r_local, c_local, comm: Comm, plan: Plan, *, fast=None
    ):
        """ONE butterfly for both panel results: the stacked
        ``(R, Σ AᵖᵀAᵗ)`` payload rides a single plan — ``log P`` rounds
        instead of the ``2·log P`` of two serialized butterflies, and the
        replica copies of the stacked tuple double as fault-tolerance
        copies for *both* leaves.  Returns ``((r, c_sum), valid)``;
        per-leaf bit-identical to :meth:`reduce_r_prepared` followed by the
        ``sum`` all-reduce over the same plan (same combine order, same
        exchanges — only the messages are batched)."""
        return execute_plan(
            (r_local, c_local), comm, plan, FUSED_PANEL_COMBINER, fast=fast
        )

    def form_q(self, a_panel, r, comm: Comm):
        return form_q(a_panel, r, comm, self.reorth)
