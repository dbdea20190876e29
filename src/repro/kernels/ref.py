"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's test sweeps shapes/dtypes and asserts allclose against the
function of the same name here.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.lax import optimization_barrier

from .backend import F32_PRECISION

__all__ = [
    "gram",
    "apply_right",
    "fused_apply_gram",
    "combine_gram",
    "cholesky_qr",
    "cholesky_qr2",
    "trailing_update",
    "panel_cross",
    "pad_cross",
]


def gram(a: jnp.ndarray) -> jnp.ndarray:
    """G = AᵀA accumulated in float32.  a: (..., m, n) → (..., n, n) f32."""
    a32 = a.astype(jnp.float32)
    return jnp.einsum("...mi,...mj->...ij", a32, a32, precision=F32_PRECISION)


def apply_right(a: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """A @ W with float32 accumulation, result in A's dtype.  w: (..., n, k)."""
    out = jnp.matmul(a.astype(jnp.float32), w.astype(jnp.float32),
                     precision=F32_PRECISION)
    return out.astype(a.dtype)


def fused_apply_gram(
    a: jnp.ndarray, w: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the fused kernel: Q = A @ W and G' = QᵀQ of the *stored*
    (cast) Q — the rounding a materialized panel would carry."""
    q = apply_right(a, w)
    return q, gram(q)


# XLA CPU lowers dots with an output dimension this narrow to mat-vec
# strategies whose accumulation order differs from the blocked GEMM used at
# wider shapes.  The blocked-QR drivers need *width-stable* per-element
# results (the fixed-shape pipeline computes at the padded maximal width,
# the eager driver at the true shrinking width — bit-identity between them
# is hypothesis-gated), so the two trailing-path oracles below pad narrow
# operands with zero columns up to this floor and slice the result back:
# values are unchanged, but every shape rides the same GEMM strategy.  The
# ``optimization_barrier`` keeps XLA's algebraic simplifier from folding
# the slice back into the dot (restoring the narrow strategy) when the
# oracle is traced into a larger program such as the scan pipeline.
_MIN_GEMM_WIDTH = 4


def min_gemm_width() -> int:
    """The effective GEMM-width floor: the static minimum above, raised (never
    lowered) by an installed autotune winner's ``gemm_width_floor``.  The
    tuner may prefer a wider pad when the roofline prior says the extra
    zero-column FLOPs are cheaper than the narrow-dot strategy switch; it can
    never go below :data:`_MIN_GEMM_WIDTH` — that floor is a bit-identity
    contract, not a tuning knob.  Consulted at trace time: a table installed
    *after* an oracle is traced does not rewrite the compiled program (the
    drivers' compile keys pin the config they were built with)."""
    from . import autotune as _autotune

    floors = [
        e.get("gemm_width_floor", _MIN_GEMM_WIDTH)
        for e in _autotune.installed().values()
    ]
    return max([_MIN_GEMM_WIDTH, *floors])


def _pad_cols(x: jnp.ndarray, min_width: int) -> jnp.ndarray:
    pad = min_width - x.shape[-1]
    if pad <= 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def trailing_update(
    a: jnp.ndarray, q: jnp.ndarray, w: jnp.ndarray, *, next_width: int = 0
):
    """Oracle for the fused trailing update: ``A_new = A − Q W`` (f32 math,
    stored in A's dtype) and, when ``next_width > 0``, the lookahead
    ``S = A_new[:, :next_width]ᵀ A_new`` of the *stored* (cast) update."""
    nt = a.shape[-1]
    w32 = w.astype(jnp.float32)
    floor = min_gemm_width()
    if nt < floor:
        wide = jnp.matmul(q.astype(jnp.float32), _pad_cols(w32, floor),
                          precision=F32_PRECISION)
        upd = optimization_barrier(wide)[..., :nt]
    else:
        upd = jnp.matmul(q.astype(jnp.float32), w32, precision=F32_PRECISION)
    a_new = (a.astype(jnp.float32) - upd).astype(a.dtype)
    if not next_width:
        return a_new
    return a_new, panel_cross(a_new, split=next_width)


def panel_cross(a: jnp.ndarray, *, split: int) -> jnp.ndarray:
    """S = A[:, :split]ᵀ A accumulated in float32.  a: (..., m, n)."""
    a32 = a.astype(jnp.float32)
    n = a.shape[-1]
    floor = min_gemm_width()
    if split >= floor and n >= floor:
        return jnp.einsum("...mi,...mj->...ij", a32[..., :split], a32,
                          precision=F32_PRECISION)
    left = _pad_cols(a32[..., :split], floor)
    right = _pad_cols(a32, floor)
    s = jnp.einsum("...mi,...mj->...ij", left, right, precision=F32_PRECISION)
    return optimization_barrier(s)[..., :split, :n]


def pad_cross(a: jnp.ndarray, *, split: int, out_width: int):
    """Oracle for the fused pad+cross prime: widen A with zero columns to
    ``out_width`` and compute the :func:`panel_cross` of the widened copy."""
    pad = out_width - a.shape[-1]
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    a_pad = jnp.pad(a, widths)
    return a_pad, panel_cross(a_pad, split=split)


def combine_gram(r1: jnp.ndarray, r2: jnp.ndarray) -> jnp.ndarray:
    """G = R1ᵀR1 + R2ᵀR2 in float32 — the Gram-combine of two R̃ factors."""
    return gram(r1) + gram(r2)


def _posdiag(r):
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    s = jnp.where(d < 0, -1.0, 1.0).astype(r.dtype)
    return r * s[..., :, None]


def cholesky_qr(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One CholeskyQR round: Q = A·R⁻¹ with R = chol(AᵀA)ᵀ.

    Certified only for κ(A) ≲ 1/√ε; use :func:`cholesky_qr2` in general.
    """
    import jax.scipy.linalg as jsl

    g = gram(a)
    l = jnp.linalg.cholesky(g)
    r = l.T  # upper, positive diagonal by construction
    rinv = jsl.solve_triangular(r, jnp.eye(r.shape[-1], dtype=r.dtype), lower=False)
    q = apply_right(a, rinv)
    return q, r


def cholesky_qr2(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CholeskyQR2 — two rounds; the TPU-native tall-skinny QR."""
    q1, r1 = cholesky_qr(a)
    q, r2 = cholesky_qr(q1)
    return q, _posdiag(jnp.matmul(r2, r1, precision=F32_PRECISION))
