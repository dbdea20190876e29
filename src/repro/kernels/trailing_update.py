"""Pallas TPU kernels: one-sweep trailing-matrix update for blocked QR.

The right-looking blocked QR (:mod:`repro.qr.blocked`) spends its FLOPs in
the trailing update ``A_t ← A_t − Q_p (Q_pᵀ A_t)``.  Done naively that is
*two* HBM sweeps over the trailing block per panel: one reduction sweep for
``W = Q_pᵀ A_t`` and one map sweep for the subtraction.  These kernels get
it down to exactly **one** sweep per panel by a lookahead fusion:

  * :func:`trailing_update` applies ``A_new = A_t − Q_p W`` with ``W``
    *already known*, and — in the same pass, while each updated row-panel
    is still in VMEM — accumulates the next panel's cross-Gram
    ``S = A_new[:, :next_width]ᵀ A_new`` into a VMEM-resident f32
    accumulator.  ``S[:, :next_width]`` is the next panel's Gram (its local
    QR via Cholesky) and ``S[:, next_width:]`` is the next cross product
    ``A_pᵀ A_t`` (whence the next ``W = R⁻ᵀ ΣS``), so the *next* panel
    never has to re-read the trailing block at all.
  * :func:`panel_cross` primes the pipeline: one sweep over the initial
    matrix producing ``S = A[:, :split]ᵀ A`` for panel 0.
  * :func:`pad_cross` is the fixed-shape (scan-compiled) driver's prime:
    the same sweep additionally emits a copy of A widened to the padded
    maximal trailing width with in-kernel zeroed pad columns — the column
    extension of the row-iota edge masking (DESIGN.md §9).

K panels therefore cost exactly K trailing-block sweeps — 1 per panel —
which the ``general_qr`` bench case hard-gates through the
:mod:`repro.kernels.traffic` model.

Tiling mirrors the CQR2 kernels: row-panels of the tall operands stream
HBM→VMEM over a sequential grid, the small operands (``W``, the ``S``
accumulator) are VMEM-resident constant blocks, and ragged edge tiles are
masked in-kernel against a row iota (``S`` contributions) or dropped on the
partial final block write (``A_new`` rows) — no padded HBM copy is ever
materialized.  The update is computed in f32 and cast to the storage dtype
*before* feeding the ``S`` accumulator, so ``S`` is bit-identical to
``panel_cross`` re-run on the stored ``A_new`` with the same panel height.

VMEM at defaults (block_rows=1024, n_trail≤512, b≤128, f32): input panel
+ Q panel + W + updated panel + S accumulator ≈ 5 MiB — inside ~16 MiB/core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import autotune as _autotune
from .backend import dot_precision, pick_block_rows, resolve_backend
from .dispatch import note_trace
from .gram import mask_cols, mask_rows

__all__ = ["trailing_update", "panel_cross", "pad_cross"]

_CROSS_DIMS = (((0,), (0,)), ((), ()))   # (rows, b)ᵀ @ (rows, n) → (b, n)
_APPLY_DIMS = (((1,), (0,)), ((), ()))   # (rows, b) @ (b, n) → (rows, n)


def _update_kernel(a_ref, q_ref, w_ref, *out_refs, block_rows: int, m: int,
                   next_width: int):
    i = pl.program_id(0)
    upd = lax.dot_general(
        q_ref[...], w_ref[...], _APPLY_DIMS,
        precision=dot_precision(q_ref.dtype),
        preferred_element_type=jnp.float32
    )
    a_new = (a_ref[...].astype(jnp.float32) - upd).astype(a_ref.dtype)
    out_refs[0][...] = a_new
    if next_width:
        s_ref = out_refs[1]

        @pl.when(i == 0)
        def _init():
            s_ref[...] = jnp.zeros_like(s_ref)

        a_m = mask_rows(a_new, i, block_rows, m)
        s_ref[...] += lax.dot_general(
            a_m[:, :next_width], a_m, _CROSS_DIMS,
            precision=dot_precision(a_m.dtype),
            preferred_element_type=jnp.float32,
        )


@functools.partial(
    jax.jit, static_argnames=("next_width", "block_rows", "interpret")
)
def trailing_update(a, q, w, *, next_width: int = 0,
                    block_rows: int | None = None,
                    interpret: bool | None = None):
    """One-sweep ``A_new = A − Q W`` (+ lookahead ``S``).

    a: (m, n_t), q: (m, b), w: (b, n_t).  Returns ``A_new`` (m, n_t) in
    ``a``'s dtype — and, when ``next_width > 0``, also
    ``S = A_new[:, :next_width]ᵀ A_new`` (next_width, n_t) float32, the
    next panel's fused Gram + cross product.  ``interpret=None``
    auto-detects the backend; ``block_rows=None`` consults the installed
    autotune table at trace time (see :func:`repro.kernels.gram.gram`).
    """
    note_trace("kernel:trailing_update")
    be = resolve_backend(interpret)
    m, nt = a.shape
    m2, b = q.shape
    b2, nt2 = w.shape
    assert m == m2 and b == b2 and nt == nt2, (a.shape, q.shape, w.shape)
    assert 0 <= next_width <= nt, (next_width, nt)
    block_rows = _autotune.resolve_block_rows(
        "trailing_update", m, nt, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        return _gpu.trailing_update(
            a, q, w, next_width=next_width, block_rows=block_rows,
            interpret=False,
        )
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    grid = (pl.cdiv(m, block_rows),)
    kernel = functools.partial(
        _update_kernel, block_rows=block_rows, m=m, next_width=next_width
    )
    in_specs = [
        pl.BlockSpec((block_rows, nt), lambda i: (i, 0)),
        pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
        pl.BlockSpec((b, nt), lambda i: (0, 0)),
    ]
    out_specs = [pl.BlockSpec((block_rows, nt), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((m, nt), a.dtype)]
    if next_width:
        out_specs.append(pl.BlockSpec((next_width, nt), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((next_width, nt), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=be.interpret,
    )(a, q, w)
    if next_width:
        return tuple(out)
    return out[0]


def _cross_kernel(a_ref, s_ref, *, block_rows: int, m: int, split: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    a = mask_rows(a_ref[...], i, block_rows, m)
    s_ref[...] += lax.dot_general(
        a[:, :split], a, _CROSS_DIMS, precision=dot_precision(a.dtype),
        preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("split", "block_rows", "interpret"))
def panel_cross(a, *, split: int, block_rows: int | None = None,
                interpret: bool | None = None):
    """Pipeline prime: ``S = A[:, :split]ᵀ A`` in one sweep, float32.

    a: (m, n) → (split, n).  ``S[:, :split]`` is panel 0's Gram,
    ``S[:, split:]`` its cross product against the trailing block.
    """
    note_trace("kernel:panel_cross")
    be = resolve_backend(interpret)
    m, n = a.shape
    assert 0 < split <= n, (split, n)
    block_rows = _autotune.resolve_block_rows(
        "panel_cross", m, n, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        return _gpu.panel_cross(
            a, split=split, block_rows=block_rows, interpret=False
        )
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    return pl.pallas_call(
        functools.partial(
            _cross_kernel, block_rows=block_rows, m=m, split=split
        ),
        grid=(pl.cdiv(m, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((split, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((split, n), jnp.float32),
        interpret=be.interpret,
    )(a)


def _pad_cross_kernel(a_ref, apad_ref, s_ref, *, block_rows: int, m: int,
                      split: int, n: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    # The input block is read at the widened out_width: columns >= n are
    # out-of-bounds garbage, zeroed against a column iota — the exact
    # column analogue of the row-iota edge masking below.
    a_p = mask_cols(a_ref[...], n)
    apad_ref[...] = a_p                 # OOB rows dropped on the edge write
    a_m = mask_rows(a_p, i, block_rows, m)
    s_ref[...] += lax.dot_general(
        a_m[:, :split], a_m, _CROSS_DIMS,
        precision=dot_precision(a_m.dtype),
        preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit, static_argnames=("split", "out_width", "block_rows", "interpret")
)
def pad_cross(a, *, split: int, out_width: int,
              block_rows: int | None = None,
              interpret: bool | None = None):
    """Pipeline prime for the fixed-shape blocked QR: widen A to the padded
    trailing width and compute ``S = A[:, :split]ᵀ A`` in the **same** sweep.

    a: (m, n) → ``(a_pad (m, out_width) in a's dtype, s (split, out_width)
    float32)``.  Columns ``>= n`` of both outputs are exact zeros (the
    column extension of the row-iota edge masking): the scan-compiled
    driver keeps its trailing block at the maximal width ``K·b``, and zero
    pad columns ride every later sweep without perturbing the real columns
    bit-for-bit.  Compared to ``jnp.pad`` + :func:`panel_cross` this saves
    one full HBM read of the padded copy — A is streamed once, the padded
    copy and the lookahead accumulator are produced together.
    """
    note_trace("kernel:pad_cross")
    be = resolve_backend(interpret)
    m, n = a.shape
    assert 0 < split <= n <= out_width, (split, n, out_width)
    block_rows = _autotune.resolve_block_rows(
        "pad_cross", m, n, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        return _gpu.pad_cross(
            a, split=split, out_width=out_width, block_rows=block_rows,
            interpret=False,
        )
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    return pl.pallas_call(
        functools.partial(
            _pad_cross_kernel, block_rows=block_rows, m=m, split=split, n=n
        ),
        grid=(pl.cdiv(m, block_rows),),
        # the input block is read at the *widened* width: columns >= n are
        # out-of-bounds and masked in-kernel (mask_cols), like edge rows
        in_specs=[pl.BlockSpec((block_rows, out_width), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, out_width), lambda i: (i, 0)),
            pl.BlockSpec((split, out_width), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, out_width), a.dtype),
            jax.ShapeDtypeStruct((split, out_width), jnp.float32),
        ],
        interpret=be.interpret,
    )(a)
