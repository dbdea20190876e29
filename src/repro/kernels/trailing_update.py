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
  * :func:`trailing_update_shift` is that pipeline's per-panel update: it
    reads the whole padded working matrix, updates the columns right of
    the finished panel and writes them back already shifted left by the
    panel width, with the freed columns zeroed in the kernel — so the
    trailing block is never sliced out and the result never re-padded by
    separate passes over HBM.

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

__all__ = ["trailing_update", "trailing_update_shift", "panel_cross",
           "pad_cross"]

_CROSS_DIMS = (((0,), (0,)), ((), ()))   # (rows, b)ᵀ @ (rows, n) → (b, n)
_APPLY_DIMS = (((1,), (0,)), ((), ()))   # (rows, b) @ (b, n) → (rows, n)

# Under the interpreter every dot dimension below this is zero-padded up to
# it.  XLA's CPU backend rewrites a dot with a dimension of 1 into a
# multiply and fuses it with the subtraction or accumulation around it:
# it contracts the pair into FMAs in some lanes and not others, and sums
# the rows of S in an order, both set by the width of the loop it lands
# in.  The same update would then round differently in a kernel whose
# output is wider; no XLA CPU flag fixes both.  The zero terms add exact
# zeros, and compiled kernels (the MXU) never see the padding.
_INTERPRET_DOT_FLOOR = 2


def _widen(x, axis: int, floor: int):
    short = floor - x.shape[axis]
    if short <= 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, short)
    return jnp.pad(x, pad)


def _apply(q, w, floor: int):
    """``Q W`` in float32: (rows, b) @ (b, n) → (rows, n)."""
    n = w.shape[1]
    if floor:
        q, w = _widen(q, 1, floor), _widen(_widen(w, 0, floor), 1, floor)
    out = lax.dot_general(
        q, w, _APPLY_DIMS, precision=dot_precision(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return out[:, :n] if floor else out


def _cross(a, split: int, floor: int):
    """``A[:, :split]ᵀ A`` in float32: (rows, n) → (split, n)."""
    left, right = a[:, :split], a
    if floor:
        left, right = _widen(left, 1, floor), _widen(right, 1, floor)
    out = lax.dot_general(
        left, right, _CROSS_DIMS, precision=dot_precision(a.dtype),
        preferred_element_type=jnp.float32,
    )
    return out[:split, :a.shape[1]] if floor else out


def _update_kernel(a_ref, q_ref, w_ref, *out_refs, block_rows: int, m: int,
                   next_width: int, shift: int, floor: int):
    i = pl.program_id(0)
    nt = w_ref.shape[1]
    # the shifted variant reads the whole padded row-panel and updates the
    # columns right of the finished panel: a lane-offset load
    a = a_ref[:, pl.ds(shift, nt)] if shift else a_ref[...]
    upd = _apply(q_ref[...], w_ref[...], floor)
    a_new = (a.astype(jnp.float32) - upd).astype(a_ref.dtype)
    if shift:
        out_refs[0][:, pl.ds(0, nt)] = a_new
        out_refs[0][:, pl.ds(nt, shift)] = jnp.zeros(
            (a_new.shape[0], shift), a_new.dtype
        )
    else:
        out_refs[0][...] = a_new
    if next_width:
        s_ref = out_refs[1]

        @pl.when(i == 0)
        def _init():
            s_ref[...] = jnp.zeros_like(s_ref)

        a_m = mask_rows(a_new, i, block_rows, m)
        s_ref[...] += _cross(a_m, next_width, floor)


def _trailing_update(a, q, w, *, next_width: int, shift: int,
                     block_rows: int | None, interpret: bool | None):
    be = resolve_backend(interpret)
    m, n_in = a.shape
    m2, b = q.shape
    b2, nt = w.shape
    assert m == m2 and b == b2 and n_in == nt + shift, (
        a.shape, q.shape, w.shape, shift)
    assert 0 <= next_width <= nt, (next_width, nt)
    # keyed on the updated width, so a shifted call streams the same
    # panels (and sums S in the same order) as the unshifted one
    block_rows = _autotune.resolve_block_rows(
        "trailing_update", m, nt, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        out = _gpu.trailing_update(
            a[:, shift:], q, w, next_width=next_width,
            block_rows=block_rows, interpret=False,
        )
        if not shift:
            return out
        return jax.tree.map(
            lambda x: jnp.pad(x, ((0, 0), (0, shift))), out
        )
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    grid = (pl.cdiv(m, block_rows),)
    kernel = functools.partial(
        _update_kernel, block_rows=block_rows, m=m, next_width=next_width,
        shift=shift, floor=_INTERPRET_DOT_FLOOR if be.interpret else 0,
    )
    in_specs = [
        pl.BlockSpec((block_rows, n_in), lambda i: (i, 0)),
        pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
        pl.BlockSpec((b, nt), lambda i: (0, 0)),
    ]
    out_specs = [pl.BlockSpec((block_rows, n_in), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((m, n_in), a.dtype)]
    if next_width:
        out_specs.append(pl.BlockSpec((next_width, nt), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((next_width, nt), jnp.float32))
    named = {}
    if shift:
        # each row-panel is written back where it was read, so in a loop
        # the working matrix is updated in place; unaliased, XLA copies
        # the whole carry to give the kernel a fresh output
        named = dict(name="trailing_update_shift", input_output_aliases={0: 0})
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=be.interpret,
        **named,
    )(a, q, w)
    if not next_width:
        return out[0]
    a_new, s = out
    if shift:
        # S is (next_width × n): the shift of its columns is free
        s = jnp.pad(s, ((0, 0), (0, shift)))
    return a_new, s


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
    return _trailing_update(
        a, q, w, next_width=next_width, shift=0, block_rows=block_rows,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("shift", "next_width", "block_rows", "interpret")
)
def trailing_update_shift(a, q, w, *, shift: int, next_width: int = 0,
                          block_rows: int | None = None,
                          interpret: bool | None = None):
    """:func:`trailing_update` of ``a[:, shift:]``, written back shifted.

    a: (m, n), q: (m, b), w: (b, n − shift).  Returns ``A_new`` (m, n):
    columns ``[0, n − shift)`` hold ``a[:, shift:] − Q W`` and columns
    ``[n − shift, n)`` exact zeros, zeroed in the kernel — bit for bit
    ``concatenate([trailing_update(a[:, shift:], q, w), 0])``, in one
    sweep that reads and writes the full width.  With ``next_width > 0``
    also ``S`` (next_width, n) float32, the unshifted call's ``S`` with
    ``shift`` zero columns appended.  A kernel of its own name, so a
    device trace tells the two apart.
    """
    note_trace("kernel:trailing_update_shift")
    assert shift > 0, shift
    return _trailing_update(
        a, q, w, next_width=next_width, shift=shift, block_rows=block_rows,
        interpret=interpret,
    )


def _cross_kernel(a_ref, s_ref, *, block_rows: int, m: int, split: int,
                  floor: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    a = mask_rows(a_ref[...], i, block_rows, m)
    s_ref[...] += _cross(a, split, floor)


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
            _cross_kernel, block_rows=block_rows, m=m, split=split,
            floor=_INTERPRET_DOT_FLOOR if be.interpret else 0,
        ),
        grid=(pl.cdiv(m, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((split, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((split, n), jnp.float32),
        interpret=be.interpret,
    )(a)


def _pad_cross_kernel(a_ref, apad_ref, s_ref, *, block_rows: int, m: int,
                      split: int, n: int, floor: int):
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
    s_ref[...] += _cross(a_m, split, floor)


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
            _pad_cross_kernel, block_rows=block_rows, m=m, split=split, n=n,
            floor=_INTERPRET_DOT_FLOOR if be.interpret else 0,
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
