"""Pallas TPU kernel: fused panel apply + next-round Gram, one HBM sweep.

The CholeskyQR2 pipeline interleaves two panel-streamed passes per round:
``Q = A @ W`` (apply) followed by ``G' = QᵀQ`` (the next round's Gram).
Running them as separate kernels streams the tall operand over HBM twice —
and the apply's output panel is *already in VMEM* when the Gram pass would
re-read it.  This kernel fuses the two: per row-panel it

  1. computes ``Q_i = A_i @ W`` on the MXU (f32 accumulation, cast to the
     storage dtype — the exact rounding a materialized Q would carry),
  2. optionally writes ``Q_i`` out (``want_q=True``), and
  3. accumulates ``G' += Q_iᵀ Q_i`` into the VMEM-resident (k, k)
     accumulator (a constant output block revisited by every grid step).

so one sweep over A yields both the applied panel and the Gram the next
round needs.  With ``want_q=False`` (the R-factor-only TSQR local QR) the
panel is consumed entirely in VMEM and never touches HBM at all — CQR2's R
comes out in **2** tall-operand sweeps instead of the seed's 4 (see
``ops.cholesky_qr2_r`` and the hard-gated ``kernels`` bench case).

Edge tiles are masked in-kernel against a row-index iota (zero rows
contribute nothing to either product); no padded copy of A is materialized
in HBM.  Because the Gram is taken of the *cast* panel with the same
``block_rows`` panel boundaries, the accumulated G' is bit-identical to
``gram(apply_right(A, W))`` from the unfused kernels.

VMEM budget at defaults (block_rows=1024, n=k≤512, bf16 in / f32 acc):
one (block_rows, n) input panel + one (block_rows, k) product panel +
the (k, k) f32 accumulator ≈ 3 MiB — well inside ~16 MiB/core.
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
from .gram import mask_rows

__all__ = ["fused_apply_gram"]

_GRAM_DIMS = (((0,), (0,)), ((), ()))
_APPLY_DIMS = (((1,), (0,)), ((), ()))


def _fused_kernel(a_ref, w_ref, *out_refs, block_rows: int, m: int,
                  want_q: bool):
    g_ref = out_refs[-1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    a = mask_rows(a_ref[...], i, block_rows, m)
    q32 = lax.dot_general(
        a, w_ref[...], _APPLY_DIMS, precision=dot_precision(a.dtype),
        preferred_element_type=jnp.float32
    )
    q = q32.astype(a_ref.dtype)
    if want_q:
        out_refs[0][...] = q
    g_ref[...] += lax.dot_general(
        q, q, _GRAM_DIMS, precision=dot_precision(q.dtype),
        preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "want_q")
)
def fused_apply_gram(a, w, *, block_rows: int | None = None,
                     interpret: bool | None = None, want_q: bool = True):
    """One-sweep fused ``Q = A @ W`` and ``G' = QᵀQ``.

    a: (m, n), w: (n, k).  Returns ``(q, g)`` with q (m, k) in A's dtype and
    g (k, k) float32 — or just ``g`` when ``want_q=False`` (Q never leaves
    VMEM).  ``interpret=None`` auto-detects the backend; ``block_rows=None``
    consults the installed autotune table at trace time (see
    :func:`repro.kernels.gram.gram`).
    """
    note_trace("kernel:fused_apply_gram")
    be = resolve_backend(interpret)
    m, n = a.shape
    n2, k = w.shape
    assert n == n2, (a.shape, w.shape)
    block_rows = _autotune.resolve_block_rows(
        "fused_apply_gram", m, n, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        return _gpu.fused_apply_gram(
            a, w, block_rows=block_rows, interpret=False, want_q=want_q
        )
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    grid = (pl.cdiv(m, block_rows),)
    kernel = functools.partial(
        _fused_kernel, block_rows=block_rows, m=m, want_q=want_q
    )
    in_specs = [
        pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        pl.BlockSpec((n, k), lambda i: (0, 0)),
    ]
    gram_spec = pl.BlockSpec((k, k), lambda i: (0, 0))
    gram_shape = jax.ShapeDtypeStruct((k, k), jnp.float32)
    if want_q:
        out_specs = [pl.BlockSpec((block_rows, k), lambda i: (i, 0)), gram_spec]
        out_shape = [jax.ShapeDtypeStruct((m, k), a.dtype), gram_shape]
    else:
        out_specs = [gram_spec]
        out_shape = [gram_shape]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=be.interpret,
    )(a, w)
    if want_q:
        return tuple(out)
    return out[0]
