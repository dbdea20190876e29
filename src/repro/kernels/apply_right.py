"""Pallas TPU kernel: panel-streamed right-multiply Q = A @ W.

The second hot-spot of CholeskyQR2: forming Q = A·R⁻¹ once the small
triangular factor is inverted.  Same streaming structure as the Gram
kernel — A row-panels stream HBM→VMEM, the (n, k) right operand is resident
in VMEM for the whole sweep, and each output panel is written exactly once
(index_map i → (i, 0), no revisits).  Accumulation is f32 on the MXU;
the result is cast back to A's dtype on the way out.

Edge tiles need no masking here: an out-of-bounds input row produces an
out-of-bounds output row, which Pallas discards on the partial final block
write.  No padded copy of A or W ever hits HBM (the seed padded both to
lane multiples before every call).
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

__all__ = ["apply_right"]


def _apply_kernel(a_ref, w_ref, o_ref):
    o_ref[...] = lax.dot_general(
        a_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        precision=dot_precision(a_ref.dtype),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def apply_right(a, w, *, block_rows: int | None = None,
                interpret: bool | None = None):
    """A (m, n) @ W (n, k) → (m, k) in A's dtype, f32 accumulation.

    ``interpret=None`` auto-detects the backend (compiled on TPU/GPU,
    interpreted elsewhere); ``block_rows=None`` consults the installed
    autotune table at trace time (see :func:`repro.kernels.gram.gram`).
    """
    note_trace("kernel:apply_right")
    be = resolve_backend(interpret)
    m, n = a.shape
    n2, k = w.shape
    assert n == n2, (a.shape, w.shape)
    block_rows = _autotune.resolve_block_rows(
        "apply_right", m, n, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        return _gpu.apply_right(a, w, block_rows=block_rows, interpret=False)
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    return pl.pallas_call(
        _apply_kernel,
        grid=(pl.cdiv(m, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), a.dtype),
        interpret=be.interpret,
    )(a, w)
