"""Pallas TPU kernel: fused Gram-combine of two R̃ factors.

Used by the beyond-paper "Gram-butterfly" TSQR variant (EXPERIMENTS.md
§Perf): instead of re-factorizing the stacked ``[R̃₁; R̃₂]`` (a 2n×n
Householder QR, sequential and VPU-bound on TPU), the combine keeps Gram
form ``G = R̃₁ᵀR̃₁ + R̃₂ᵀR̃₂`` — two n×n MXU matmuls fused in one VMEM-resident
kernel, deferring the single Cholesky to the end of the butterfly.

Single-block kernel: both operands and the output live entirely in VMEM
(n ≤ 512 in every TSQR use; 3·n²·4B ≤ 3 MiB).  Operands are passed at their
natural (n, n) shape — Mosaic pads to lane tiles inside VMEM; no padded
copy is materialized in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .backend import dot_precision, resolve_interpret
from .dispatch import note_trace

__all__ = ["combine_gram"]


def _combine_kernel(r1_ref, r2_ref, o_ref):
    r1 = r1_ref[...]
    r2 = r2_ref[...]
    dims = (((0,), (0,)), ((), ()))
    kw = dict(precision=dot_precision(r1.dtype), preferred_element_type=jnp.float32)
    o_ref[...] = (lax.dot_general(r1, r1, dims, **kw)
                  + lax.dot_general(r2, r2, dims, **kw))


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_gram(r1, r2, *, interpret: bool | None = None):
    """G = R1ᵀR1 + R2ᵀR2, float32.  r1, r2: (n, n) → (n, n).

    ``interpret=None`` auto-detects the backend.
    """
    note_trace("kernel:combine_gram")
    interpret = resolve_interpret(interpret)
    n = r1.shape[-1]
    assert r1.shape == r2.shape == (n, n)
    return pl.pallas_call(
        _combine_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(r1, r2)
