"""Pallas TPU kernel: blocked Gram matrix G = AᵀA for tall-skinny A.

This is the FLOP hot-spot of the TPU-native local QR (CholeskyQR2,
DESIGN.md §2, adaptation #2): for A (m, n) with m ≫ n, the Gram product is
~m·n² MACs while everything downstream (Cholesky, small inverse) is O(n³).
The kernel streams row-panels of A HBM→VMEM and accumulates the (n, n) Gram
block in VMEM across the sequential TPU grid, so A is read exactly once and
the accumulator never leaves VMEM.

Tiling:
  * grid = (⌈m / block_rows⌉,) — sequential row sweep ("arbitrary"
    dimension semantics: the accumulation is order-independent).
  * A panel  BlockSpec (block_rows, n), index_map i → (i, 0).
  * G output BlockSpec (n, n), index_map i → (0, 0): a constant output
    block revisited by every grid step = the VMEM accumulator.
  * Edge tiles are handled **in-kernel**: when ``block_rows ∤ m`` the last
    panel's out-of-bounds rows are zeroed against a row-index iota before
    the matmul, so zero rows contribute nothing to AᵀA.  No padded copy of
    A is ever materialized in HBM (the seed ``jnp.pad``-ed A to lane/block
    multiples before every call — a full extra HBM round-trip); sub-lane n
    is padded by Mosaic inside VMEM only.

VMEM budget at defaults (block_rows=1024, n≤512, bf16 in / f32 acc):
1 MiB panel + 1 MiB accumulator — comfortably inside the ~16 MiB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import autotune as _autotune
from .backend import DEFAULT_BLOCK_ROWS, dot_precision, pick_block_rows, resolve_backend
from .dispatch import note_trace

__all__ = [
    "gram",
    "DEFAULT_BLOCK_ROWS",
    "pick_block_rows",
    "mask_rows",
    "mask_cols",
]


def mask_rows(panel, grid_idx, block_rows: int, m: int):
    """Zero the out-of-bounds rows of an edge panel (no-op when blocks
    divide m exactly — the branch is static)."""
    if m % block_rows == 0:
        return panel
    rows = grid_idx * block_rows + lax.broadcasted_iota(
        jnp.int32, panel.shape, 0
    )
    return jnp.where(rows < m, panel, jnp.zeros_like(panel))


def mask_cols(block, n_valid: int):
    """Zero columns ``>= n_valid`` of a block — the column analogue of
    :func:`mask_rows`, used by the fixed-shape blocked-QR pipeline to keep
    a padded trailing block exact (no-op when the block is exactly
    ``n_valid`` wide — the branch is static)."""
    if block.shape[-1] == n_valid:
        return block
    cols = lax.broadcasted_iota(jnp.int32, block.shape, block.ndim - 1)
    return jnp.where(cols < n_valid, block, jnp.zeros_like(block))


def _gram_kernel(a_ref, o_ref, *, block_rows: int, m: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = mask_rows(a_ref[...], i, block_rows, m)
    o_ref[...] += lax.dot_general(
        a, a, (((0,), (0,)), ((), ())), precision=dot_precision(a.dtype),
        preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gram(a, *, block_rows: int | None = None,
         interpret: bool | None = None):
    """G = AᵀA, float32.  a: (m, n); returns (n, n).

    ``interpret=None`` auto-detects the backend (compiled Mosaic kernel on
    TPU, compiled Triton on GPU, Pallas interpreter elsewhere); pass an
    explicit bool to override.  ``block_rows=None`` consults the installed
    autotune table at trace time (the resolved int is frozen into this
    shape's compiled program — callers that want table changes to take
    effect per call resolve at the Python level, as ``ops`` does, and pass
    the concrete int).
    """
    note_trace("kernel:gram")
    be = resolve_backend(interpret)
    m, n = a.shape
    block_rows = _autotune.resolve_block_rows(
        "gram", m, n, a.dtype, explicit=block_rows, backend=be
    )
    if be.kind == "gpu-triton":
        from . import gpu as _gpu

        return _gpu.gram(a, block_rows=block_rows, interpret=False)
    block_rows = pick_block_rows(m, block_rows, sublane=be.sublane)
    return pl.pallas_call(
        functools.partial(_gram_kernel, block_rows=block_rows, m=m),
        grid=(pl.cdiv(m, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=be.interpret,
    )(a)
