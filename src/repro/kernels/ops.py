"""Jit'd public wrappers over the Pallas kernels (with pure-jnp fallbacks).

``cholesky_qr2`` is the TPU-native local QR used by the TSQR variants
(DESIGN.md §2, adaptation #2): Householder panels are sequential and
VPU-bound, while CQR2 is two rounds of (Gram matmul → n×n Cholesky →
triangular inverse → panel matmul) — all MXU-shaped.  Numerically CQR2
delivers Householder-grade orthogonality for κ(A) ≲ 1/√ε per round.

The pipeline is **fused** (DESIGN.md §Kernels): round 1's panel apply also
accumulates round 2's Gram in VMEM (:mod:`repro.kernels.fused_apply_gram`),
so the full factorization streams the tall operand 3× instead of the seed's
4×, and the R-factor-only variant (:func:`cholesky_qr2_r` — what the TSQR
local QR actually needs) streams it exactly **2×** with no tall intermediate
ever written to HBM.  Every wrapper reports its HBM traffic to
:mod:`repro.kernels.traffic`, which the ``kernels`` bench case hard-gates.

``interpret=None`` (the default everywhere) auto-detects the backend:
compiled Mosaic kernels on TPU, the Pallas interpreter elsewhere
(:mod:`repro.kernels.backend`).  Every wrapper accepts arbitrary leading
batch dimensions (the SimComm backend carries a (P,) rank axis); Pallas
calls are vmapped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from . import apply_right as _apply_mod
from . import autotune as _autotune
from . import combine_gram as _combine_mod
from . import dispatch as _dispatch
from . import fused_apply_gram as _fused_mod
from . import gram as _gram_mod
from . import ref as _ref
from . import traffic as _traffic
from . import trailing_update as _trailing_mod
from .backend import F32_PRECISION, resolve_backend

__all__ = [
    "gram",
    "apply_right",
    "fused_apply_gram",
    "combine_gram",
    "cholesky_qr",
    "cholesky_qr2",
    "cholesky_qr2_r",
    "tri_inv",
    "trailing_update",
    "panel_cross",
    "pad_cross",
]


def _batched(fn, n_array_args):
    """Apply ``fn`` over arbitrary shared leading batch dims."""

    def wrapped(*args, **kwargs):
        arrays = args[:n_array_args]
        extra = arrays[0].ndim - 2
        if extra == 0:
            return fn(*args, **kwargs)
        f = functools.partial(fn, **kwargs)
        for _ in range(extra):
            f = jax.vmap(f)
        return f(*arrays)

    return wrapped


def _nbytes(x) -> int:
    return int(x.size) * x.dtype.itemsize


def _resolve_br(op: str, a, block_rows: int | None,
                interpret: bool | None) -> int:
    """Resolve the tuned panel height **at the Python level, per call**:
    explicit caller choice > installed autotune winner for the shape-class >
    aligned default.  The concrete int becomes the kernel's static jit key,
    so installing a new tuned table takes effect immediately for its
    shape-classes and never retraces any other warm class (the retrace
    guard pins this)."""
    return _autotune.resolve_block_rows(
        op, a.shape[-2], a.shape[-1], a.dtype, explicit=block_rows,
        backend=resolve_backend(interpret),
    )


def _pre(op: str) -> int:
    """Snapshot the kernel's process-lifetime trace count before a call."""
    return _dispatch.trace_count("kernel:" + op)


def _note(op: str, t0: int, **traffic_kw) -> None:
    """Record one wrapper call: a device dispatch plus its HBM traffic, with
    the number of *new* jit traces the call caused (0 on warm calls — the
    zero-retrace contract the ``dispatch`` bench case gates)."""
    _dispatch.note_dispatch(op)
    _traffic.note(
        op, dispatches=1, traces=_dispatch.trace_count("kernel:" + op) - t0,
        **traffic_kw,
    )


# -- kernel entry points (batched, pallas/jnp switchable) -------------------

def gram(a, *, use_pallas: bool = False, interpret: bool | None = None,
         block_rows: int | None = None):
    t0 = _pre("gram")
    if use_pallas:
        out = _batched(_gram_mod.gram, 1)(
            a, interpret=interpret,
            block_rows=_resolve_br("gram", a, block_rows, interpret),
        )
    else:
        out = _ref.gram(a)
    _note("gram", t0, sweeps=1, read_bytes=_nbytes(a),
          write_bytes=_nbytes(out))
    return out


def apply_right(a, w, *, use_pallas: bool = False,
                interpret: bool | None = None,
                block_rows: int | None = None):
    t0 = _pre("apply_right")
    if use_pallas:
        out = _batched(_apply_mod.apply_right, 2)(
            a, w, interpret=interpret,
            block_rows=_resolve_br("apply_right", a, block_rows, interpret),
        )
    else:
        out = _ref.apply_right(a, w)
    _note("apply_right", t0, sweeps=1,
          read_bytes=_nbytes(a) + _nbytes(w),
          write_bytes=_nbytes(out))
    return out


def fused_apply_gram(a, w, *, use_pallas: bool = False,
                     interpret: bool | None = None, want_q: bool = True,
                     block_rows: int | None = None):
    """One tall-operand sweep: ``Q = A @ W`` and ``G' = QᵀQ`` together.

    Returns ``(q, g)`` — or just ``g`` when ``want_q=False``, in which case
    the applied panel never leaves VMEM (no tall HBM write at all).
    """
    t0 = _pre("fused_apply_gram")
    if use_pallas:
        out = _batched(_fused_mod.fused_apply_gram, 2)(
            a, w, interpret=interpret, want_q=want_q,
            block_rows=_resolve_br("fused_apply_gram", a, block_rows,
                                   interpret),
        )
    else:
        q = _ref.apply_right(a, w)
        g = _ref.gram(q)
        out = (q, g) if want_q else g
    g_out = out[1] if want_q else out
    q_bytes = _nbytes(out[0]) if want_q else 0
    _note("fused_apply_gram", t0, sweeps=1,
          read_bytes=_nbytes(a) + _nbytes(w),
          write_bytes=q_bytes + _nbytes(g_out))
    return out


def combine_gram(r1, r2, *, use_pallas: bool = False,
                 interpret: bool | None = None):
    t0 = _pre("combine_gram")
    out = (
        _batched(_combine_mod.combine_gram, 2)(r1, r2, interpret=interpret)
        if use_pallas
        else _ref.combine_gram(r1, r2)
    )
    _note("combine_gram", t0, read_bytes=_nbytes(r1) + _nbytes(r2),
          write_bytes=_nbytes(out))
    return out


# -- raw dispatchers (no traffic/dispatch notes) ----------------------------
#
# The scan-compiled blocked-QR pipeline (repro.qr.blocked) traces these
# *once* for all K panels, so noting at kernel-call time would undercount by
# K−1 on the first call and by K on every warm call; the pipeline wrapper
# notes its exact per-call totals itself instead.
#
# The jnp oracles are dispatched through module-level jits: the eager
# driver then executes the *same compiled pattern* the pipeline traces into
# its single program (XLA applies rewrites like fusing a width-1 panel's
# degenerate product into the trailing subtraction's FMA only under jit —
# op-by-op eager execution would differ from the pipeline in the last ulp),
# and the jnp path stops re-dispatching op-by-op on every panel.  They note
# traces under the same ``kernel:<op>`` keys as the Pallas kernels, so the
# per-call trace deltas in ``_note`` are honest on both kernel paths.

@functools.partial(jax.jit, static_argnames=("next_width",))
def _ref_trailing_jit(a, q, w, *, next_width: int = 0):
    _dispatch.note_trace("kernel:trailing_update")
    return _ref.trailing_update(a, q, w, next_width=next_width)


@functools.partial(jax.jit, static_argnames=("split",))
def _ref_panel_cross_jit(a, *, split: int):
    _dispatch.note_trace("kernel:panel_cross")
    return _ref.panel_cross(a, split=split)


@functools.partial(jax.jit, static_argnames=("split", "out_width"))
def _ref_pad_cross_jit(a, *, split: int, out_width: int):
    _dispatch.note_trace("kernel:pad_cross")
    return _ref.pad_cross(a, split=split, out_width=out_width)


def _shift_left(x, shift: int):
    """Append ``shift`` zero columns to the updated block(s)."""
    return jax.tree.map(
        lambda y: jnp.concatenate(
            [y, jnp.zeros(y.shape[:-1] + (shift,), y.dtype)], axis=-1
        ),
        x,
    )


def _trailing_update_raw(a, q, w, *, next_width: int = 0, shift: int = 0,
                         use_pallas: bool = False,
                         interpret: bool | None = None,
                         block_rows: int | None = None):
    """``shift > 0``: update ``a[..., shift:]`` and return it (and ``S``)
    shifted left into ``a``'s full width, the freed columns zero.  The
    Pallas path does it inside the kernel's one sweep; the jnp oracle
    slices and concatenates around its own jit, so its program is the
    unshifted one — XLA would round a width-1 product differently once
    the concatenate shared the program."""
    if use_pallas:
        if shift:
            return _batched(_trailing_mod.trailing_update_shift, 3)(
                a, q, w, shift=shift, next_width=next_width,
                interpret=interpret, block_rows=block_rows,
            )
        return _batched(_trailing_mod.trailing_update, 3)(
            a, q, w, next_width=next_width, interpret=interpret,
            block_rows=block_rows,
        )
    if shift:
        return _shift_left(
            _ref_trailing_jit(a[..., shift:], q, w, next_width=next_width),
            shift,
        )
    return _ref_trailing_jit(a, q, w, next_width=next_width)


def _panel_cross_raw(a, *, split: int, use_pallas: bool = False,
                     interpret: bool | None = None,
                     block_rows: int | None = None):
    if use_pallas:
        return _batched(_trailing_mod.panel_cross, 1)(
            a, split=split, interpret=interpret, block_rows=block_rows
        )
    return _ref_panel_cross_jit(a, split=split)


def _pad_cross_raw(a, *, split: int, out_width: int, use_pallas: bool = False,
                   interpret: bool | None = None,
                   block_rows: int | None = None):
    if use_pallas:
        return _batched(_trailing_mod.pad_cross, 1)(
            a, split=split, out_width=out_width, interpret=interpret,
            block_rows=block_rows,
        )
    return _ref_pad_cross_jit(a, split=split, out_width=out_width)


def trailing_update(a, q, w, *, next_width: int = 0, use_pallas: bool = False,
                    interpret: bool | None = None,
                    block_rows: int | None = None):
    """Blocked-QR trailing update ``A − Q W`` in **one** trailing-block
    sweep, with the next panel's cross-Gram ``S`` accumulated in the same
    pass when ``next_width > 0`` (see :mod:`repro.kernels.trailing_update`).

    Returns ``a_new`` — or ``(a_new, s)`` when ``next_width > 0``.
    """
    t0 = _pre("trailing_update")
    if use_pallas:
        block_rows = _resolve_br("trailing_update", a, block_rows, interpret)
    out = _trailing_update_raw(
        a, q, w, next_width=next_width, use_pallas=use_pallas,
        interpret=interpret, block_rows=block_rows,
    )
    a_new = out[0] if next_width else out
    s_bytes = _nbytes(out[1]) if next_width else 0
    _note("trailing_update", t0, sweeps=1,
          read_bytes=_nbytes(a) + _nbytes(q) + _nbytes(w),
          write_bytes=_nbytes(a_new) + s_bytes)
    return out


def panel_cross(a, *, split: int, use_pallas: bool = False,
                interpret: bool | None = None,
                block_rows: int | None = None):
    """Pipeline prime for blocked QR: ``S = A[:, :split]ᵀ A`` in one sweep."""
    t0 = _pre("panel_cross")
    if use_pallas:
        block_rows = _resolve_br("panel_cross", a, block_rows, interpret)
    out = _panel_cross_raw(
        a, split=split, use_pallas=use_pallas, interpret=interpret,
        block_rows=block_rows,
    )
    _note("panel_cross", t0, sweeps=1, read_bytes=_nbytes(a),
          write_bytes=_nbytes(out))
    return out


def pad_cross(a, *, split: int, out_width: int, use_pallas: bool = False,
              interpret: bool | None = None, block_rows: int | None = None):
    """Fixed-shape pipeline prime: widen A to the padded trailing width and
    compute ``S = A[:, :split]ᵀ A`` in the same single sweep.  Returns
    ``(a_pad, s)`` — see :func:`repro.kernels.trailing_update.pad_cross`."""
    t0 = _pre("pad_cross")
    if use_pallas:
        block_rows = _resolve_br("pad_cross", a, block_rows, interpret)
    out = _pad_cross_raw(
        a, split=split, out_width=out_width, use_pallas=use_pallas,
        interpret=interpret, block_rows=block_rows,
    )
    _note("pad_cross", t0, sweeps=1, read_bytes=_nbytes(a),
          write_bytes=_nbytes(out[0]) + _nbytes(out[1]))
    return out


# -- composed ops -----------------------------------------------------------

def tri_inv(r):
    """Inverse of an upper-triangular (…, n, n) factor.

    Solves against the single unbatched identity — no broadcast (…, n, n)
    identity is ever materialized; batch dims are vmapped over ``r`` only.
    Accumulation stays in ``r``'s (f32 in every CQR2 use) precision.
    """
    eye = jnp.eye(r.shape[-1], dtype=r.dtype)

    def solve(rr):
        return jsl.solve_triangular(rr, eye, lower=False)

    for _ in range(r.ndim - 2):
        solve = jax.vmap(solve)
    return solve(r)


def _posdiag(r):
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    s = jnp.where(d < 0, -1.0, 1.0).astype(r.dtype)
    return r * s[..., :, None]


def _chol_upper(g):
    """Upper-triangular Cholesky factor of a Gram matrix (positive diag)."""
    return jnp.swapaxes(jnp.linalg.cholesky(g), -1, -2)


def cholesky_qr(a, *, use_pallas: bool = False, interpret: bool | None = None):
    """One CholeskyQR round.  a: (…, m, n) → (Q (…, m, n), R (…, n, n) f32)."""
    g = gram(a, use_pallas=use_pallas, interpret=interpret)
    r = _chol_upper(g)
    q = apply_right(
        a, tri_inv(r).astype(a.dtype), use_pallas=use_pallas, interpret=interpret
    )
    return q, r


def cholesky_qr2(a, *, use_pallas: bool = False, interpret: bool | None = None,
                 fused: bool = True):
    """CholeskyQR2: Householder-grade orthogonality, MXU-native FLOPs.

    ``fused=True`` (default) rides :func:`fused_apply_gram`: round 1's panel
    apply accumulates round 2's Gram in the same sweep — 3 tall-operand
    sweeps (A, A, Q₁) instead of the unfused 4 (A, A, Q₁, Q₁).
    ``fused=False`` keeps the seed's two independent rounds (the bench
    baseline and the property-test reference).
    """
    if not fused:
        q1, r1 = cholesky_qr(a, use_pallas=use_pallas, interpret=interpret)
        q, r2 = cholesky_qr(q1, use_pallas=use_pallas, interpret=interpret)
        return q, _posdiag(jnp.matmul(r2, r1, precision=F32_PRECISION))
    g1 = gram(a, use_pallas=use_pallas, interpret=interpret)       # sweep 1
    r1 = _chol_upper(g1)
    q1, g2 = fused_apply_gram(                                     # sweep 2
        a, tri_inv(r1).astype(a.dtype),
        use_pallas=use_pallas, interpret=interpret,
    )
    r2 = _chol_upper(g2)
    q = apply_right(                                               # sweep 3
        q1, tri_inv(r2).astype(a.dtype),
        use_pallas=use_pallas, interpret=interpret,
    )
    return q, _posdiag(jnp.matmul(r2, r1, precision=F32_PRECISION))


def cholesky_qr2_r(a, *, use_pallas: bool = False,
                   interpret: bool | None = None):
    """CholeskyQR2, R factor only — **2 HBM sweeps** over the tall operand.

    This is the TSQR local QR (``QRCombiner.prepare``): the butterfly only
    carries R, so Q₁ is never needed.  Sweep 1 is the Gram of A; sweep 2 is
    :func:`fused_apply_gram` with ``want_q=False`` — the applied panel is
    consumed in VMEM for round 2's Gram and no tall intermediate touches
    HBM.  Bit-identical to ``cholesky_qr2(a)[1]`` (same panel boundaries,
    same cast points); the seed computed the full 4-sweep factorization and
    discarded Q.
    """
    g1 = gram(a, use_pallas=use_pallas, interpret=interpret)       # sweep 1
    r1 = _chol_upper(g1)
    g2 = fused_apply_gram(                                         # sweep 2
        a, tri_inv(r1).astype(a.dtype),
        use_pallas=use_pallas, interpret=interpret, want_q=False,
    )
    r2 = _chol_upper(g2)
    return _posdiag(jnp.matmul(r2, r1, precision=F32_PRECISION))
