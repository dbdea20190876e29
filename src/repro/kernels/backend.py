"""Backend resolution for the Pallas kernels: one descriptor, three worlds.

The seed knew exactly two execution modes — compiled Mosaic on TPU or the
Pallas interpreter everywhere else — collapsed into a single boolean.  That
made the GPU invisible: ``jax.default_backend() == "gpu"`` silently fell
into the interpreter and the whole bench trajectory measured
interpreter-CPU.  This module replaces the boolean tri-state with a
:class:`Backend` descriptor carrying everything a kernel (or the autotuner)
needs to know about the lowering it is about to take:

  * ``kind`` — the lowering family:

      - ``"tpu-mosaic"``  — compiled Mosaic kernels (sequential grid; a
        revisited output block is a legal VMEM accumulator);
      - ``"gpu-triton"``  — compiled Triton kernels via Pallas's GPU
        lowering (grid programs run in PARALLEL; accumulators must be
        per-program partials — see :mod:`repro.kernels.gpu`);
      - ``"interpret"``   — the Pallas interpreter (XLA ops, any backend;
        the CPU test/CI path).

  * ``arch`` — the concrete device kind (``"TPU v5e"``, ``"NVIDIA H100"``,
    ``"cpu"``), the autotune-table key component.
  * ``interpret`` — the flag that reaches ``pl.pallas_call``.  An explicit
    ``True``/``False`` from the caller always wins (tests pin this); when
    it forces the interpreter although a compiled backend is available, a
    one-time warning is emitted — the silent-interpretation failure mode
    this module exists to kill.
  * ``sublane`` — the row-tile alignment quantum for ``block_rows``:
    8 on TPU (f32 sublanes), 16 on GPU (half a warp; Triton block dims
    want power-of-two multiples), 8 under the interpreter (which follows
    the TPU kernel structure).

:func:`pick_block_rows` lives here (re-exported by ``gram`` for
compatibility) because the clamp is backend-derived now: panels are never
taller than sublane-rounded ``m`` and never shorter than one sublane tile.
For tiny ``m < sublane`` panels the choice is one full sublane tile — the
kernels mask the out-of-bounds rows in-kernel against a row iota, so the
padding is compute waste (bounded by ``sublane − 1`` rows), never a
correctness hazard.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax

__all__ = [
    "Backend",
    "DEFAULT_BLOCK_ROWS",
    "F32_PRECISION",
    "KINDS",
    "default_interpret",
    "dot_precision",
    "pick_block_rows",
    "resolve_backend",
    "resolve_interpret",
]

KINDS = ("tpu-mosaic", "gpu-triton", "interpret")

# The untuned streaming panel height (rows per grid step).  Re-exported by
# ``gram`` for compatibility; the autotuner treats it as the baseline
# candidate every measured search must include.
DEFAULT_BLOCK_ROWS = 1024

# The precision of every f32 matmul on the QR path, Pallas and XLA alike.
# A TPU's default f32 matmul rounds its operands to bfloat16, and CholeskyQR
# squares the condition number in its Gram, so the factors need all f32 bits.
# The CPU ignores the setting: its f32 matmul is exact f32 either way.
F32_PRECISION = jax.lax.Precision.HIGHEST


def dot_precision(dtype):
    """The precision of a Pallas kernel's dot over ``dtype`` operands:
    :data:`F32_PRECISION` for float32, the MXU's native single pass (None)
    for bfloat16, which Mosaic refuses to run at the f32 setting."""
    return F32_PRECISION if jax.numpy.dtype(dtype) == jax.numpy.float32 else None

_TPU_SUBLANE = 8
_GPU_SUBLANE = 16


@dataclasses.dataclass(frozen=True)
class Backend:
    """One resolved kernel-execution target (see module docstring)."""

    kind: str            # "tpu-mosaic" | "gpu-triton" | "interpret"
    arch: str            # device kind of device 0, e.g. "TPU v5e" / "cpu"
    interpret: bool      # the flag that reaches pl.pallas_call
    sublane: int         # block_rows alignment quantum

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"kind must be one of {KINDS}, got {self.kind!r}")

    @property
    def compiled(self) -> bool:
        return not self.interpret


def _arch() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:          # uninitialized / mocked runtime
        return jax.default_backend()


# one warning per process per platform — not one per kernel call
_FORCED_WARNED: set[str] = set()


def _warn_forced_interpret(platform: str) -> None:
    if platform in _FORCED_WARNED:
        return
    _FORCED_WARNED.add(platform)
    kind = "tpu-mosaic" if platform == "tpu" else "gpu-triton"
    warnings.warn(
        f"interpret=True forces the Pallas interpreter although the "
        f"compiled {kind} backend is available on this {platform!r} "
        "runtime — kernels will execute as XLA ops, orders of magnitude "
        "below hardware speed.  Pass interpret=None (the default) to use "
        "the compiled lowering, or silence this by really meaning it "
        "(the warning fires once per process).",
        stacklevel=3,
    )


def resolve_backend(interpret: bool | None = None) -> Backend:
    """Resolve the tri-state ``interpret`` flag into a full :class:`Backend`.

    ``None`` auto-detects: compiled Mosaic on TPU, compiled Triton on GPU,
    interpreter elsewhere.  An explicit bool always wins — ``True`` on a
    compiled-capable runtime warns once (see module docstring); ``False``
    on a runtime with no compiled lowering is honored verbatim and reaches
    ``pl.pallas_call`` (where it fails at lowering — the "explicit always
    wins" contract the kernel tests pin with a mocked ``pallas_call``).
    """
    platform = jax.default_backend()
    if interpret is None:
        interpret = platform not in ("tpu", "gpu")
    else:
        interpret = bool(interpret)
        if interpret and platform in ("tpu", "gpu"):
            _warn_forced_interpret(platform)
    if not interpret and platform == "tpu":
        return Backend("tpu-mosaic", _arch(), False, _TPU_SUBLANE)
    if not interpret and platform == "gpu":
        return Backend("gpu-triton", _arch(), False, _GPU_SUBLANE)
    return Backend("interpret", _arch(), interpret, _TPU_SUBLANE)


def default_interpret() -> bool:
    """True when auto-detection lands on the interpreter (no compiled
    backend on this runtime).  Kept for compatibility — new code should
    consult :func:`resolve_backend` for the full descriptor."""
    return resolve_backend(None).interpret


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state ``interpret`` flag: ``None`` → auto-detect."""
    return resolve_backend(interpret).interpret


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def pick_block_rows(m: int, block_rows: int, *,
                    sublane: int | None = None) -> int:
    """Clamp the streaming panel height to the backend's alignment quantum:
    never taller than (sublane-rounded) ``m``, never shorter than one
    sublane tile.  ``sublane=None`` derives the quantum from the
    auto-detected backend (8 TPU sublanes, 16 GPU rows); kernels that
    already resolved a :class:`Backend` pass its ``sublane`` explicitly.

    Tiny panels (``m < sublane``) get exactly one sublane tile: the
    kernels' in-kernel row-iota masking zeroes the out-of-bounds rows, so
    the cost is at most ``sublane − 1`` rows of masked compute — never an
    HBM pad round-trip, never a wrong result.
    """
    if sublane is None:
        sublane = resolve_backend(None).sublane
    return max(sublane, min(block_rows, _ceil_to(m, sublane)))
