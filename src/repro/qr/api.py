"""The unified QR entry facade: one config object, one ``factorize`` call.

The QR entry points grew organically — ``blocked_qr_sim`` /
``blocked_qr_batched`` / ``blocked_qr_shard_map`` and the three ``tsqr_*``
functions each carried a dozen duplicated kwargs, three of them
stringly-typed tri-states (``pipeline``/``fuse``: ``"auto"/"on"/"off"``,
``recover``: ``"replica"/"off"``) whose typos used to fall through to
driver internals.  This module is the redesign:

  * :class:`Pipeline` / :class:`Fuse` / :class:`Recover` — real enums for
    the tri-state flags, coerced and validated at every public entry with
    actionable error messages (the string spellings still work).
  * :class:`QRConfig` — ONE frozen, hashable dataclass holding every
    static policy knob.  Because it is hashable it doubles as the
    jit-cache key: the module-level ``lru_cache`` compile builders in
    :mod:`repro.qr.blocked` key on ``(geometry, config)`` instead of the
    old ad-hoc 10-tuples, so "same config" and "same compiled program"
    are the same statement.
  * :func:`factorize` — the single facade the serving layer
    (:mod:`repro.serve`) consumes.  It routes by input rank and mesh
    presence:

      ==========================  =================================
      input                       driver
      ==========================  =================================
      (P, m_local, n), no mesh    blocked QR, simulated ranks
      (B, P, m_local, n), no mesh batched blocked QR — one dispatch
      (m, n) + mesh               blocked QR under ``shard_map``
      any of the above with       single-panel TSQR (the paper's
      ``panel_width=None``        tall-and-skinny workload)
      ==========================  =================================

The legacy kwarg entry points remain as thin delegating shims that emit
``DeprecationWarning`` (see :mod:`repro.qr.blocked` / :mod:`repro.qr.tsqr`);
ruff's banned-api rule fails new uses of them outside the shim modules.
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
import warnings

from repro.collective.faults import FaultSpec
from repro.collective.plan import VARIANTS
from repro.kernels import dispatch as _dispatch

__all__ = [
    "Fuse",
    "Pipeline",
    "QRConfig",
    "Recover",
    "Redundancy",
    "factorize",
]


# ---------------------------------------------------------------------------
# Enums for the tri-state flags
# ---------------------------------------------------------------------------

class _CoercibleEnum(enum.Enum):
    """Enum with string coercion and an actionable failure mode."""

    @classmethod
    def coerce(cls, value):
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            try:
                return cls(value.lower())
            except ValueError:
                pass
        options = ", ".join(
            f"{cls.__name__}.{m.name} ({m.value!r})" for m in cls
        )
        raise ValueError(
            f"{cls.__name__.lower()} must be one of: {options}; "
            f"got {value!r}.  Import the enum from repro.qr.api "
            "(string spellings are accepted case-insensitively)."
        )


class Pipeline(_CoercibleEnum):
    """Scan-compiled single-program pipeline vs the eager per-panel driver.

    ``AUTO`` compiles fault-free runs into the one-dispatch pipeline and
    falls back to the eager general driver whenever any plan carries
    faults; ``ON`` demands the pipeline (raises on faulty plans); ``OFF``
    forces the eager driver (the bit-identity oracle).
    """

    AUTO = "auto"
    ON = "on"
    OFF = "off"


class Fuse(_CoercibleEnum):
    """One stacked butterfly per panel vs the split two-butterfly schedule.

    ``AUTO`` fuses every panel the fault schedule allows; ``ON`` demands
    fusion everywhere (raises when update-phase faults are scheduled);
    ``OFF`` restores the serialized two-butterfly schedule (bit-identical
    results either way — DESIGN.md §10).
    """

    AUTO = "auto"
    ON = "on"
    OFF = "off"


class Recover(_CoercibleEnum):
    """Replica-fetch restoration of ranks lost inside a panel reduction.

    ``REPLICA`` (default) restores invalid ranks from butterfly replicas at
    phase boundaries; ``OFF`` demonstrates the honest NaN-cascade of
    running without recovery.
    """

    REPLICA = "replica"
    OFF = "off"


class Redundancy(_CoercibleEnum):
    """Which fault-tolerance scheme backs the panel reductions.

    ``BUTTERFLY`` (default) is the paper's scheme: full replicas of every
    intermediate R ride the recursive-doubling exchanges, tolerating
    ``2^s - 1`` fail-stop deaths at 100% redundancy overhead.  ``CODED``
    is the checksum-coded scheme (DESIGN.md §12): ``parity`` extra ranks
    hold Cauchy-weighted linear combinations of the local factors, so up
    to ``parity`` lost, straggling, *or silently-corrupted* contributions
    are reconstructed from parity — at an overhead of ``c/P`` extra
    payload instead of the butterfly's ``(P-1)×``, and with numerical
    verification that *detects* SDC replication propagates silently.
    """

    BUTTERFLY = "butterfly"
    CODED = "coded"


# ---------------------------------------------------------------------------
# The config
# ---------------------------------------------------------------------------

_LOCAL_R = ("auto", "chol", "jnp", "cqr2", "cqr2_pallas")


@dataclasses.dataclass(frozen=True)
class QRConfig:
    """Every static policy knob of a QR factorization, in one frozen value.

    ``panel_width=None`` selects the single-panel TSQR workload (the whole
    matrix is one panel); an int selects the right-looking blocked driver.
    ``local_r="auto"`` resolves per workload — ``"chol"`` (zero-extra-sweep
    lookahead Gram) for blocked, ``"jnp"`` (Householder) for TSQR.
    ``gram=True`` selects the Gram-butterfly TSQR (shard_map only).

    The instance is hashable and serves directly as the jit-cache key of
    the module-level compile builders: two calls with equal configs and
    equal geometry share one compiled program.
    """

    panel_width: int | None = None
    variant: str = "redundant"
    local_r: str = "auto"
    reorth: int = 1
    compute_q: bool = False
    use_pallas: bool = False
    interpret: bool | None = None
    block_rows: int | None = None
    pipeline: Pipeline = Pipeline.AUTO
    fuse: Fuse = Fuse.AUTO
    recover: Recover = Recover.REPLICA
    gram: bool = False
    redundancy: Redundancy = Redundancy.BUTTERFLY
    parity: int = 2

    def __post_init__(self):
        coerce = object.__setattr__
        coerce(self, "pipeline", Pipeline.coerce(self.pipeline))
        coerce(self, "fuse", Fuse.coerce(self.fuse))
        coerce(self, "recover", Recover.coerce(self.recover))
        coerce(self, "redundancy", Redundancy.coerce(self.redundancy))
        if self.panel_width is not None and self.panel_width <= 0:
            raise ValueError(
                f"panel_width must be a positive int or None (single-panel "
                f"TSQR), got {self.panel_width!r}"
            )
        if self.variant not in VARIANTS:
            raise ValueError(
                f"unknown variant {self.variant!r}; choose from {VARIANTS}"
            )
        if isinstance(self.local_r, str) and self.local_r not in _LOCAL_R:
            raise ValueError(
                f"unknown local_r {self.local_r!r}; choose from {_LOCAL_R} "
                "or pass a callable mapping a panel to its R factor"
            )
        if self.reorth < 0:
            raise ValueError(f"reorth must be >= 0, got {self.reorth}")
        if self.block_rows is not None and self.block_rows <= 0:
            raise ValueError(
                f"block_rows must be a positive int (an explicit Pallas "
                f"streaming panel height) or None (autotuned per "
                f"shape-class), got {self.block_rows!r}"
            )
        if self.gram and self.panel_width is not None:
            raise ValueError(
                "gram=True selects the Gram-butterfly TSQR, which factors "
                "the whole matrix as one panel — it is incompatible with "
                f"panel_width={self.panel_width} (use panel_width=None)"
            )
        if self.panel_width is None and self.local_r == "chol":
            raise ValueError(
                "local_r='chol' derives the panel R from the blocked "
                "driver's lookahead Gram accumulator, which the single-panel "
                "TSQR does not run; use local_r='auto'/'jnp'/'cqr2'/"
                "'cqr2_pallas', or gram=True for the Gram-butterfly TSQR"
            )
        if self.parity < 1:
            raise ValueError(
                f"parity must be >= 1 (the number of checksum ranks the "
                f"coded scheme adds), got {self.parity}"
            )
        if self.redundancy is Redundancy.CODED:
            if self.gram:
                raise ValueError(
                    "redundancy='coded' codes the per-rank R contributions; "
                    "the Gram-butterfly TSQR reduces a Gram matrix over the "
                    "butterfly instead — the two schemes do not compose "
                    "(use gram=False)"
                )
            if self.pipeline is Pipeline.ON:
                raise ValueError(
                    "pipeline='on' demands the scan-compiled butterfly "
                    "pipeline, which is replica-redundancy only; the coded "
                    "scheme runs the eager per-panel driver (use "
                    "pipeline='auto' or 'off')"
                )

    # -- resolution helpers -------------------------------------------------

    def resolved_local_r(self) -> str:
        """Concrete local factorization for the selected workload."""
        if self.local_r != "auto":
            return self.local_r
        return "chol" if self.panel_width is not None else "jnp"

    def canonical(self) -> "QRConfig":
        """The compile-relevant projection of this config — used as the
        jit-cache key, so knobs that do not change the traced program
        (``pipeline`` mode, ``recover`` policy) are normalized away and
        ``local_r="auto"`` is resolved.  Two configs with equal
        ``canonical()`` share one compiled pipeline."""
        return dataclasses.replace(
            self,
            local_r=self.resolved_local_r(),
            pipeline=Pipeline.AUTO,
            recover=Recover.REPLICA,
            # block_rows only shapes Pallas kernel tiling — the jnp oracles
            # have no streaming panels, so it must not split their cache key
            block_rows=self.block_rows if self.use_pallas else None,
            # AUTO and ON trace the same fused program (ON only tightens
            # host-side validation); OFF is the split-schedule program
            fuse=Fuse.OFF if self.fuse is Fuse.OFF else Fuse.AUTO,
            # parity only shapes the traced program under the coded scheme
            parity=self.parity if self.redundancy is Redundancy.CODED else 2,
        )

    def factorizer(self):
        """The :class:`~repro.qr.panel.PanelFactorizer` this config implies."""
        from .panel import PanelFactorizer

        local_r = self.resolved_local_r()
        return PanelFactorizer(
            local_qr="jnp" if local_r == "chol" else local_r,
            reorth=self.reorth,
            interpret=self.interpret,
        )


# ---------------------------------------------------------------------------
# Deprecation machinery for the legacy kwarg entry points
# ---------------------------------------------------------------------------

def warn_deprecated_entry(name: str) -> None:
    warnings.warn(
        f"{name}() is deprecated: build a repro.qr.api.QRConfig and call "
        "repro.qr.api.factorize(a, config) instead (same drivers, same "
        "results — the legacy kwargs map 1:1 onto QRConfig fields; see the "
        "migration table in README.md)",
        DeprecationWarning,
        stacklevel=3,
    )


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------

# Process-wide call numbers: the ``call`` count of each ``repro.factorize``
# span, which the spans nested in it share.
_CALLS = itertools.count()


def _route_error(a, mesh) -> str:
    return (
        f"cannot route input of shape {getattr(a, 'shape', None)} with "
        f"mesh={'present' if mesh is not None else 'absent'}: factorize "
        "expects (P, m_local, n) row blocks or a batched (B, P, m_local, n) "
        "stack without a mesh, or a global (m, n) matrix with mesh= (and "
        "its row-sharding axis=)"
    )


def factorize(
    a,
    config: QRConfig | None = None,
    *,
    mesh=None,
    axis: str | None = None,
    faults=None,
    jit: bool = True,
):
    """Factorize ``a`` under ``config`` — the one QR entry point.

    Routing is by input rank and mesh presence (see the module table):
    3-D input is P row blocks on simulated ranks, 4-D is a batch of B such
    stacks drained in ONE device dispatch, and 2-D input with ``mesh=``
    runs under ``shard_map`` row-sharded over ``axis`` (defaulting to the
    mesh's sole axis).  ``config.panel_width=None`` selects the
    single-panel TSQR workload; an int selects the blocked driver.

    ``faults`` is the per-call fault injection: a
    :class:`~repro.collective.faults.FaultSpec` for TSQR, a
    :class:`~repro.qr.blocked.PanelFaultSchedule` for the blocked driver
    (validated — passing the wrong kind is an error, not silence).
    Returns :class:`~repro.qr.tsqr.TSQRResult` or
    :class:`~repro.qr.blocked.BlockedQRResult` accordingly.
    """
    from . import blocked as _blocked

    if config is None:
        config = QRConfig()
    elif not isinstance(config, QRConfig):
        raise TypeError(
            f"config must be a repro.qr.api.QRConfig, got "
            f"{type(config).__name__} — construct one (all fields have "
            "defaults) rather than passing loose kwargs"
        )
    tsqr_mode = config.panel_width is None
    deaths = 0
    if faults is not None:
        want = FaultSpec if tsqr_mode else _blocked.PanelFaultSchedule
        if not isinstance(faults, want):
            raise TypeError(
                f"faults must be a {want.__name__} for this workload "
                f"(panel_width={config.panel_width}), got "
                f"{type(faults).__name__}"
            )
        specs = [faults] if tsqr_mode else [*faults.panel.values(),
                                            *faults.update.values()]
        deaths = sum(len(spec.deaths) for spec in specs)
    shape = getattr(a, "shape", ())
    panels = 0 if tsqr_mode or not shape else -(-shape[-1] // config.panel_width)
    with _dispatch.span(_dispatch.FACTORIZE, call=next(_CALLS), panels=panels,
                        deaths=deaths):
        return _route(a, config, mesh, axis, faults, jit)


def _route(a, config: QRConfig, mesh, axis, faults, jit: bool):
    """The driver ``factorize`` routes ``a`` to (see the module table)."""
    from . import blocked as _blocked
    from . import tsqr as _tsqr

    tsqr_mode = config.panel_width is None
    if mesh is not None:
        if config.redundancy is Redundancy.CODED:
            raise ValueError(
                "redundancy='coded' is a simulated-ranks scheme: the coded "
                "world holds P data ranks plus `parity` checksum ranks, and "
                "the decode indexes the gather root's row — neither maps "
                "onto the fixed-size shard_map mesh; run the 3-D simulated "
                "entry (or redundancy='butterfly' under the mesh)"
            )
        if getattr(a, "ndim", None) != 2:
            raise ValueError(_route_error(a, mesh))
        if axis is None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"mesh has axes {mesh.axis_names}; pass axis= to pick "
                    "the row-sharding axis"
                )
            axis = mesh.axis_names[0]
        if tsqr_mode:
            if config.gram:
                return _tsqr._factorize_gram_shard(
                    a, config, mesh=mesh, axis=axis, jit=jit
                )
            return _tsqr._factorize_shard(
                a, config, mesh=mesh, axis=axis, fault_spec=faults, jit=jit
            )
        return _blocked._factorize_shard_map(
            a, config, mesh=mesh, axis=axis, faults=faults, jit=jit
        )

    if config.gram:
        raise ValueError(
            "gram=True (the Gram-butterfly TSQR) is a shard_map-only "
            "driver; pass mesh= (and axis=), or use gram=False"
        )
    ndim = getattr(a, "ndim", None)
    if ndim == 3:
        if tsqr_mode:
            return _tsqr._factorize_sim(a, config, fault_spec=faults)
        return _blocked._factorize_sim(a, config, faults=faults)
    if ndim == 4:
        if config.redundancy is Redundancy.CODED:
            raise ValueError(
                "batched factorization is the fault-free hot path, where "
                "coded parity buys nothing over the plain butterfly; use "
                "redundancy='butterfly' for batches, or factor matrices "
                "one at a time through the 3-D entry for coded runs"
            )
        if faults is not None:
            raise ValueError(
                "batched factorization is the fault-free hot path (a real "
                "fleet replans at step boundaries); serve faulted batches "
                "matrix-by-matrix through the 3-D entry instead — that is "
                "exactly what repro.serve does on a mid-flight fault"
            )
        if tsqr_mode:
            return _tsqr._factorize_batched(a, config)
        return _blocked._factorize_batched(a, config)
    raise ValueError(_route_error(a, mesh))
