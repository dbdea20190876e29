"""Trace-time HBM traffic model for the CQR2 kernel pipeline.

The fused-pipeline claim of DESIGN.md §Kernels — CholeskyQR2's R factor in
**2** HBM sweeps over the tall operand instead of the seed's 4 — is gated
as a hard benchmark metric (``repro.bench.cases.kernels``), so it needs a
measurement, not an assertion-by-construction.  Because every kernel's
routing is static (shapes known at trace time, one ``pallas_call`` per
streamed sweep), the public wrappers in :mod:`repro.kernels.ops` can report
their exact traffic as they are called: each wrapper notes the bytes it
streams from/to HBM and whether the call is a *sweep* over a tall operand
(the (m, n) panel stream; the n×n Cholesky/inverse work is not).

Usage::

    with track_traffic() as t:
        ops.cholesky_qr2_r(a, use_pallas=True)
    assert t.tall_sweeps == 2

Counting happens at Python call time in the ``ops`` wrappers (outside any
``jit``), so call the pipeline un-jitted when measuring; the model is the
same traffic a compiled TPU execution commits to, since the block streaming
is fixed by the BlockSpecs.

Scope (DESIGN.md §9): per-call accounting is exact for the sim drivers
(wrappers run per call) and for the scan-compiled blocked-QR pipeline
(whose entry point notes its own K-sweep totals).  Kernel calls made
*inside* a cached ``shard_map`` body note at trace time only — a warm
repeat of those entry points records nothing, because the body never
re-executes (that the seed noted per call there was an artifact of its
per-call ``jax.jit(shard)`` rebuild, i.e. of the retrace bug itself).
"""
from __future__ import annotations

import contextlib
import dataclasses

__all__ = ["KernelTraffic", "note", "suppress", "track_traffic", "tracking"]


@dataclasses.dataclass
class KernelTraffic:
    """Accumulated per-op HBM traffic records."""

    records: list[dict] = dataclasses.field(default_factory=list)

    @property
    def tall_sweeps(self) -> int:
        """Number of HBM sweeps over a tall (panel-streamed) operand."""
        return sum(r["sweeps"] for r in self.records)

    def sweeps_of(self, *ops: str) -> int:
        """Tall sweeps attributed to the named ops only — e.g. the blocked-QR
        trailing-block accounting counts ``panel_cross`` + ``trailing_update``
        and excludes the narrow panel-local factorization sweeps."""
        wanted = set(ops)
        return sum(r["sweeps"] for r in self.records if r["op"] in wanted)

    @property
    def read_bytes(self) -> int:
        return sum(r["read_bytes"] for r in self.records)

    @property
    def write_bytes(self) -> int:
        return sum(r["write_bytes"] for r in self.records)

    @property
    def total_bytes(self) -> int:
        return self.read_bytes + self.write_bytes

    @property
    def dispatches(self) -> int:
        """Compiled-program launches recorded alongside the bytes (each
        eager kernel wrapper is one jitted call = one device dispatch; the
        scan-compiled pipeline records 1 for the whole factorization)."""
        return sum(r["dispatches"] for r in self.records)

    @property
    def traces(self) -> int:
        """New jit traces the recorded calls caused (0 on warm calls)."""
        return sum(r["traces"] for r in self.records)

    @property
    def collective_rounds(self) -> int:
        """Serial butterfly rounds committed by the recorded collectives —
        the latency proxy (one record per butterfly; the blocked drivers
        note one ``panel_reduce`` per panel plus ``reorth_reduce`` polish
        rounds, priced from the host plans)."""
        return sum(r["rounds"] for r in self.records)

    def rounds_of(self, *ops: str) -> int:
        """Collective rounds attributed to the named ops only — the
        ``overlap`` bench case gates ``rounds_of("panel_reduce")`` at
        exactly ``log P`` per panel on the fused path."""
        wanted = set(ops)
        return sum(r["rounds"] for r in self.records if r["op"] in wanted)

    @property
    def wire_bytes(self) -> int:
        """Collective payload bytes committed by the recorded reductions
        (plan-priced: packed symmetric leaves, dense rectangular leaves)."""
        return sum(r["wire_bytes"] for r in self.records)

    def wire_bytes_of(self, *ops: str) -> int:
        wanted = set(ops)
        return sum(
            r["wire_bytes"] for r in self.records if r["op"] in wanted
        )

    @property
    def shifted_sweeps(self) -> int:
        """Trailing-update sweeps that wrote their block back shifted left
        by the finished panel's width, in the same pass (the scan
        pipeline's padded working matrix), rather than into a separate
        narrower array that XLA then slices and re-pads."""
        return sum(r["shifted"] for r in self.records)

    @property
    def overlapped(self) -> int:
        """Reductions issued against lookahead accumulators *during* the
        previous panel's trailing sweep (the double-buffered pipeline's
        comm/compute overlap depth — K−1 for a K-panel fused run, 0 for the
        serialized two-butterfly schedule)."""
        return sum(r["overlapped"] for r in self.records)

    def as_dict(self) -> dict:
        return {
            "tall_sweeps": self.tall_sweeps,
            "read_bytes": self.read_bytes,
            "write_bytes": self.write_bytes,
            "dispatches": self.dispatches,
            "traces": self.traces,
            "collective_rounds": self.collective_rounds,
            "wire_bytes": self.wire_bytes,
            "overlapped": self.overlapped,
            "shifted_sweeps": self.shifted_sweeps,
            "ops": [r["op"] for r in self.records],
        }


_ACTIVE: list[KernelTraffic] = []
_SUPPRESS: list[bool] = []


def note(op: str, *, sweeps: int = 0, read_bytes: int = 0,
         write_bytes: int = 0, dispatches: int = 1, traces: int = 0,
         rounds: int = 0, wire_bytes: int = 0, overlapped: int = 0,
         shifted: int = 0) -> None:
    """Record one kernel invocation into every active tracker (no-op when
    nothing is tracking — the hot path pays one list check).

    ``dispatches``/``traces`` ride alongside the bytes: a plain wrapper call
    is one compiled-program launch (default 1); callers that know better —
    the scan pipeline records its K-panel traffic as several byte records
    but a single dispatch — pass explicit counts.

    ``rounds``/``wire_bytes``/``overlapped`` account collectives: serial
    butterfly rounds the record commits, plan-priced payload bytes on the
    wire, and whether the reduction was issued against lookahead
    accumulators under the previous panel's trailing sweep.  The blocked
    drivers note one ``panel_reduce`` record per butterfly with
    ``dispatches=0, sweeps=0`` so the collective accounting never perturbs
    the HBM-sweep and single-dispatch gates.

    ``shifted`` marks a trailing-update sweep that wrote its block back
    shifted left in the same pass (:attr:`KernelTraffic.shifted_sweeps`).
    """
    if not _ACTIVE or _SUPPRESS:
        return
    rec = {
        "op": op,
        "sweeps": int(sweeps),
        "read_bytes": int(read_bytes),
        "write_bytes": int(write_bytes),
        "dispatches": int(dispatches),
        "traces": int(traces),
        "rounds": int(rounds),
        "wire_bytes": int(wire_bytes),
        "overlapped": int(overlapped),
        "shifted": int(shifted),
    }
    for t in _ACTIVE:
        t.records.append(rec)


def tracking() -> bool:
    """Would :func:`note` record anything here?"""
    return bool(_ACTIVE) and not _SUPPRESS


@contextlib.contextmanager
def track_traffic():
    """Context manager yielding a :class:`KernelTraffic` that observes every
    ``ops``-level kernel call made inside the block."""
    t = KernelTraffic()
    _ACTIVE.append(t)
    try:
        yield t
    finally:
        _ACTIVE.remove(t)


@contextlib.contextmanager
def suppress():
    """Drop :func:`note` calls inside the block.  The scan-compiled pipeline
    wraps its compiled-function invocation with this: any kernel wrapper
    reached while *tracing* the body (e.g. a ``cqr2`` local QR) would note
    once per trace instead of once per panel per call — the pipeline entry
    point notes its own exact per-call totals instead."""
    _SUPPRESS.append(True)
    try:
        yield
    finally:
        _SUPPRESS.pop()
