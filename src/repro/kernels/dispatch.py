"""Trace- and dispatch-count accounting for the compiled hot paths.

The single-program claims of DESIGN.md §9 are *numbers*, so — like the HBM
model in :mod:`repro.kernels.traffic` — they need a measurement, not an
assertion-by-construction:

  * **traces** — how many times a guarded entry point's Python body was
    re-traced by ``jax.jit``.  Every guarded body calls :func:`note_trace`
    as its first statement; because a jitted function's Python body only
    executes while tracing, the global per-name counter increments exactly
    once per (re)compilation.  A second call with identical
    ``(plan, combiner, treedef, shapes)`` must add **zero** — that is the
    zero-retrace contract the ``dispatch`` bench case and the CI
    retrace-guard step pin.
  * **dispatches** — how many compiled XLA programs a factorization
    launches.  Each jitted-callable invocation is one device dispatch; the
    public wrappers call :func:`note_dispatch` per call (Python-level, so
    the count is exact whether or not the call hit the jit cache).  The
    scan-compiled blocked-QR pipeline dispatches **1** program per
    factorization independent of the panel count; the eager per-panel
    driver dispatches O(K).

Usage::

    with track_dispatch() as d:
        blocked_qr_sim(a, panel_width=128)
    assert d.dispatches["blocked_qr_pipeline"] == 1

    before = trace_count("blocked_qr_pipeline")
    blocked_qr_sim(a, panel_width=128)        # same shapes again
    assert trace_count("blocked_qr_pipeline") == before   # zero retrace

The global trace counters are monotonic for the life of the process (they
survive ``track_dispatch`` scopes), so retrace guards compare deltas.

**Spans.**  :func:`span` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: a host span in the profiler's own trace, on the clock
of its device planes, carrying small integer counts as its stats.  With
no profiler session it records nothing.  While a body is traced, the
same name also enters the JAX name stack (``jax.named_scope``), so the
device operations of a compiled body carry it in their ``op_name``
metadata.  The names are the constants below: one vocabulary for the
host spans and the device scopes of a ``factorize`` call.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses

import jax

__all__ = [
    "DispatchStats",
    "note_dispatch",
    "note_trace",
    "span",
    "suppress",
    "trace_count",
    "track_dispatch",
]

SPAN_PREFIX = "repro."

# The span vocabulary, from the entry point down (PERF.md §3).
FACTORIZE = "factorize"              # one whole factorize call
PLAN = "plan"                        # host planning and accounting
LAUNCH = "launch"                    # a compiled whole-factorization program
PANEL = "panel"                      # one panel of the eager blocked driver
LOCAL_R = "local_r"                  # each rank's R before the butterfly
REDUCE = "reduce"                    # one butterfly (or coded) reduction
EXCHANGE = "exchange"                # one permutation between ranks
RECOVER = "recover"                  # restoring lost ranks from replicas
FORM_Q = "form_q"                    # explicit Q and its polish passes
BLOCK_ROW = "block_row"              # the panel's block row of R
TRAILING_UPDATE = "trailing_update"  # the sweep over the trailing block

# Monotonic per-name trace counts for the whole process (retrace guards
# compare before/after deltas; never reset).
_TRACES: collections.Counter = collections.Counter()


@dataclasses.dataclass
class DispatchStats:
    """Per-scope counters collected by :func:`track_dispatch`."""

    traces: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    dispatches: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )

    @property
    def n_traces(self) -> int:
        return sum(self.traces.values())

    @property
    def n_dispatches(self) -> int:
        return sum(self.dispatches.values())

    def as_dict(self) -> dict:
        return {
            "traces": dict(self.traces),
            "dispatches": dict(self.dispatches),
        }


_ACTIVE: list[DispatchStats] = []


def note_trace(name: str) -> None:
    """Record one (re)trace of the named entry point.  Call as the first
    statement of a jitted body — it only executes while tracing."""
    _TRACES[name] += 1
    for t in _ACTIVE:
        t.traces[name] += 1


_SUPPRESS: list[bool] = []


def note_dispatch(name: str, n: int = 1) -> None:
    """Record ``n`` compiled-program launches for the named entry point
    (no-op when nothing is tracking or inside :func:`suppress`)."""
    if not _ACTIVE or _SUPPRESS:
        return
    for t in _ACTIVE:
        t.dispatches[name] += n


def trace_count(name: str | None = None) -> int:
    """Process-lifetime trace count — total, or for one entry point."""
    if name is None:
        return sum(_TRACES.values())
    return _TRACES[name]


@contextlib.contextmanager
def track_dispatch():
    """Context manager yielding a :class:`DispatchStats` that observes every
    guarded entry point entered inside the block."""
    t = DispatchStats()
    _ACTIVE.append(t)
    try:
        yield t
    finally:
        _ACTIVE.remove(t)


@contextlib.contextmanager
def suppress():
    """Drop :func:`note_dispatch` calls inside the block (the pipeline
    invokes its compiled function under this so wrappers reached at trace
    time don't count phantom launches).  :func:`note_trace` is *not*
    suppressed — trace counters are process-lifetime facts the retrace
    guards rely on."""
    _SUPPRESS.append(True)
    try:
        yield
    finally:
        _SUPPRESS.pop()


class span:
    """``with span(name, **counts):`` — the span ``repro.<name>`` around the
    block, with ``counts`` (small ints the caller already holds) as its
    stats.  While a body is traced the name also enters the name stack.
    Without a profiler session and outside a trace it records nothing."""

    __slots__ = ("_annotation", "_scope")

    def __init__(self, name: str, **counts: int):
        name = SPAN_PREFIX + name
        self._annotation = jax.profiler.TraceAnnotation(name, **counts)
        self._scope = (
            None if jax.core.trace_ctx.is_top_level() else jax.named_scope(name)
        )

    def __enter__(self):
        self._annotation.__enter__()
        if self._scope is not None:
            self._scope.__enter__()
        return self._annotation

    def __exit__(self, *exc):
        if self._scope is not None:
            self._scope.__exit__(*exc)
        self._annotation.__exit__(*exc)
