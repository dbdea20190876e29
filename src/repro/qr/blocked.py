"""Fault-tolerant right-looking blocked QR for general m×n matrices.

Coti's follow-on to the TSQR paper ("Fault Tolerant QR Factorization for
General Matrices", arXiv:1604.02504) extends the redundant-computation
trick beyond tall-and-skinny: use TSQR as the *panel* factorization inside
a right-looking blocked QR, and the butterfly's ``2^s``-copy redundancy
protects every panel's reduced factors for free.  This driver implements
that on the repo's collective engine:

  per column panel ``k`` (width ``b``):
    1. **Panel TSQR** — each rank's local R of the panel block rides the
       fault-tolerant butterfly (QR combiner, any variant/plan); every
       valid rank ends holding the identical global ``R_kk``.  The
       redundant copies double as the fault-tolerance replicas — the
       "broadcast" of the implicit panel factor costs nothing extra.
    2. **Explicit panel Q** — ``Q_k = A_panel R_kk⁻¹`` locally (plus
       ``reorth`` CholeskyQR polish passes over the same butterfly).
    3. **Block row of R** — ``W = R_totᵀ⁻¹ · Σ_ranks A_panelᵀ A_trail``:
       the cross products ride the *same* butterfly as the panel R by
       default (``fuse="auto"``): a stacked ``(R, Σ AᵖᵀAᵗ)`` payload under
       one plan costs ``log P`` rounds per panel instead of the ``2·log P``
       of two serialized butterflies, and the replica copies of the stacked
       tuple double as fault-tolerance copies for *both* leaves (one
       :func:`~repro.collective.engine.replica_fetch` restores R and the
       cross products together).  ``fuse="off"`` restores the split
       schedule — a second ``sum`` butterfly after Q formation —
       bit-identical results either way (DESIGN.md §10).
    4. **Trailing update** — ``A_trail ← A_trail − Q_k W`` by the fused
       Pallas kernel (:mod:`repro.kernels.trailing_update`), which also
       accumulates the *next* panel's Gram + cross products in the same
       pass.  The trailing block is touched exactly **once per panel**
       (hard-gated by the ``general_qr`` bench case); panel-local reads
       are narrow (m×b).

**Failure semantics, per panel** (DESIGN.md §8): a death during phase 1 or
phase 3 follows the variant's butterfly guarantee (``2^s − 1`` at entry of
exchange ``s``).  Ranks that lose a replicated factor are restored at the
phase boundary via :func:`~repro.collective.engine.replica_fetch` — the
blocked-QR analogue of Self-Healing's respawn, hoisted to the panel
boundary where a real runtime replans (``recover="replica"``, default).
With ``recover="off"`` the honest no-recovery consequence is observable:
the NaN-poisoned rank corrupts every later panel's reduction — exactly why
the general-matrix paper needs a recovery story at all.  ``valid`` reports
the *strict survivors* (ranks valid through every reduction with no
replica fetch); ``reports`` carries the per-panel tolerance verdicts and
recovery counts.

**Compilation model** (DESIGN.md §9): the eager per-panel loop above is
the *fault* path.  Fault-free runs auto-dispatch to the scan-compiled
fixed-shape pipeline — padded maximal trailing width, shifted layout, one
``lax.scan`` trace for all uniform panels plus a static ragged epilogue —
which executes the whole factorization as ONE jitted device program,
bit-identical to the eager driver, with module-level cached compiles
(zero retrace on repeat calls) and a ``vmap``-batched B-matrix variant
(:func:`blocked_qr_batched`).  Under the default fused schedule the
pipeline is *double-buffered*: each panel's single stacked butterfly is
issued the moment the producing trailing sweep lands its lookahead
accumulators and consumed one scan stage later (the pending reduction
rides the carry), decoupling every collective from its consumer by a full
stage.  Trace/dispatch counts, per-panel collective rounds and overlap
depth are measured by :mod:`repro.kernels.dispatch` /
:mod:`repro.kernels.traffic` and hard-gated by the ``dispatch`` and
``overlap`` bench cases.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.collective.coded import CodedPlan, execute_coded, make_coded_plan
from repro.collective.comm import Comm, ShardMapComm, SimComm
from repro.collective.engine import ft_allreduce, recover_payload
from repro.collective.faults import FaultSpec, within_tolerance
from repro.collective.plan import Plan, make_plan
from repro.kernels import autotune as _autotune
from repro.kernels import dispatch as _dispatch
from repro.kernels import ops as kops
from repro.kernels import traffic as _traffic
from repro.kernels.backend import resolve_backend

from ._shard import dummy_q, shard_compile
from .api import (
    Fuse, Pipeline, QRConfig, Recover, Redundancy, warn_deprecated_entry,
)
from .panel import FUSED_PANEL_COMBINER, PanelFactorizer, chol_r

__all__ = [
    "PanelFaultSchedule",
    "PanelReport",
    "BlockedQRResult",
    "blocked_qr_sim",
    "blocked_qr_batched",
    "blocked_qr_shard_map",
    "panel_widths",
]

PIPELINE_NAME = "blocked_qr_pipeline"    # trace/dispatch counter key


def panel_widths(n: int, panel_width: int) -> tuple[int, ...]:
    """Column widths of the ``⌈n / panel_width⌉`` panels (ragged tail)."""
    if panel_width <= 0:
        raise ValueError(f"panel_width must be positive, got {panel_width}")
    k = math.ceil(n / panel_width)
    return tuple(
        min(panel_width, n - i * panel_width) for i in range(k)
    )


@dataclasses.dataclass(frozen=True)
class PanelFaultSchedule:
    """Fail-stop deaths scheduled into a blocked factorization.

    ``panel[k]`` strikes during panel ``k``'s TSQR reduction (phase 1);
    ``update[k]`` during its cross-product reduction (phase 3 — "death
    during the trailing update": the local subtraction has no communication,
    so the W butterfly is where a mid-update death is observable).  Each
    value is a :class:`~repro.collective.faults.FaultSpec` whose steps index
    that butterfly's exchanges.
    """

    panel: Mapping[int, FaultSpec] = dataclasses.field(default_factory=dict)
    update: Mapping[int, FaultSpec] = dataclasses.field(default_factory=dict)

    @classmethod
    def of(cls, panel=None, update=None) -> "PanelFaultSchedule":
        """From ``{panel_index: FaultSpec | {rank: step}}`` mappings."""

        def norm(d):
            return {
                int(k): v if isinstance(v, FaultSpec) else FaultSpec.of(v)
                for k, v in (d or {}).items()
            }

        return cls(panel=norm(panel), update=norm(update))

    def __bool__(self) -> bool:
        return bool(self.panel) or bool(self.update)


@dataclasses.dataclass(frozen=True)
class PanelReport:
    """Host-side verdicts for one panel (the guarantee bookkeeping).

    ``fused`` — this panel rides the single-butterfly double-buffered
    schedule: its R and cross-product leaves ship as one stacked payload
    over ``plan_r`` (``log P`` rounds instead of ``2·log P``), issued the
    moment the producing trailing sweep lands its lookahead accumulators
    and consumed one pipeline stage later.  The last panel has no cross
    leaf; its ``fused`` bit records that its R-only reduction is issued
    ahead on the same schedule.  A panel with an update-phase fault cannot
    fuse — the scheduled death indexes the second butterfly's exchanges,
    so that butterfly must exist (the split schedule).
    """

    panel: int
    plan_r: Plan | CodedPlan
    plan_w: Plan | CodedPlan | None
    within_tolerance_r: bool
    within_tolerance_w: bool
    recovered_r: int          # contributions restored after phase 1
    recovered_w: int          # …after phase 3
    recoverable: bool         # some rank held every replicated factor
    fused: bool = False       # one stacked butterfly, issued one stage ahead
    scheme: str = "butterfly"  # which redundancy scheme recovered_* used:
    #   "butterfly" — invalid ranks re-fetched full replicas at the phase
    #   boundary; "coded" — erased contributions (deaths, stragglers,
    #   declared corruptions) reconstructed from Cauchy parity *inside*
    #   the collective (recovered_* counts reconstructed contributions).

    @property
    def within_tolerance(self) -> bool:
        return self.within_tolerance_r and self.within_tolerance_w


@dataclasses.dataclass
class BlockedQRResult:
    """Outcome of a fault-tolerant blocked QR.

    ``r``      — (P, n, n) in sim / per-device (n, n) under shard_map: the
                 assembled upper-triangular factor (replicated row blocks).
    ``valid``  — (P,) strict survivors: valid through every panel's
                 reductions without replica recovery.
    ``q``      — optional per-rank (m_local, n) explicit orthonormal factor.
    ``reports``— per-panel :class:`PanelReport` (tolerance + recovery).
    ``detected`` — coded runs only: (P,) device bool, OR over all panels,
                 flagging ranks whose payload failed checksum verification.
    """

    r: jax.Array
    valid: jax.Array
    q: jax.Array | None
    reports: tuple[PanelReport, ...]
    panel_width: int
    detected: jax.Array | None = None

    @property
    def n_panels(self) -> int:
        return len(self.reports)

    @property
    def recoverable(self) -> bool:
        return all(rep.recoverable for rep in self.reports)


# Registered as a pytree (arrays as leaves, host reports as static aux) so
# results flow through jax transformations — `jax.vmap(blocked_qr_sim …)`
# batches B independent factorizations directly.
jax.tree_util.register_pytree_node(
    BlockedQRResult,
    lambda res: (
        (res.r, res.valid, res.q, res.detected),
        (res.reports, res.panel_width),
    ),
    lambda aux, ch: BlockedQRResult(
        r=ch[0], valid=ch[1], q=ch[2], detected=ch[3],
        reports=aux[0], panel_width=aux[1],
    ),
)


def _data_valid(plan) -> np.ndarray:
    """Per-*data*-rank slice of ``final_valid`` — coded plans append parity
    rows the driver's validity logic must not see."""
    return plan.final_valid[: getattr(plan, "n_data", plan.n_ranks)]


# ---------------------------------------------------------------------------
# Host-side planning
# ---------------------------------------------------------------------------

def _build_reports(
    variant: str,
    p: int,
    widths: tuple[int, ...],
    faults: PanelFaultSchedule,
    recover: Recover,
    fuse: Fuse,
    redundancy: Redundancy = Redundancy.BUTTERFLY,
    parity: int = 2,
) -> tuple[PanelReport, ...]:
    n_panels = len(widths)
    coded = redundancy is Redundancy.CODED
    for key in set(faults.panel) | set(faults.update):
        if not 0 <= key < n_panels:
            raise ValueError(
                f"fault schedule names panel {key}, but only {n_panels} "
                "panels exist"
            )
    if (n_panels - 1) in faults.update:
        raise ValueError(
            f"panel {n_panels - 1} is the last panel — it has no trailing "
            "update to die during"
        )
    reports = []
    for k in range(n_panels):
        spec_r = faults.panel.get(k, FaultSpec.none())
        last = k == n_panels - 1
        plan_w = None
        tol_w = True
        # A panel fuses its two reductions into one stacked butterfly
        # unless the schedule pins a death to the *second* butterfly
        # specifically — panel-phase faults ride the fused plan_r (a
        # mid-reduction death strikes both leaves at once, and the one
        # replica fetch restores both).
        fused = fuse is not Fuse.OFF and (last or k not in faults.update)
        if coded:
            # Coded redundancy: per-panel CodedPlan over the P + parity
            # world.  "Within tolerance" is the erasure budget — at most
            # ``parity`` dead/slow/corrupt contributions, reconstructed
            # in-collective (no phase-boundary fetch).
            plan_r = make_coded_plan(p, parity, spec_r)
            tol_r = plan_r.recoverable
            if not last:
                spec_w = faults.update.get(k, FaultSpec.none())
                plan_w = make_coded_plan(p, parity, spec_w)
                tol_w = plan_w.recoverable
            recoverable = plan_r.recoverable and (
                plan_w is None or plan_w.recoverable
            )
            rec_r = plan_r.n_erased if plan_r.recoverable else 0
            if fused and plan_w is not None:
                rec_w = rec_r  # one stacked reduction reconstructs both
            else:
                rec_w = (
                    plan_w.n_erased
                    if plan_w is not None and plan_w.recoverable else 0
                )
        else:
            plan_r = make_plan(variant, p, spec_r)
            tol_r = within_tolerance(variant, spec_r, plan_r.n_steps)
            if not last:
                spec_w = faults.update.get(k, FaultSpec.none())
                plan_w = make_plan(variant, p, spec_w)
                tol_w = within_tolerance(variant, spec_w, plan_w.n_steps)
            recoverable = bool(plan_r.final_valid.any()) and (
                plan_w is None or bool(plan_w.final_valid.any())
            )
            # recovered_* counts ranks replica_fetch actually restores —
            # zero when recovery is disabled (the ranks stay poisoned).
            fetching = recover is Recover.REPLICA and recoverable
            rec_r = int((~plan_r.final_valid).sum()) if fetching else 0
            if fused and plan_w is not None:
                rec_w = rec_r  # the one stacked fetch restores both leaves
            else:
                rec_w = (
                    int((~plan_w.final_valid).sum())
                    if fetching and plan_w is not None else 0
                )
        reports.append(
            PanelReport(
                panel=k,
                plan_r=plan_r,
                plan_w=plan_w,
                within_tolerance_r=tol_r,
                within_tolerance_w=tol_w,
                recovered_r=rec_r,
                recovered_w=rec_w,
                recoverable=recoverable,
                fused=fused,
                scheme="coded" if coded else "butterfly",
            )
        )
    if fuse is Fuse.ON:
        bad = [r.panel for r in reports if not r.fused]
        if bad:
            raise ValueError(
                f"fuse=Fuse.ON but panels {bad} carry update-phase faults, "
                "which require the split two-butterfly schedule; schedule "
                "the death on the panel phase or use Fuse.AUTO"
            )
    return tuple(reports)


# ---------------------------------------------------------------------------
# The driver body (backend-agnostic: arrays may carry a leading (P,) axis
# under SimComm, or be per-rank local blocks under ShardMapComm)
# ---------------------------------------------------------------------------

def _solve_w(r_tot, c_sum, pad_to: int | None = None):
    """W = R_totᵀ⁻¹ C  (C = Σ A_panelᵀ A_trail, so W = Q_kᵀ A_trail).

    ``pad_to`` right-pads the RHS with zero columns to a canonical width
    before solving (and slices the result back).  XLA's *batched*
    triangular solve picks its lowering by RHS shape, so per-column results
    are not width-stable; both blocked drivers solve every panel at the
    same padded maximal width ``n_pad − b``, which makes the eager driver
    and the fixed-shape pipeline solve bit-identical by construction (the
    appended zero columns solve to exact zeros).
    """
    import jax.scipy.linalg as jsl

    nt = c_sum.shape[-1]
    if pad_to is not None and pad_to > nt:
        widths = [(0, 0)] * (c_sum.ndim - 1) + [(0, pad_to - nt)]
        c_sum = jnp.pad(c_sum, widths)
    w = jsl.solve_triangular(
        jnp.swapaxes(r_tot, -1, -2), c_sum, lower=True
    )
    return w[..., :nt] if pad_to is not None and pad_to > nt else w


def _blocked_body(
    a,
    comm: Comm,
    reports: tuple[PanelReport, ...],
    widths: tuple[int, ...],
    pf: PanelFactorizer,
    *,
    local_r: str,
    compute_q: bool,
    use_pallas: bool,
    interpret: bool | None,
    block_rows: int | None = None,
    world: Comm | None = None,
):
    m_local, n = a.shape[-2], a.shape[-1]
    n_pad = widths[0] * len(widths)
    kw = dict(use_pallas=use_pallas, interpret=interpret,
              block_rows=block_rows)
    r_full = jnp.zeros(a.shape[:-2] + (n, n), jnp.float32)
    valid = comm.take(np.ones(comm.n_ranks, dtype=bool))
    # coded runs reduce over the P + parity ``world`` comm; ``detected``
    # accumulates per-panel checksum-verification flags over data ranks
    coded = world is not None
    detected = (
        comm.take(np.zeros(comm.n_ranks, dtype=bool)) if coded else None
    )
    q_cols = []
    trail = a
    with _dispatch.span(_dispatch.TRAILING_UPDATE, width=n):
        s = kops.panel_cross(a, split=widths[0], **kw)      # pipeline prime

    def local_r_of(panel, g):
        with _dispatch.span(_dispatch.LOCAL_R, ranks=comm.n_ranks,
                            rows=m_local, cols=panel.shape[-1]):
            if local_r == "chol":
                return chol_r(g)                  # free: lookahead Gram
            return pf.local_fn()(panel.astype(jnp.float32))

    def coded_reduce(payload, plan, combiner):
        p = comm.n_ranks
        val, fv, det = execute_coded(payload, world, plan, combiner)
        return jax.tree.map(lambda t: t[:p], val), fv[:p], det[:p]

    def issue(rep, panel, g_loc, c_loc):
        """Put a fused panel's single butterfly on the wire: the stacked
        (R, Σ AᵖᵀAᵗ) payload over ``plan_r`` (the last panel's payload is
        R-only).  Called right after the trailing sweep that produced the
        lookahead accumulators — one pipeline stage ahead of consumption,
        so the collective is in flight while the panel's bookkeeping and
        the next consume stage run."""
        r_loc = local_r_of(panel, g_loc)
        if rep.plan_w is None:
            if coded:
                r_kk, valid_r, det = coded_reduce(
                    r_loc, rep.plan_r, FUSED_PANEL_COMBINER.parts[0]
                )
                return r_kk, None, valid_r, None, det
            r_kk, valid_r = pf.reduce_r_prepared(r_loc, comm, rep.plan_r)
            return r_kk, None, valid_r, None, None
        if coded:
            (r_kk, c_sum), v, det = coded_reduce(
                (r_loc, c_loc), rep.plan_r, FUSED_PANEL_COMBINER
            )
            return r_kk, c_sum, v, v, det
        (r_kk, c_sum), v = pf.reduce_panel_fused(r_loc, c_loc, comm,
                                                 rep.plan_r)
        return r_kk, c_sum, v, v, None

    def reduce_cross(rep, c_loc):
        """The split schedule's second, serialized sum butterfly of the
        cross products (its own plan — update-phase deaths strike here),
        restored from replicas where it lost ranks.  Returns
        ``(c_sum, valid_w, detected_w)``."""
        det_w = None
        if coded:
            c_sum, valid_w, det_w = coded_reduce(
                c_loc, rep.plan_w, FUSED_PANEL_COMBINER.parts[1]
            )
        else:
            c_sum, valid_w = ft_allreduce(
                c_loc, comm, op="sum", plan=rep.plan_w
            )
        if rep.recovered_w:
            c_sum = recover_payload(
                c_sum, comm, rep.plan_w.final_valid, plan=rep.plan_w
            )
        return c_sum, valid_w, det_w

    pending = None
    if reports[0].fused:
        b0 = widths[0]
        pending = issue(
            reports[0], trail[..., :, :b0], s[..., :, :b0], s[..., :, b0:]
        )
    c0 = 0
    for rep, b in zip(reports, widths):
        with _dispatch.span(_dispatch.PANEL, k=rep.panel):
            nt = n - c0 - b
            panel = trail[..., :, :b]
            # -- phase 1: panel reduction(s) over the butterfly -------------
            if rep.fused:
                r_kk, c_sum, valid_r, valid_w, det = pending
                pending = None
            else:
                r_loc = local_r_of(panel, s[..., :, :b])
                if coded:
                    r_kk, valid_r, det = coded_reduce(
                        r_loc, rep.plan_r, FUSED_PANEL_COMBINER.parts[0]
                    )
                else:
                    r_kk, valid_r = pf.reduce_r_prepared(r_loc, comm, rep.plan_r)
                    det = None
                c_sum = valid_w = None
            valid = valid & valid_r
            if det is not None:
                detected = detected | det
            all_valid_r = bool(_data_valid(rep.plan_r).all())
            if rep.recovered_r:
                # recover_payload dispatches per scheme: butterfly plans fetch
                # full replicas from donors; coded plans already reconstructed
                # in-collective, so it only validates the erasure budget held.
                if rep.fused and c_sum is not None:
                    # ONE fetch restores both stacked leaves — the replica
                    # copies of the fused payload double as FT copies for R
                    # and the cross products alike.
                    r_kk, c_sum = recover_payload(
                        (r_kk, c_sum), comm, rep.plan_r.final_valid,
                        plan=rep.plan_r,
                    )
                else:
                    r_kk = recover_payload(
                        r_kk, comm, rep.plan_r.final_valid, plan=rep.plan_r
                    )
            # -- phase 2: explicit panel Q (+ reorth polish) ----------------
            # The polish's gram all-reduce mixes every rank's contribution, so
            # it needs every rank to hold a finite r_kk; when a no-recovery run
            # left poisoned ranks, skip the polish — survivors keep their exact
            # unpolished factor instead of inheriting the NaN.
            clean = all_valid_r or bool(rep.recovered_r)
            pf_k = pf if clean else dataclasses.replace(pf, reorth=0)
            q_k, r_tot = pf_k.form_q(panel.astype(jnp.float32), r_kk, comm)
            q_k = q_k.astype(a.dtype)
            if compute_q:
                q_cols.append(q_k)
            # -- phase 3: block row of R ------------------------------------
            if nt:
                with _dispatch.span(_dispatch.BLOCK_ROW):
                    if not rep.fused:
                        c_sum, valid_w, det_w = reduce_cross(rep, s[..., :, b:])
                        valid = valid & valid_w
                        if det_w is not None:
                            detected = detected | det_w
                    w = _solve_w(r_tot, c_sum, pad_to=n_pad - widths[0])
                    r_full = r_full.at[..., c0:c0 + b, c0:].set(
                        jnp.concatenate([r_tot, w], axis=-1)
                    )
                # -- phase 4: one-sweep trailing update + lookahead ---------
                b2 = widths[rep.panel + 1]
                with _dispatch.span(_dispatch.TRAILING_UPDATE, width=nt):
                    trail, s = kops.trailing_update(
                        trail[..., :, b:], q_k, w.astype(a.dtype),
                        next_width=b2, **kw
                    )
                nxt = reports[rep.panel + 1]
                if nxt.fused:
                    # double-buffer: the next panel's butterfly launches as
                    # soon as the sweep lands its lookahead accumulators
                    pending = issue(
                        nxt, trail[..., :, :b2], s[..., :, :b2], s[..., :, b2:]
                    )
            else:
                with _dispatch.span(_dispatch.BLOCK_ROW):
                    r_full = r_full.at[..., c0:c0 + b, c0:].set(r_tot)
        c0 += b
    q = jnp.concatenate(q_cols, axis=-1) if compute_q else None
    return r_full, valid, q, detected


# ---------------------------------------------------------------------------
# The scan-compiled fixed-shape pipeline (fault-free hot path)
#
# The eager driver above re-traces per panel: the trailing width shrinks, so
# K panels mean K distinct shapes, K compilations, and O(K) device
# dispatches.  The pipeline removes the shape dependence with a *shifted*
# layout: the working matrix stays at the padded maximal width n_pad = K·b
# (zero columns on the right, produced in-kernel by the column-masked
# ``pad_cross`` prime), and after each panel the trailing block is shifted
# left by b — the live panel is always columns [0, b), the trailing block
# always columns [b, n_pad).  The update kernel does the shift in its one
# sweep (``trailing_update_shift``: it reads the full width and writes back
# in place, shifted, the freed columns zeroed), so no separate slice or pad
# pass over the working matrix exists.  Every scan iteration therefore has
# identical shapes, one ``lax.scan`` trace covers all K−1 uniform panels
# (the ragged last panel is a static epilogue in the same program), and the
# whole factorization compiles to ONE device program that never retraces.  Zero
# pad columns ride every sweep without perturbing the real columns: the
# results are bit-identical to the eager driver (hypothesis-swept).
# ---------------------------------------------------------------------------

def _plans_fault_free(reports: tuple[PanelReport, ...]) -> bool:
    """Pipeline eligibility: every collective of every panel rides the
    straight-line fast path (also excludes ``tree``, whose fault-free plans
    leave non-root ranks invalid — the general driver handles it)."""
    return all(
        rep.plan_r.is_fault_free
        and (rep.plan_w is None or rep.plan_w.is_fault_free)
        for rep in reports
    )


def _resolve_pipeline(pipeline: Pipeline, reports) -> bool:
    """Decide the path for a validated mode: True → the scan-compiled
    single program, False → the eager general driver."""
    fault_free = _plans_fault_free(reports)
    if pipeline is Pipeline.ON and not fault_free:
        raise ValueError(
            "pipeline=Pipeline.ON requires fault-free plans (the "
            "scan-compiled program has no validity machinery); faulty plans "
            "route to the general driver under Pipeline.AUTO"
        )
    return fault_free and pipeline is not Pipeline.OFF


def _pipeline_body(
    a,
    comm: Comm,
    plan: Plan,
    widths: tuple[int, ...],
    pf: PanelFactorizer,
    *,
    local_r: str,
    compute_q: bool,
    use_pallas: bool,
    interpret: bool | None,
    block_rows: int | None = None,
    fused: bool = True,
):
    """The traced single-program body (backend-agnostic like
    :func:`_blocked_body`; ``plan`` is the one fault-free plan every
    collective of every panel shares).  ``fused=True`` (the default path)
    runs the double-buffered one-butterfly-per-panel schedule; ``False``
    the split two-butterfly baseline — bit-identical results either way."""
    if fused:
        return _pipeline_body_fused(
            a, comm, plan, widths, pf, local_r=local_r, compute_q=compute_q,
            use_pallas=use_pallas, interpret=interpret,
            block_rows=block_rows,
        )
    b, k_panels, b_last = widths[0], len(widths), widths[-1]
    n = a.shape[-1]
    n_pad = b * k_panels
    kw = dict(use_pallas=use_pallas, interpret=interpret,
              block_rows=block_rows)

    def panel_qr(panel, g):
        with _dispatch.span(_dispatch.LOCAL_R, ranks=comm.n_ranks,
                            rows=panel.shape[-2], cols=b):
            if local_r == "chol":
                r_loc = chol_r(g)
            else:
                r_loc = pf.local_fn()(panel.astype(jnp.float32))
        r_kk, _ = pf.reduce_r_prepared(r_loc, comm, plan)
        q_k, r_tot = pf.form_q(panel.astype(jnp.float32), r_kk, comm)
        return q_k.astype(a.dtype), r_tot

    # -- prime: padded working copy + panel-0 lookahead, one sweep ----------
    with _dispatch.span(_dispatch.TRAILING_UPDATE, width=n_pad):
        if n_pad == n:
            awork = a
            s = kops._panel_cross_raw(a, split=b, **kw)
        else:
            awork, s = kops._pad_cross_raw(a, split=b, out_width=n_pad, **kw)

    # -- K−1 uniform panels: one traced body, scanned -----------------------
    def step(carry, _):
        awork, s = carry
        q_k, r_tot = panel_qr(awork[..., :, :b], s[..., :, :b])
        with _dispatch.span(_dispatch.BLOCK_ROW):
            c_sum, _ = ft_allreduce(s[..., :, b:], comm, op="sum", plan=plan)
            w = _solve_w(r_tot, c_sum)
            r_row = jnp.concatenate([r_tot, w], axis=-1)   # (…, b, n_pad)
        with _dispatch.span(_dispatch.TRAILING_UPDATE, width=n_pad - b):
            # shift left by b in the sweep: drop the finished panel, keep
            # the width with fresh zero columns (the pad stays exactly zero
            # inductively)
            carry = kops._trailing_update_raw(
                awork, q_k, w.astype(a.dtype), next_width=b, shift=b, **kw
            )
        return carry, ((r_row, q_k) if compute_q else r_row)

    if k_panels > 1:
        (awork, s), ys = lax.scan(step, (awork, s), None, length=k_panels - 1)
        r_rows = ys[0] if compute_q else ys
        q_cols = ys[1] if compute_q else None

    # -- ragged epilogue: the last panel (static, no trailing update) -------
    q_last, r_last = panel_qr(
        awork[..., :, :b_last], s[..., :b_last, :b_last]
    )

    # -- reassemble R (and Q) in original column coordinates ----------------
    with _dispatch.span(_dispatch.BLOCK_ROW):
        r_full = jnp.zeros(a.shape[:-2] + (n, n), jnp.float32)
        for k in range(k_panels - 1):
            c0 = k * b
            r_full = r_full.at[..., c0:c0 + b, c0:].set(
                r_rows[k][..., :, :n - c0]
            )
        c0 = (k_panels - 1) * b
        r_full = r_full.at[..., c0:, c0:].set(r_last)
    q = None
    if compute_q:
        q = jnp.concatenate(
            [q_cols[k] for k in range(k_panels - 1)] + [q_last], axis=-1
        )
    valid = comm.take(np.ones(comm.n_ranks, dtype=bool))
    return r_full, valid, q


def _pipeline_body_fused(
    a,
    comm: Comm,
    plan: Plan,
    widths: tuple[int, ...],
    pf: PanelFactorizer,
    *,
    local_r: str,
    compute_q: bool,
    use_pallas: bool,
    interpret: bool | None,
    block_rows: int | None = None,
):
    """The double-buffered single-program body: ONE stacked butterfly per
    panel instead of two (``log P`` rounds per panel), issued the moment
    the producing sweep lands its lookahead accumulators and consumed one
    pipeline stage later — the pending reduction rides the ``lax.scan``
    carry, so the issue and use sites are decoupled by a full stage and an
    async-collective runtime overlaps each butterfly with the surrounding
    panel bookkeeping instead of paying two serialized collectives per
    panel.  Per-leaf bit-identical to the split schedule (the stacked
    engine runs the same combines over the same plan; only the messages
    are batched), hence bit-identical to the eager driver too."""
    b, k_panels, b_last = widths[0], len(widths), widths[-1]
    n = a.shape[-1]
    n_pad = b * k_panels
    kw = dict(use_pallas=use_pallas, interpret=interpret,
              block_rows=block_rows)

    def local_r_of(panel, g):
        with _dispatch.span(_dispatch.LOCAL_R, ranks=comm.n_ranks,
                            rows=panel.shape[-2], cols=panel.shape[-1]):
            if local_r == "chol":
                return chol_r(g)
            return pf.local_fn()(panel.astype(jnp.float32))

    def issue(awork, s):
        # stacked (R, cross) payload of the live panel, one butterfly;
        # the zero pad columns of the cross leaf reduce to exact zeros
        r_loc = local_r_of(awork[..., :, :b], s[..., :, :b])
        (r_red, c_red), _ = pf.reduce_panel_fused(
            r_loc, s[..., :, b:], comm, plan
        )
        return r_red, c_red

    def issue_last(panel, g):
        # the last panel has no cross leaf; reduce at the exact ragged
        # width — a width-b issue would Cholesky the zero-padded
        # (singular) Gram
        r_red, _ = pf.reduce_r_prepared(local_r_of(panel, g), comm, plan)
        return r_red

    def consume(panel, r_red):
        q_k, r_tot = pf.form_q(panel.astype(jnp.float32), r_red, comm)
        return q_k.astype(a.dtype), r_tot

    # -- prime: padded working copy + panel-0 lookahead + first issue -------
    with _dispatch.span(_dispatch.TRAILING_UPDATE, width=n_pad):
        if n_pad == n:
            awork = a
            s = kops._panel_cross_raw(a, split=b, **kw)
        else:
            awork, s = kops._pad_cross_raw(a, split=b, out_width=n_pad, **kw)

    rows: list = []           # per-panel (…, b, n_pad) R rows, panels 0..K−2
    qs: list = []
    if k_panels == 1:
        r_red = issue_last(awork[..., :, :b_last], s[..., :b_last, :b_last])
    else:
        r_red, c_red = issue(awork, s)

        # -- K−2 uniform stages: consume the carried reduction, sweep, and
        # put the next panel's butterfly on the wire before the scan yields
        def step(carry, _):
            awork, s, r_red, c_red = carry
            q_k, r_tot = consume(awork[..., :, :b], r_red)
            with _dispatch.span(_dispatch.BLOCK_ROW):
                w = _solve_w(r_tot, c_red)
                r_row = jnp.concatenate([r_tot, w], axis=-1)
            with _dispatch.span(_dispatch.TRAILING_UPDATE, width=n_pad - b):
                # shift left by b in the sweep: drop the finished panel,
                # keep the width with fresh zero columns (the pad stays
                # exactly zero inductively)
                awork, s = kops._trailing_update_raw(
                    awork, q_k, w.astype(a.dtype), next_width=b, shift=b,
                    **kw
                )
            r_red, c_red = issue(awork, s)
            return (awork, s, r_red, c_red), (
                (r_row, q_k) if compute_q else r_row
            )

        if k_panels > 2:
            (awork, s, r_red, c_red), ys = lax.scan(
                step, (awork, s, r_red, c_red), None, length=k_panels - 2
            )
            r_rows = ys[0] if compute_q else ys
            rows = [r_rows[k] for k in range(k_panels - 2)]
            if compute_q:
                qs = [ys[1][k] for k in range(k_panels - 2)]

        # -- static penultimate stage: the ragged last panel needs an
        # R-only issue at width b_last, so its producing sweep sits outside
        # the scan ------------------------------------------------------
        q_k, r_tot = consume(awork[..., :, :b], r_red)
        with _dispatch.span(_dispatch.BLOCK_ROW):
            w = _solve_w(r_tot, c_red)
            rows.append(jnp.concatenate([r_tot, w], axis=-1))
        with _dispatch.span(_dispatch.TRAILING_UPDATE, width=n_pad - b):
            awork, s = kops._trailing_update_raw(
                awork, q_k, w.astype(a.dtype), next_width=b, shift=b, **kw
            )
        # the last panel lives in columns [0, b_last)
        r_red = issue_last(awork[..., :, :b_last], s[..., :b_last, :b_last])
        if compute_q:
            qs.append(q_k)

    # -- epilogue: consume the last carried reduction -----------------------
    q_last, r_last = consume(awork[..., :, :b_last], r_red)

    # -- reassemble R (and Q) in original column coordinates ----------------
    with _dispatch.span(_dispatch.BLOCK_ROW):
        r_full = jnp.zeros(a.shape[:-2] + (n, n), jnp.float32)
        for k in range(k_panels - 1):
            c0 = k * b
            r_full = r_full.at[..., c0:c0 + b, c0:].set(
                rows[k][..., :, :n - c0]
            )
        c0 = (k_panels - 1) * b
        r_full = r_full.at[..., c0:, c0:].set(r_last)
    q = None
    if compute_q:
        q = jnp.concatenate(qs + [q_last], axis=-1)
    valid = comm.take(np.ones(comm.n_ranks, dtype=bool))
    return r_full, valid, q


@functools.lru_cache(maxsize=64)
def _compiled_sim_pipeline(
    p: int,
    widths: tuple[int, ...],
    config: QRConfig,
    batched: bool,
):
    """One compiled program per ``(geometry, canonical config)``; the jit
    cache under it keys on the payload's (treedef, shapes, dtypes) — repeat
    calls with identical shapes perform zero new traces (CI
    retrace-guarded).  ``config`` must be :meth:`QRConfig.canonical` so
    policy knobs that do not change the traced program never split the
    cache (the old builder keyed on an ad-hoc 10-tuple of loose kwargs)."""
    comm = SimComm(p)
    plan = make_plan(config.variant, p)
    pf = config.factorizer()

    def fn(a):
        _dispatch.note_trace(PIPELINE_NAME)
        return _pipeline_body(
            a, comm, plan, widths, pf,
            local_r=config.resolved_local_r(), compute_q=config.compute_q,
            use_pallas=config.use_pallas, interpret=config.interpret,
            block_rows=config.block_rows,
            fused=config.fuse is not Fuse.OFF,
        )

    return jax.jit(jax.vmap(fn) if batched else fn)


def _note_reductions(
    reports: tuple[PanelReport, ...],
    widths: tuple[int, ...],
    c_widths: tuple[int, ...],
    reorth_counts: tuple[int, ...],
    reorth_plan: Plan,
    wire_scale: int = 1,
) -> None:
    """Per-butterfly collective accounting: serial rounds, plan-priced wire
    bytes (packed symmetric leaves, dense rectangular leaves), and the
    overlap flag.  One ``panel_reduce`` record per butterfly — a fused
    panel is ONE record carrying the stacked payload, a split panel two —
    plus a ``reorth_reduce`` record for the polish passes.  Every record
    has ``dispatches=0, sweeps=0`` so the HBM-sweep and single-dispatch
    gates never see the collective accounting.

    ``c_widths`` is the cross-leaf width each panel actually reduces (the
    padded ``n_pad − b`` in the pipeline, the live trailing width in the
    eager driver); ``reorth_counts`` the polish passes each panel's
    ``form_q`` ran (0 when a no-recovery fault skipped the polish);
    ``wire_scale`` the batch factor (B matrices ride each message)."""
    for rep, b, cw, n_reorth in zip(reports, widths, c_widths, reorth_counts):
        overlapped = 1 if rep.fused and rep.panel > 0 else 0
        if rep.fused or rep.plan_w is None:
            leaves = [(b, b, 4, False)]
            if rep.plan_w is not None:
                leaves.append((b, cw, 4, False))
            recs = [(rep.plan_r, leaves, overlapped)]
        else:
            recs = [
                (rep.plan_r, [(b, b, 4, False)], 0),
                (rep.plan_w, [(b, cw, 4, False)], 0),
            ]
        for plan, leaves, ov in recs:
            _traffic.note(
                "panel_reduce", dispatches=0, rounds=plan.round_count(),
                wire_bytes=wire_scale * plan.bytes_on_wire_stacked(leaves),
                overlapped=ov,
            )
        if n_reorth:
            _traffic.note(
                "reorth_reduce", dispatches=0,
                rounds=n_reorth * reorth_plan.round_count(),
                wire_bytes=wire_scale * n_reorth
                * reorth_plan.bytes_on_wire_stacked([(b, b, 4, True)]),
            )


def _note_eager_reductions(
    reports: tuple[PanelReport, ...],
    widths: tuple[int, ...],
    n: int,
    pf: PanelFactorizer,
) -> None:
    """Collective accounting for one eager (general-driver) factorization:
    cross leaves at their live trailing widths, polish skipped on panels a
    no-recovery fault left unclean.  Nothing to do when nothing tracks."""
    if not _traffic.tracking():
        return
    with _dispatch.span(_dispatch.PLAN, plans=1):
        c0 = 0
        c_widths = []
        for b in widths:
            c_widths.append(n - c0 - b)
            c0 += b
        reorth_counts = tuple(
            pf.reorth
            if bool(_data_valid(rep.plan_r).all()) or rep.recovered_r else 0
            for rep in reports
        )
        plan0 = reports[0].plan_r
        _note_reductions(
            reports, widths, tuple(c_widths), reorth_counts,
            make_plan("redundant", getattr(plan0, "n_data", plan0.n_ranks)),
        )


def _note_pipeline(shape, dtype, widths, traced: int,
                   reports: tuple[PanelReport, ...], reorth: int,
                   shifted: bool) -> None:
    """Per-call traffic/dispatch accounting for the pipeline (the kernels
    inside the scan are traced once but *execute* once per panel, so the
    wrapper records the exact per-call totals: K sweeps, 1 dispatch).
    ``shifted`` (the Pallas path): each update streams the whole padded
    working matrix and writes it back shifted; otherwise the jnp update
    streams the sliced trailing block, and XLA's slice and re-pad passes
    around it are not recorded.  Only the trailing path is modeled — a
    ``cqr2``/``cqr2_pallas`` local QR adds narrow (m×b) panel-local sweeps
    that are not recorded (their wrappers' own notes are suppressed at
    trace time; the eager driver remains the reference for panel-local
    accounting).  Collective records ride along:
    one ``panel_reduce`` per butterfly (fused panels: one stacked record at
    the padded cross width) plus the ``reorth_reduce`` polish.  Nothing to
    do when nothing tracks."""
    _dispatch.note_dispatch(PIPELINE_NAME)
    if not _traffic.tracking():
        return
    with _dispatch.span(_dispatch.PLAN, plans=1):
        lead = int(np.prod(shape[:-2], dtype=np.int64))
        m, n = shape[-2], shape[-1]
        b, k_panels = widths[0], len(widths)
        n_pad = b * k_panels
        it = jnp.dtype(dtype).itemsize
        if n_pad == n:
            recs = [("panel_cross", lead * m * n * it, lead * b * n * 4)]
        else:
            recs = [(
                "pad_cross",
                lead * m * n * it,
                lead * (m * n_pad * it + b * n_pad * 4),
            )]
        nt = n_pad - b
        swept = n_pad if shifted else nt       # columns A streams through
        for _ in range(k_panels - 1):
            recs.append((
                "trailing_update",
                lead * (m * swept * it + m * b * it + b * nt * it),
                lead * (m * swept * it + b * nt * 4),
            ))
        first = True
        for op, read, write in recs:
            _traffic.note(
                op, sweeps=1, read_bytes=read, write_bytes=write,
                dispatches=1 if first else 0, traces=traced if first else 0,
                shifted=int(shifted and op == "trailing_update"),
            )
            first = False
        p = reports[0].plan_r.n_ranks
        c_widths = tuple(
            n_pad - b if k < k_panels - 1 else 0 for k in range(k_panels)
        )
        _note_reductions(
            reports, widths, c_widths, (reorth,) * k_panels,
            make_plan("redundant", p),
            wire_scale=int(np.prod(shape[:-3], dtype=np.int64)),
        )


def _tuned_config(config: QRConfig, m_local: int, n: int, dtype) -> QRConfig:
    """Resolve ``block_rows=None`` to the installed autotune winner for this
    geometry **before** the config reaches a compile builder's lru key.
    The tuned int is part of the canonical config, so installing a new
    table (a) takes effect on the next call for the affected shape-classes
    and (b) leaves every other geometry's cached program untouched — the
    zero-warm-retrace contract the CI guard pins.  The trailing-update
    class keys the lookup: it is the driver's dominant sweep and shares its
    panel height with every kernel in the body.  No installed entry →
    ``block_rows`` stays None (kernels fall back to the aligned default at
    trace time, which never changes, so the key is still stable)."""
    if not config.use_pallas or config.block_rows is not None:
        return config
    e = _autotune.lookup(
        "trailing_update", m_local, n, dtype,
        backend=resolve_backend(config.interpret),
    )
    if e is None:
        return config
    return dataclasses.replace(config, block_rows=int(e["block_rows"]))


def _run_sim_pipeline(a, widths, config: QRConfig, reports, *, batched=False):
    with _dispatch.span(_dispatch.PLAN, plans=0):
        config = _tuned_config(config, a.shape[-2], a.shape[-1], a.dtype)
        fun = _compiled_sim_pipeline(
            a.shape[-3], widths, config.canonical(), batched
        )
    t0 = _dispatch.trace_count(PIPELINE_NAME)
    # suppress the wrappers' own notes while the body traces (a cqr2 local
    # QR would otherwise record phantom once-per-trace kernel launches);
    # _note_pipeline records the exact per-call totals below.
    with _traffic.suppress(), _dispatch.suppress(), \
            _dispatch.span(_dispatch.LAUNCH):
        out = fun(a)
    _note_pipeline(
        a.shape, a.dtype, widths,
        _dispatch.trace_count(PIPELINE_NAME) - t0, reports, config.reorth,
        config.use_pallas,
    )
    return out


def _setup(
    m_local: int,
    n: int,
    p: int,
    config: QRConfig,
    faults: PanelFaultSchedule | None,
) -> tuple[tuple[int, ...], tuple[PanelReport, ...], PanelFactorizer]:
    """Shared entry-point geometry validation + host planning (sim and
    shard_map).  Policy validation already happened in ``QRConfig``."""
    if config.panel_width is None:
        raise ValueError(
            "the blocked driver needs panel_width; panel_width=None selects "
            "the single-panel TSQR workload (route through "
            "repro.qr.api.factorize)"
        )
    widths = panel_widths(n, config.panel_width)
    if m_local < max(widths):
        raise ValueError(
            f"each rank's row block ({m_local} rows) must be at least as "
            f"tall as the widest panel ({max(widths)}); shrink panel_width "
            "or use fewer ranks"
        )
    # a plan per panel reduction and per cross-product reduction
    with _dispatch.span(_dispatch.PLAN, plans=2 * len(widths) - 1):
        reports = _build_reports(
            config.variant, p, widths, faults or PanelFaultSchedule(),
            config.recover, config.fuse, config.redundancy, config.parity,
        )
        return widths, reports, config.factorizer()


# ---------------------------------------------------------------------------
# factorize() implementations (routed to by repro.qr.api.factorize)
# ---------------------------------------------------------------------------

def _factorize_sim(
    a_blocks, config: QRConfig, *, faults: PanelFaultSchedule | None = None
) -> BlockedQRResult:
    """Single-device simulation: ``a_blocks`` is (P, m_local, n) — the
    general-matrix analogue of the TSQR sim driver.  Fault-free runs
    compile into the single-dispatch scan pipeline per ``config.pipeline``;
    faulty plans route to the eager host-replanned general driver."""
    p, m_local, n = a_blocks.shape
    widths, reports, pf = _setup(m_local, n, p, config, faults)
    coded = config.redundancy is Redundancy.CODED
    detected = None
    if not coded and _resolve_pipeline(config.pipeline, reports):
        r, valid, q = _run_sim_pipeline(a_blocks, widths, config, reports)
    else:
        # coded runs always take the eager driver (the scan pipeline's
        # one-plan butterfly schedule is replica-redundancy only;
        # pipeline=ON + coded is rejected at config validation)
        with _dispatch.span(_dispatch.PLAN, plans=0):
            eager_cfg = _tuned_config(config, m_local, n, a_blocks.dtype)
        r, valid, q, detected = _blocked_body(
            a_blocks, SimComm(p), reports, widths, pf,
            local_r=config.resolved_local_r(), compute_q=config.compute_q,
            use_pallas=config.use_pallas, interpret=config.interpret,
            block_rows=eager_cfg.block_rows,
            world=SimComm(p + config.parity) if coded else None,
        )
        _note_eager_reductions(reports, widths, n, pf)
    return BlockedQRResult(
        r=r, valid=valid, q=q, reports=reports,
        panel_width=config.panel_width, detected=detected,
    )


def _factorize_batched(a_batch, config: QRConfig) -> BlockedQRResult:
    """B independent factorizations in **one** device dispatch.

    ``a_batch`` is (B, P, m_local, n): B user matrices, each row-blocked
    over the same P simulated ranks.  The scan pipeline is ``vmap``-ped
    over the leading axis inside one compiled program, so serving B
    requests costs one launch.  Each element matches the 3-D sim driver on
    that matrix to ~1 ulp of the triangular solves (XLA's *batched*
    triangular-solve lowering reorders intra-solve arithmetic, so the
    agreement is fp-tight rather than bitwise — the ``dispatch`` bench
    case gates it hard; see DESIGN.md §9).  Fault-free only (a real fleet
    replans at step boundaries; faulted batches go matrix-by-matrix
    through the general driver — :mod:`repro.serve` automates exactly
    that).  Returns a result with leading (B,) axes on ``r``/``valid``
    (and ``q``).
    """
    if a_batch.ndim != 4:
        raise ValueError(
            f"a_batch must be (B, P, m_local, n), got shape {a_batch.shape}"
        )
    _, p, m_local, n = a_batch.shape
    widths, reports, _ = _setup(m_local, n, p, config, None)
    if not _plans_fault_free(reports):
        raise ValueError(
            f"variant {config.variant!r} is not pipeline-eligible (its "
            "fault-free plans leave ranks invalid, which the scan-compiled "
            "program has no machinery to track); batch via jax.vmap over "
            "the 3-D sim entry instead"
        )
    r, valid, q = _run_sim_pipeline(
        a_batch, widths, config, reports, batched=True
    )
    return BlockedQRResult(
        r=r, valid=valid, q=q, reports=reports,
        panel_width=config.panel_width,
    )


@functools.lru_cache(maxsize=64)
def _compiled_shard_pipeline(
    mesh, axis: str, p: int, widths, config: QRConfig, jit: bool
):
    """One compiled shard_map pipeline per ``(mesh geometry, canonical
    config)`` — ``config`` must be :meth:`QRConfig.canonical` so policy
    knobs that don't change the traced program never split the cache."""
    comm = ShardMapComm(p, axis)
    plan = make_plan(config.variant, p)
    pf = config.factorizer()
    want_q = config.compute_q

    def body(a_blk):
        _dispatch.note_trace(PIPELINE_NAME)
        r, valid, q = _pipeline_body(
            a_blk, comm, plan, widths, pf,
            local_r=config.resolved_local_r(), compute_q=want_q,
            use_pallas=config.use_pallas, interpret=config.interpret,
            block_rows=config.block_rows,
            fused=config.fuse is not Fuse.OFF,
        )
        return r[None], valid[None], q if want_q else dummy_q(a_blk)

    return shard_compile(body, mesh=mesh, axis=axis, n_outputs=3, jit=jit)


@functools.lru_cache(maxsize=64)
def _compiled_shard_general(
    mesh, axis: str, p: int, reports, widths, config: QRConfig, jit: bool
):
    """The host-replanned general driver under ``shard_map`` — cached at
    module level (the old per-call ``jax.jit(shard)`` rebuilt the wrapper
    and discarded the compile cache on every invocation).  Keyed on the
    fault-bearing ``reports`` (they alter the traced collective schedule)
    plus the canonical config."""
    comm = ShardMapComm(p, axis)
    pf = config.factorizer()
    want_q = config.compute_q

    def body(a_blk):
        _dispatch.note_trace("blocked_qr_shard_map")
        r, valid, q, _ = _blocked_body(
            a_blk, comm, reports, widths, pf,
            local_r=config.resolved_local_r(), compute_q=want_q,
            use_pallas=config.use_pallas, interpret=config.interpret,
            block_rows=config.block_rows,
        )
        return r[None], valid[None], q if want_q else dummy_q(a_blk)

    return shard_compile(body, mesh=mesh, axis=axis, n_outputs=3, jit=jit)


def _factorize_shard_map(
    a_global,
    config: QRConfig,
    *,
    mesh,
    axis: str,
    faults: PanelFaultSchedule | None = None,
    jit: bool = True,
) -> BlockedQRResult:
    """Production path: A (m, n) row-sharded over ``mesh`` axis ``axis``.

    Same body as the sim driver under ``shard_map`` — exchanges lower to
    ``lax.ppermute``, replica fetches ride the same wires.  Fault-free
    runs compile into the single-dispatch scan pipeline; faulted plans
    route to the general driver.  Both programs are cached at module
    level, so repeat calls with identical statics and shapes perform zero
    new traces.  Returns r (P, n, n) (one copy per rank), valid (P,),
    q (m, n) row-sharded or None.
    """
    p = mesh.shape[axis]
    m, n = a_global.shape
    widths, reports, pf = _setup(m // p, n, p, config, faults)
    with _dispatch.span(_dispatch.PLAN, plans=0):
        config = _tuned_config(config, m // p, n, a_global.dtype)
        pipelined = _resolve_pipeline(config.pipeline, reports)
        if pipelined:
            fun = _compiled_shard_pipeline(
                mesh, axis, p, widths, config.canonical(), jit
            )
        else:
            fun = _compiled_shard_general(
                mesh, axis, p, reports, widths, config.canonical(), jit
            )
    if pipelined:
        t0 = _dispatch.trace_count(PIPELINE_NAME)
        with _traffic.suppress(), _dispatch.suppress(), \
                _dispatch.span(_dispatch.LAUNCH):
            r, valid, q = fun(a_global)
        _note_pipeline(
            (p, m // p, n), a_global.dtype, widths,
            _dispatch.trace_count(PIPELINE_NAME) - t0, reports, pf.reorth,
            config.use_pallas,
        )
    else:
        _dispatch.note_dispatch("blocked_qr_shard_map")
        with _dispatch.span(_dispatch.LAUNCH):
            r, valid, q = fun(a_global)
        _note_eager_reductions(reports, widths, n, pf)
    return BlockedQRResult(
        r=r, valid=valid, q=(q if config.compute_q else None),
        reports=reports, panel_width=config.panel_width,
    )


# ---------------------------------------------------------------------------
# Legacy kwarg entry points (deprecated shims over the implementations)
# ---------------------------------------------------------------------------

def blocked_qr_sim(
    a_blocks,
    *,
    panel_width: int,
    variant: str = "redundant",
    faults: PanelFaultSchedule | None = None,
    compute_q: bool = False,
    local_r: str = "chol",
    reorth: int = 1,
    use_pallas: bool = False,
    interpret: bool | None = None,
    recover: str = "replica",
    pipeline: str = "auto",
    fuse: str = "auto",
) -> BlockedQRResult:
    """Deprecated kwarg shim — build a :class:`~repro.qr.api.QRConfig` and
    call :func:`repro.qr.api.factorize` on the (P, m_local, n) row blocks
    instead.  The kwargs map 1:1 onto config fields; results are
    bit-identical (this shim delegates to the same implementation)."""
    warn_deprecated_entry("blocked_qr_sim")
    config = QRConfig(
        panel_width=panel_width, variant=variant, local_r=local_r,
        reorth=reorth, compute_q=compute_q, use_pallas=use_pallas,
        interpret=interpret, pipeline=pipeline, fuse=fuse, recover=recover,
    )
    return _factorize_sim(a_blocks, config, faults=faults)


def blocked_qr_batched(
    a_batch,
    *,
    panel_width: int,
    variant: str = "redundant",
    compute_q: bool = False,
    local_r: str = "chol",
    reorth: int = 1,
    use_pallas: bool = False,
    interpret: bool | None = None,
    fuse: str = "auto",
) -> BlockedQRResult:
    """Deprecated kwarg shim — build a :class:`~repro.qr.api.QRConfig` and
    call :func:`repro.qr.api.factorize` on the (B, P, m_local, n) batch
    instead (one device dispatch either way, bit-identical results)."""
    warn_deprecated_entry("blocked_qr_batched")
    config = QRConfig(
        panel_width=panel_width, variant=variant, local_r=local_r,
        reorth=reorth, compute_q=compute_q, use_pallas=use_pallas,
        interpret=interpret, fuse=fuse,
    )
    return _factorize_batched(a_batch, config)


def blocked_qr_shard_map(
    a_global,
    *,
    mesh,
    axis: str,
    panel_width: int,
    variant: str = "redundant",
    faults: PanelFaultSchedule | None = None,
    compute_q: bool = False,
    local_r: str = "chol",
    reorth: int = 1,
    use_pallas: bool = False,
    interpret: bool | None = None,
    recover: str = "replica",
    jit: bool = True,
    pipeline: str = "auto",
    fuse: str = "auto",
) -> BlockedQRResult:
    """Deprecated kwarg shim — build a :class:`~repro.qr.api.QRConfig` and
    call :func:`repro.qr.api.factorize` with ``mesh=``/``axis=`` instead
    (same shard_map drivers, bit-identical results)."""
    warn_deprecated_entry("blocked_qr_shard_map")
    config = QRConfig(
        panel_width=panel_width, variant=variant, local_r=local_r,
        reorth=reorth, compute_q=compute_q, use_pallas=use_pallas,
        interpret=interpret, pipeline=pipeline, fuse=fuse, recover=recover,
    )
    return _factorize_shard_map(
        a_global, config, mesh=mesh, axis=axis, faults=faults, jit=jit
    )
