"""Fault-tolerant, communication-avoiding TSQR (Coti 2015) entry points.

The tall-and-skinny workload of the paper: one panel — the whole matrix —
factored by the generic collective engine (:mod:`repro.collective`) with
the QR combiner.  The panel-local machinery (local QR fns, ``form_q``)
lives in :mod:`repro.qr.panel` as the :class:`~repro.qr.panel.
PanelFactorizer` shared with the blocked general-matrix driver
(:mod:`repro.qr.blocked`); this module contributes only the entry-point
plumbing (plan construction, backends, result container).

The four variants of the paper are driven by a host-computed
:class:`~repro.collective.plan.Plan` and execute identically on the
:class:`~repro.collective.comm.SimComm` (single device, leading (P,) axis)
and :class:`~repro.collective.comm.ShardMapComm` (SPMD, ``lax.ppermute``)
backends:

  * ``tree``        — Alg. 1, the baseline reduction tree (zero redundancy);
  * ``redundant``   — Alg. 2, butterfly *exchange*: both buddies combine, so
                      every intermediate R̃ exists in ``2^s`` copies;
  * ``replace``     — Alg. 3, identical fault-free, reroutes to a replica of
                      a dead buddy;
  * ``selfhealing`` — Alg. 4–6, additionally respawns dead ranks from a
                      replica at every level.

Hot-path notes (DESIGN.md §7): fault-free plans ride the engine's
straight-line fast path automatically, and the CQR2 local QRs use the
fused 2-sweep R-only pipeline (``cholesky_qr2_r``) — the butterfly only
carries R, so no tall intermediate is ever materialized.

Compilation model (DESIGN.md §9): the ``shard_map`` entry points are
module-level cached compiles keyed on ``(mesh, plan, factorizer, …)`` —
the seed rebuilt ``jax.jit(shard)`` on every call, discarding the compile
cache — so repeat calls with identical statics and shapes perform zero
new traces (CI retrace-guarded).  :class:`TSQRResult` is a registered
pytree, so ``jax.vmap(tsqr_sim …)`` batches B independent factorizations.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.collective.combiners import posdiag as _posdiag
from repro.collective.comm import ShardMapComm, SimComm
from repro.collective.engine import ft_allreduce
from repro.collective.faults import FaultSpec
from repro.collective.plan import Plan, make_plan
from repro.kernels import dispatch as _dispatch
from repro.kernels.backend import F32_PRECISION

from ._shard import dummy_q, shard_compile
from .api import QRConfig, Redundancy, warn_deprecated_entry
from .panel import PanelFactorizer, form_q

__all__ = [
    "TSQRResult",
    "tsqr_sim",
    "tsqr_shard_map",
    "tsqr_gram_shard_map",
]


# ---------------------------------------------------------------------------
# Result container
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TSQRResult:
    """Per-rank outcome of a fault-tolerant TSQR.

    ``r``        — (P, n, n) in sim / per-device (n, n) under shard_map.
    ``valid``    — who holds a correct final R (the paper's semantics).
    ``q``        — optional per-rank (m_local, n) orthonormal factor.
    ``plan``     — the communication plan that was executed (accounting):
                   a butterfly :class:`~repro.collective.plan.Plan` or a
                   :class:`~repro.collective.coded.CodedPlan`.
    ``detected`` — coded runs only: (P,) device bool flagging ranks whose
                   payload failed checksum verification (silent data
                   corruption the butterfly would have propagated).
    """

    r: jax.Array
    valid: jax.Array
    q: jax.Array | None
    plan: Plan
    detected: jax.Array | None = None


# Registered as a pytree (arrays as leaves, the host plan as static aux) so
# results flow through jax transformations — `jax.vmap(tsqr_sim …)` batches
# B independent tall-skinny factorizations directly.
jax.tree_util.register_pytree_node(
    TSQRResult,
    lambda res: ((res.r, res.valid, res.q, res.detected), (res.plan,)),
    lambda aux, ch: TSQRResult(
        r=ch[0], valid=ch[1], q=ch[2], detected=ch[3], plan=aux[0]
    ),
)


# ---------------------------------------------------------------------------
# Module-level compiled programs (zero-retrace: the old per-call
# ``jax.jit(shard)`` rebuilt the wrapper — and discarded the compile cache —
# on every invocation; these builders key on the hashable statics and the
# jit cache underneath keys on the payload shapes)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _compiled_tsqr_shard(mesh, axis: str, plan: Plan, pf: PanelFactorizer,
                         want_q: bool, jit: bool):
    comm = ShardMapComm(plan.n_ranks, axis)

    def body(a_blk):
        _dispatch.note_trace("tsqr_shard_map")
        r, valid = pf.reduce_r(a_blk, comm, plan)
        q = None
        if want_q:
            q, r = pf.form_q(a_blk, r, comm)
        return r[None], valid[None], q if want_q else dummy_q(a_blk)

    return shard_compile(body, mesh=mesh, axis=axis, n_outputs=3, jit=jit)


@functools.lru_cache(maxsize=64)
def _compiled_tsqr_gram_shard(mesh, axis: str, p: int, reorth: int,
                              jit: bool):
    comm = ShardMapComm(p, axis)

    def body(a_blk):
        _dispatch.note_trace("tsqr_gram_shard_map")
        a32 = a_blk.astype(jnp.float32)
        g = jnp.einsum("mi,mj->ij", a32, a32, precision=F32_PRECISION)
        g, _ = ft_allreduce(g, comm, op="gram_sum")
        r = _posdiag(jnp.swapaxes(jnp.linalg.cholesky(g), -1, -2))
        q, r = form_q(a_blk, r, comm, reorth)
        return r[None], q

    return shard_compile(body, mesh=mesh, axis=axis, n_outputs=2, jit=jit)


# ---------------------------------------------------------------------------
# factorize() implementations (routed to by repro.qr.api.factorize)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _compiled_tsqr_coded(config: QRConfig, plan):
    """One compiled coded TSQR per ``(canonical config, coded plan)`` —
    the coded analogue of the butterfly's cached builders, so repeat calls
    under the same fault picture perform zero new traces (CI-guarded)."""
    from repro.collective.coded import execute_coded

    p = plan.n_data
    world = SimComm(plan.n_ranks)
    data_comm = SimComm(p)
    pf = config.factorizer()

    def fn(a, observed):
        _dispatch.note_trace("tsqr_coded")
        val, fv, det = execute_coded(
            a, world, plan, pf.combiner(), observed=observed
        )
        r, valid, detected = val[:p], fv[:p], det[:p]
        q = None
        if config.compute_q:
            q, r = pf.form_q(a, r, data_comm)
        return r, valid, q, detected

    return jax.jit(fn)


def _factorize_sim_coded(
    a_blocks, config: QRConfig, fault_spec, observed
) -> TSQRResult:
    """Checksum-coded TSQR (DESIGN.md §12): ``config.parity`` checksum
    ranks are appended to the P data blocks, Cauchy-encoded at
    distribution time, and up to ``parity`` dead / straggling / corrupted
    contributions are reconstructed from parity in-collective — no
    ``replica_fetch``, and declared-corrupt payloads are *verified*
    against their reconstruction (``detected``)."""
    from repro.collective.coded import make_coded_plan

    p = a_blocks.shape[0]
    with _dispatch.span(_dispatch.PLAN, plans=1):
        plan = make_coded_plan(p, config.parity, fault_spec)
        if config.compute_q and not plan.final_valid[:p].all():
            raise ValueError(
                "compute_q requires every data rank to end valid; this fault "
                f"spec exceeds the coded erasure budget (c={config.parity}) — "
                f"final_valid={plan.final_valid[:p]}"
            )
        fun = _compiled_tsqr_coded(config.canonical(), plan)
    _dispatch.note_dispatch("tsqr_coded")
    with _dispatch.span(_dispatch.LAUNCH):
        r, valid, q, detected = fun(a_blocks, observed)
    return TSQRResult(
        r=r, valid=valid, q=(q if config.compute_q else None), plan=plan,
        detected=detected,
    )


def _factorize_sim(
    a_blocks,
    config: QRConfig,
    *,
    fault_spec: FaultSpec | None = None,
    observed=None,
) -> TSQRResult:
    """Single-device simulation: ``a_blocks`` is (P, m_local, n).

    This is the backend the test-suite and the hypothesis robustness sweeps
    drive; the algorithm body is shared with the shard_map driver.

    ``observed`` (coded runs only) is what the data ranks *currently*
    hold — parity is always encoded from ``a_blocks``, the distribution-
    time truth, so a scenario injects silent corruption by perturbing
    ``observed`` and the checksum verification catches the divergence.
    """
    if config.redundancy is Redundancy.CODED:
        return _factorize_sim_coded(a_blocks, config, fault_spec, observed)
    if observed is not None:
        raise ValueError(
            "observed= models silently-corrupted payloads, which only the "
            "coded scheme can act on; use redundancy='coded'"
        )
    p = a_blocks.shape[0]
    with _dispatch.span(_dispatch.PLAN, plans=1):
        plan = make_plan(config.variant, p, fault_spec)
        if config.compute_q and not plan.final_valid.all():
            raise ValueError(
                "compute_q requires an all-valid plan (fault-free, or "
                "self-healing within tolerance); got final_valid="
                f"{plan.final_valid}"
            )
        comm = SimComm(p)
        pf = config.factorizer()
    r, valid = pf.reduce_r(a_blocks, comm, plan)
    q = None
    if config.compute_q:
        q, r = pf.form_q(a_blocks, r, comm)
    return TSQRResult(r=r, valid=valid, q=q, plan=plan)


@functools.lru_cache(maxsize=64)
def _compiled_tsqr_batched(p: int, config: QRConfig):
    """One compiled vmap-batched TSQR per ``(P, canonical config)``: B
    independent tall-skinny factorizations in one device dispatch (the
    single-panel analogue of the blocked batched pipeline)."""
    comm = SimComm(p)
    plan = make_plan(config.variant, p)
    pf = config.factorizer()

    def fn(a):
        _dispatch.note_trace("tsqr_batched")
        r, valid = pf.reduce_r(a, comm, plan)
        q = None
        if config.compute_q:
            q, r = pf.form_q(a, r, comm)
        return r, valid, q
    return jax.jit(jax.vmap(fn)), plan


def _factorize_batched(a_batch, config: QRConfig) -> TSQRResult:
    """B independent TSQRs in one device dispatch; ``a_batch`` is
    (B, P, m_local, n).  Fault-free only, like the blocked batched path."""
    if a_batch.ndim != 4:
        raise ValueError(
            f"a_batch must be (B, P, m_local, n), got shape {a_batch.shape}"
        )
    p = a_batch.shape[1]
    with _dispatch.span(_dispatch.PLAN, plans=1):
        fun, plan = _compiled_tsqr_batched(p, config.canonical())
    if config.compute_q and not plan.final_valid.all():
        raise ValueError(
            "compute_q requires an all-valid plan; variant "
            f"{config.variant!r} leaves ranks invalid even fault-free"
        )
    _dispatch.note_dispatch("tsqr_batched")
    with _dispatch.span(_dispatch.LAUNCH):
        r, valid, q = fun(a_batch)
    return TSQRResult(r=r, valid=valid, q=q, plan=plan)


def _factorize_gram_shard(
    a_global, config: QRConfig, *, mesh, axis: str, jit: bool = True
) -> TSQRResult:
    """Beyond-paper optimized TSQR: the **Gram butterfly** (EXPERIMENTS.md
    §Perf, cell C).

    The paper's combine is ``QR([R̃ᵢ; R̃ⱼ])`` at every butterfly level —
    log₂(P) Householder factorizations of 2n×n on the critical path, each
    sequential and VPU-bound on TPU.  This variant keeps the *same
    butterfly* (same exchanges, same 2^s-copy redundancy, same fault
    semantics) but swaps the combiner to ``gram_sum``: it carries Gram
    matrices ``G = Σ AᵢᵀAᵢ``, one Cholesky at the end, and a CholeskyQR2
    polish for Householder-grade orthogonality.  Per level the combine is
    an n×n add instead of an O(n³) QR; the local work is one MXU Gram
    matmul instead of a Householder panel.  Wire bytes are n² per exchange
    shipped square — n(n+1)/2 with symmetric packing, which
    ``Plan.bytes_on_wire(symmetric=True)`` now prices (see
    benchmarks/comm_volume.py).

    Numerics: κ(A)² enters the Gram, so the polish round is mandatory;
    certified for κ(A) ≲ 1/√ε like CQR2.
    """
    p = mesh.shape[axis]
    with _dispatch.span(_dispatch.PLAN, plans=1):
        fun = _compiled_tsqr_gram_shard(mesh, axis, p, config.reorth, jit)
        plan = make_plan("redundant", p)
    _dispatch.note_dispatch("tsqr_gram_shard_map")
    with _dispatch.span(_dispatch.LAUNCH):
        r, q = fun(a_global)
    return TSQRResult(r=r, valid=jnp.ones((p,), bool), q=q, plan=plan)


def _factorize_shard(
    a_global,
    config: QRConfig,
    *,
    mesh,
    axis: str,
    fault_spec: FaultSpec | None = None,
    jit: bool = True,
) -> TSQRResult:
    """Production path: A (m, n) row-sharded over ``mesh`` axis ``axis``.

    Returns r (P, n, n) — one (replicated-if-valid) copy per rank — valid
    (P,) and q (m, n) row-sharded (or None).

    The permutation plan is host-computed from ``fault_spec``; on a real
    fleet the runtime re-invokes this with a fresh plan after each health
    change (step-boundary replanning, DESIGN.md §2).
    """
    p = mesh.shape[axis]
    with _dispatch.span(_dispatch.PLAN, plans=1):
        plan = make_plan(config.variant, p, fault_spec)
        if config.compute_q and not plan.final_valid.all():
            raise ValueError(
                "compute_q requires an all-valid plan (fault-free, or "
                "self-healing within tolerance)"
            )
        pf = config.factorizer()
        fun = _compiled_tsqr_shard(mesh, axis, plan, pf, config.compute_q,
                                   jit)
    _dispatch.note_dispatch("tsqr_shard_map")
    with _dispatch.span(_dispatch.LAUNCH):
        r, valid, q = fun(a_global)
    return TSQRResult(
        r=r, valid=valid, q=(q if config.compute_q else None), plan=plan
    )


# ---------------------------------------------------------------------------
# Legacy kwarg entry points (deprecated shims over the implementations)
# ---------------------------------------------------------------------------

def _config_of(compute_q, reorth, local_qr) -> QRConfig:
    return QRConfig(
        panel_width=None, local_r=local_qr, reorth=reorth,
        compute_q=compute_q,
    )


def tsqr_sim(
    a_blocks,
    *,
    variant: str = "redundant",
    fault_spec: FaultSpec | None = None,
    compute_q: bool = False,
    reorth: int = 1,
    local_qr: str | Callable = "jnp",
) -> TSQRResult:
    """Deprecated kwarg shim — build a :class:`~repro.qr.api.QRConfig`
    (``panel_width=None`` selects TSQR) and call
    :func:`repro.qr.api.factorize` on the (P, m_local, n) row blocks
    instead; results are bit-identical (this delegates to the same
    implementation)."""
    warn_deprecated_entry("tsqr_sim")
    config = dataclasses.replace(
        _config_of(compute_q, reorth, local_qr), variant=variant
    )
    return _factorize_sim(a_blocks, config, fault_spec=fault_spec)


def tsqr_gram_shard_map(
    a_global,
    *,
    mesh,
    axis: str,
    reorth: int = 1,
    jit: bool = True,
):
    """Deprecated kwarg shim — build a :class:`~repro.qr.api.QRConfig` with
    ``gram=True`` and call :func:`repro.qr.api.factorize` with ``mesh=``
    instead (same Gram-butterfly driver, bit-identical results)."""
    warn_deprecated_entry("tsqr_gram_shard_map")
    config = QRConfig(panel_width=None, gram=True, reorth=reorth)
    return _factorize_gram_shard(
        a_global, config, mesh=mesh, axis=axis, jit=jit
    )


def tsqr_shard_map(
    a_global,
    *,
    mesh,
    axis: str,
    variant: str = "redundant",
    fault_spec: FaultSpec | None = None,
    compute_q: bool = False,
    reorth: int = 1,
    local_qr: str | Callable = "jnp",
    jit: bool = True,
):
    """Deprecated kwarg shim — build a :class:`~repro.qr.api.QRConfig`
    (``panel_width=None``) and call :func:`repro.qr.api.factorize` with
    ``mesh=``/``axis=`` instead (same compiled driver, bit-identical
    results)."""
    warn_deprecated_entry("tsqr_shard_map")
    config = dataclasses.replace(
        _config_of(compute_q, reorth, local_qr), variant=variant
    )
    return _factorize_shard(
        a_global, config, mesh=mesh, axis=axis, fault_spec=fault_spec,
        jit=jit,
    )
