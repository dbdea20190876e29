"""The plan-driven, fault-tolerant butterfly-collective engine.

This is the generic half of the paper's contribution, factored out of the
TSQR implementation: :func:`execute_plan` runs any
:class:`~repro.collective.plan.Plan` (tree / redundant / replace /
selfhealing) with any :class:`~repro.collective.combiners.Combiner`,
threading validity bits alongside every payload and performing the
Self-Healing restore rounds.  It is written once against
:class:`~repro.collective.comm.Comm`, so every combiner executes identically
on :class:`~repro.collective.comm.SimComm` (single device, leading (P,)
axis) and :class:`~repro.collective.comm.ShardMapComm` (SPMD,
``lax.ppermute``).

:func:`ft_allreduce` is the public entry point for arithmetic reductions —
a recursive-doubling all-reduce over the same butterfly as TSQR, inheriting
the paper's ``2^s − 1`` fault tolerance for free.  It replaces the old
fault-oblivious ``butterfly_allreduce_sum``: PowerSGD's Gram reductions,
the CholeskyQR reorthogonalization passes, and the trainer's BLANK-mode
gradient reduction all route through it.

**Fault-free fast path.**  ~100% of production steps run a fault-free plan:
one perm-round per level, nobody dies, every rank stays valid.  The general
executor still paid per level for machinery only faults need — a
``zeros_like`` + ``add`` receive-staging loop (multi-round Replace
multicast), a validity bit on the wire, per-rank validity updates and
NaN-poison writes.  When the host plan proves fault-freeness
(:func:`plan_is_fault_free`), :func:`execute_plan` dispatches to a
straight-line butterfly — exchange, order by the level bit, combine — that
both the jnp and Pallas combiners ride, and returns the host-predicted
(all-true) validity.  The result is bit-identical to the general path
(asserted across the test suite); pass ``fast=False`` to force the general
executor.

**Symmetric wire packing.**  Combiners that declare ``wire_symmetric``
(``gram_sum``) carry symmetric (…, n, n) payloads; both executors pack them
to the n(n+1)/2 upper triangle at the comm boundary
(:mod:`repro.collective.packing`), so the wire carries exactly what
``Plan.bytes_on_wire(symmetric=True)`` prices.  The decision is per leaf
(:meth:`Combiner.wire_pack_flags`): a mixed payload — e.g. a stacked
symmetric Gram leaf next to a dense rectangular cross leaf — packs exactly
the leaves that qualify, priced by ``Plan.bytes_on_wire_stacked``.

Validity semantics: a dead rank's contribution is zero-filled (XLA
collective-permute semantics) and flagged invalid — the step-boundary
analogue of ULFM's error returns.  The host plan predicts the same validity;
tests assert the two agree bit-for-bit.  Invalid payload slots are poisoned
(NaN for inexact dtypes) so accidental use is loud.

Payloads may be arbitrary pytrees (one shared validity bit per rank): the
trainer routes whole gradient trees through one call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch as _dispatch

from .combiners import Combiner, get_combiner
from .comm import Comm, ShardMapComm, SimComm
from .faults import NEVER, FaultSpec
from .packing import pack_sym, unpack_sym
from .plan import Plan, _split_rounds, make_plan

__all__ = ["execute_plan", "ft_allreduce", "ft_allreduce_jit",
           "plan_is_fault_free", "recover_payload", "replica_fetch"]


def _poison(leaf):
    """Fill for invalid slots: NaN where representable, zero otherwise."""
    if jnp.issubdtype(leaf.dtype, jnp.inexact):
        return jnp.full_like(leaf, jnp.nan)
    return jnp.zeros_like(leaf)


def plan_is_fault_free(plan: Plan) -> bool:
    """Fast-path eligibility — cached on the plan (:attr:`Plan.
    is_fault_free`), so the K×3 collectives of a blocked factorization pay
    the step walk once instead of once per call."""
    return plan.is_fault_free


def _wire_codec(combiner: Combiner, val):
    """(pack, unpack) applied at the comm boundary, decided **per leaf**:
    a leaf ships the n(n+1)/2 upper triangle iff its governing combiner
    declares ``wire_symmetric`` and the leaf is square
    (:meth:`Combiner.wire_pack_flags` — a stacked payload routes the
    decision per part).  Everything else passes through dense, so a mixed
    payload with one symmetric leaf and one rectangular leaf ships each
    optimally instead of falling back to all-dense."""
    flags = combiner.wire_pack_flags(val)
    if not any(flags):
        def ident(t):
            return t

        return ident, ident

    treedef = jax.tree.structure(val)
    ns = [leaf.shape[-1] for leaf in jax.tree.leaves(val)]

    def pack(t):
        return treedef.unflatten([
            pack_sym(leaf) if f else leaf
            for leaf, f in zip(jax.tree.leaves(t), flags)
        ])

    def unpack(t):
        return treedef.unflatten([
            unpack_sym(leaf, n) if f else leaf
            for leaf, f, n in zip(jax.tree.leaves(t), flags, ns)
        ])

    return pack, unpack


def _execute_fast(x, comm: Comm, plan: Plan, combiner: Combiner):
    """Straight-line fault-free butterfly: no receive staging, no validity
    bit on the wire, no poison writes.  Requires :func:`plan_is_fault_free`;
    bit-identical to the general executor on such plans."""
    val = combiner.tree_prepare(x)
    pack, unpack = _wire_codec(combiner, val)
    my = comm.ranks()
    for step in plan.steps:
        recv = unpack(comm.exchange(pack(val), step.perm_rounds[0]))
        mine_first = ((my >> step.level) & 1) == 0
        lo = jax.tree.map(lambda m, o: comm.bwhere(mine_first, m, o), val, recv)
        hi = jax.tree.map(lambda m, o: comm.bwhere(mine_first, o, m), val, recv)
        val = combiner.tree_combine(lo, hi)
    return val, comm.take(plan.final_valid)


def execute_plan(
    x,
    comm: Comm,
    plan: Plan,
    combiner: Combiner | str,
    *,
    fast: bool | None = None,
):
    """Run ``plan`` over ``x`` with ``combiner``.  Returns ``(value, valid)``.

    ``x`` is a pytree of per-rank payloads (leading (P,) axis under
    ``SimComm``, local blocks under ``ShardMapComm``).  ``value`` is the
    un-finalized combine (callers wanting mean semantics etc. should use
    :func:`ft_allreduce`); ``valid`` is the per-rank validity bit, which
    matches ``plan.final_valid`` bit-for-bit.

    ``fast=None`` auto-dispatches to the fault-free fast path when the host
    plan permits; ``False`` forces the general executor; ``True`` demands
    the fast path (raises if the plan is not fault-free).
    """
    with _reduce_span(plan):
        return _execute(x, comm, plan, get_combiner(combiner), fast)


def _reduce_span(plan):
    return _dispatch.span(_dispatch.REDUCE, rounds=plan.round_count(),
                          messages=plan.message_count())


def _execute(x, comm: Comm, plan: Plan, combiner: Combiner, fast):
    fault_free = plan.is_fault_free
    if fast is True and not fault_free:
        raise ValueError(
            "fast=True requires a fault-free plan (one perm-round per step, "
            "no deaths, all ranks valid)"
        )
    if fault_free and fast is not False:
        return _execute_fast(x, comm, plan, combiner)

    val = combiner.tree_prepare(x)
    pack, unpack = _wire_codec(combiner, val)
    d = comm.take(plan.death)
    my = comm.ranks()
    valid = d > 0
    for step in plan.steps:
        s = step.level
        can = valid & (d > s)
        # ---- exchange (possibly several unique-source rounds) -------------
        pval = pack(val)
        recv_p = jax.tree.map(jnp.zeros_like, pval)
        recv_v = jnp.zeros_like(can)
        for rnd in step.perm_rounds:
            rr, rv = comm.exchange((pval, can), rnd)
            recv_p = jax.tree.map(jnp.add, recv_p, rr)  # each rank receives ≤once
            recv_v = recv_v | rv
        recv = unpack(recv_p)
        # ---- combine: operands ordered by this level's block bit ----------
        mine_first = ((my >> s) & 1) == 0
        lo = jax.tree.map(lambda m, o: comm.bwhere(mine_first, m, o), val, recv)
        hi = jax.tree.map(lambda m, o: comm.bwhere(mine_first, o, m), val, recv)
        new = combiner.tree_combine(lo, hi)
        valid = can & recv_v
        val = jax.tree.map(lambda nv: comm.bwhere(valid, nv, _poison(nv)), new)
        # ---- Self-Healing: respawn dead ranks from a replica ---------------
        if step.restore_rounds:
            for rnd in step.restore_rounds:
                rr, rv = comm.exchange((pack(val), valid), rnd)
                rr = unpack(rr)
                got = rv & ~valid
                val = jax.tree.map(
                    lambda cur, rec: comm.bwhere(got, rec, cur), val, rr
                )
                valid = valid | got
            respawned = comm.take(step.respawned)
            d = jnp.where(respawned, jnp.asarray(NEVER, d.dtype), d)
    return val, valid


def replica_fetch(x, comm: Comm, valid) -> object:
    """Restore invalid ranks' payloads from replicas of the reduced value.

    After a within-tolerance butterfly, every *valid* rank holds an
    identical copy of the reduction — the redundant copies the paper buys
    with the exchange.  This converts that data existence into recovery at
    a step boundary: each invalid rank receives the value from a valid
    donor (round-robin, decomposed into unique-source rounds exactly like
    the Replace multicast).  ``valid`` is the *host-side* (P,) prediction
    (``plan.final_valid``) — routing must be trace-time static, the same
    step-boundary replanning contract as the plans themselves.

    The blocked-QR driver uses this between panels: a rank that lost a
    panel's R or W re-joins the pipeline instead of poisoning every later
    panel's reduction.  Raises ``ValueError`` when no rank is valid —
    the value is genuinely extinct and no routing can recover it.
    """
    valid = np.asarray(valid, dtype=bool)
    if valid.all():
        return x
    if not valid.any():
        raise ValueError("replica_fetch: no valid rank holds the value")
    donors = np.flatnonzero(valid)
    starved = np.flatnonzero(~valid)
    pairs = [
        (int(donors[i % len(donors)]), int(r)) for i, r in enumerate(starved)
    ]
    rounds = _split_rounds(pairs)
    with _dispatch.span(_dispatch.RECOVER, restored=len(starved),
                        rounds=len(rounds)):
        for rnd in rounds:
            got = np.zeros(valid.shape[0], dtype=bool)
            got[[d for _, d in rnd]] = True
            g = comm.take(got)
            recv = comm.exchange(x, rnd)
            x = jax.tree.map(
                lambda cur, rec: comm.bwhere(g, rec, cur), x, recv
            )
        return x


def recover_payload(x, comm: Comm, valid, *, plan=None) -> object:
    """Scheme-dispatching phase-boundary recovery — the only entry drivers
    may call (ruff TID251 bans direct ``replica_fetch`` use outside this
    module).

    * Butterfly plans (or no plan): replication holds full copies of the
      reduced value on every valid rank, so invalid ranks fetch from donors
      (:func:`replica_fetch`).
    * Coded plans (:class:`~repro.collective.coded.CodedPlan`): recovery
      already happened *inside* the collective — erased contributions were
      reconstructed from parity at the root and the broadcast handed the
      result to every recipient (dead data ranks respawned) — so there is
      nothing left to fetch.  An invalid rank here means the erasure budget
      was exceeded; no donor path exists (parity is not a replica), which
      this surfaces as ``ValueError`` instead of silently fetching garbage.
    """
    from .coded import CodedPlan  # local: coded imports this module

    if plan is not None and isinstance(plan, CodedPlan):
        valid = np.asarray(valid, dtype=bool)
        if not valid[: plan.n_data].all():
            raise ValueError(
                "recover_payload: coded recovery happens in-collective; "
                "invalid data ranks after a coded reduce mean the erasure "
                "budget was exceeded and no donor path exists"
            )
        return x
    return replica_fetch(x, comm, valid)


def ft_allreduce(
    x,
    comm: Comm,
    *,
    op: Combiner | str = "sum",
    variant: str = "redundant",
    fault_spec: FaultSpec | None = None,
    plan: Plan | None = None,
    fast: bool | None = None,
):
    """Fault-tolerant all-reduce over the paper's butterfly.

    Fault-free this is exactly the redundant-TSQR communication pattern with
    the requested combiner (ridden on the straight-line fast path); under a
    ``fault_spec`` (or explicit ``plan``) it inherits the variant's
    tolerance — ``2^s − 1`` failures at the entry of exchange ``s`` — and
    survivors end with the full reduction.

    Returns ``(value, valid)``: ``value`` is the finalized reduction (pytree
    like ``x``), ``valid`` the per-rank validity bit.  Invalid ranks hold
    poisoned (NaN) payloads.
    """
    if plan is None:
        plan = make_plan(variant, comm.n_ranks, fault_spec)
    combiner = get_combiner(op)
    with _reduce_span(plan):
        val, valid = _execute(x, comm, plan, combiner, fast)
        return combiner.tree_finalize(val, plan.n_ranks), valid


# ---------------------------------------------------------------------------
# Retrace-proof compiled entry point
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _ft_allreduce_compiled(comm: Comm, plan: Plan, op, fast):
    """One compiled butterfly per ``(comm, plan, combiner)`` — the jit cache
    underneath keys on the payload's ``(treedef, shapes, dtypes)``, so the
    full cache key is exactly ``(plan, combiner-name, treedef, shapes)``."""

    @jax.jit
    def fun(x):
        _dispatch.note_trace("ft_allreduce")
        return ft_allreduce(x, comm, op=op, plan=plan, fast=fast)

    return fun


@functools.lru_cache(maxsize=256)
def _ft_allreduce_shard_compiled(mesh, comm: ShardMapComm, plan: Plan, op, fast):
    """One compiled SPMD butterfly per ``(mesh-equivalence-class, plan,
    combiner)``.  The ``mesh`` position of the key is the equivalence class:
    ``Mesh`` hashes by value (device ids + axis names), so an elastically
    rebuilt mesh over the same devices hits the same entry — the same
    contract the TSQR/blocked shard builders rely on.  The payload keeps the
    SimComm global view (leading ``(P,)`` axis); ``shard_map`` hands each
    rank its ``(1, …)`` slice and the engine runs on local blocks over real
    ``ppermute`` wires, so the returned layout — and, fault-free, the bits —
    match the SimComm program exactly (same plans, same combine order)."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map as _shard_map

    axis = comm.axis

    def body(x):
        _dispatch.note_trace("ft_allreduce")
        local = jax.tree.map(lambda leaf: leaf[0], x)
        val, ok = ft_allreduce(local, comm, op=op, plan=plan, fast=fast)
        return jax.tree.map(lambda leaf: leaf[None], val), ok[None]

    fun = _shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=(P(axis), P(axis))
    )
    return jax.jit(fun)


def ft_allreduce_jit(
    x,
    comm: Comm,
    *,
    op: Combiner | str = "sum",
    variant: str = "redundant",
    fault_spec: FaultSpec | None = None,
    plan: Plan | None = None,
    fast: bool | None = None,
    mesh=None,
):
    """:func:`ft_allreduce` as a cached, zero-retrace device program.

    The plan is hashable-static (value-keyed ``Plan.__hash__``) and the
    combiner resolves to a frozen instance, so the whole butterfly closes
    over them and compiles once per ``(plan, combiner, treedef, shapes)`` —
    a repeat call with identical statics performs **zero** new traces (the
    ``dispatch`` bench case and the CI retrace guard pin this).

    Backends:

    * :class:`~repro.collective.comm.SimComm` — the payload carries the
      leading ``(P,)`` axis; the butterfly compiles standalone.
    * :class:`~repro.collective.comm.ShardMapComm` — pass ``mesh=``; the
      payload keeps the same global ``(P,)``-leading layout and the cached
      compile wraps the butterfly in ``shard_map`` over ``comm.axis``
      (exchanges lower to ``collective-permute``).  The cache keys on the
      mesh *equivalence class* (``Mesh`` hashes by value), so an elastic
      rebuild over the same devices reuses the compile.  Fault-free results
      are bit-identical to the SimComm program; faulted plans degrade
      identically in kind (same validity bits, same poisoned slots).  For a
      collective *inside* an enclosing ``shard_map`` body, keep calling
      :func:`ft_allreduce` directly — the enclosing program is what gets
      compiled there.
    """
    if plan is None:
        plan = make_plan(variant, comm.n_ranks, fault_spec)
    if isinstance(comm, SimComm):
        fun = _ft_allreduce_compiled(comm, plan, get_combiner(op), fast)
    elif isinstance(comm, ShardMapComm):
        if mesh is None:
            raise ValueError(
                "ft_allreduce_jit on ShardMapComm needs mesh= (the Mesh "
                "whose axis the comm permutes over) to build the enclosing "
                "shard_map program"
            )
        if comm.axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} do not include comm axis "
                f"{comm.axis!r}"
            )
        if mesh.shape[comm.axis] != comm.n_ranks:
            raise ValueError(
                f"mesh axis {comm.axis!r} has {mesh.shape[comm.axis]} "
                f"devices but comm.n_ranks={comm.n_ranks}"
            )
        fun = _ft_allreduce_shard_compiled(
            mesh, comm, plan, get_combiner(op), fast
        )
    else:
        raise ValueError(
            f"ft_allreduce_jit supports SimComm and ShardMapComm, got "
            f"{type(comm).__name__}"
        )
    _dispatch.note_dispatch("ft_allreduce")
    return fun(x)
