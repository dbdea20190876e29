"""Checksum-coded redundancy: the second fault-tolerance scheme.

The butterfly (``plan.py`` / ``engine.py``) buys its ``2^s − 1`` tolerance
with *replication*: every exchange doubles the number of full copies of the
partial result, a 100% redundancy overhead in wire traffic, and the copies
are blind to anything that is not a clean process death.  This module
implements the coded-computing alternative (coded parallel QR,
arXiv:2311.11943; Bosilca-style ABFT checksums, arXiv:0806.3121): the ``P``
data ranks are augmented with ``c`` checksum ranks, each holding a fixed
linear combination — *parity* — of the prepared per-rank contributions:

    ``p_j = Σ_i w_{ji} · prepare(x_i)``            (j = 0 .. c−1)

The weights are a Cauchy matrix (``w_{ji} = 1 / (P + j − i)``), so **every**
square submatrix is nonsingular: *any* ℓ ≤ c lost contributions can be
re-solved from *any* ℓ surviving parity lanes (an MDS erasure code).  The
parity is maintained as a data invariant — it is encoded on-device when the
data is distributed, before any fault can strike, and therefore costs no
priced wire (storage/compute redundancy, not communication; see DESIGN.md
§12).

**Topology.**  One coded reduction is four statically-planned phases over
the ``W = P + c`` world (executed by :func:`execute_coded`, each phase its
own ``comm.exchange`` so :class:`~repro.collective.instrument.
InstrumentedComm` observes exactly what :meth:`CodedPlan.bytes_on_wire`
prices — no validity byte ships, the routing is fully host-static):

  1. *gather* — a binomial tree over the ``S`` surviving data ranks to a
     root.  Each message carries the running combine (``tree_combine`` of
     the inner combiner, operands in rank order — for ℓ = 0 this is the
     **same balanced combine tree as the butterfly**, so the fault-free
     result is bit-identical) plus ℓ *reconstruction lanes*: the weighted
     sums ``q_j = Σ_{i∈S} w_{ji} prepare(x_i)``, combined by addition.
     ``(S−1)`` messages of ``(1+ℓ)`` payload units.
  2. *parity sends* — the ℓ parity lanes chosen for decoding each send
     ``p_j`` to the root: the *deficit* ``p_j − q_j = Σ_{i∈lost} w_{ji} x_i``
     restricts the checksum to exactly the lost contributions.  ℓ messages.
  3. *raw sends* — each declared-corrupt rank forwards its raw contribution
     to the root for verification (it is quarantined from phase 1: its
     true value is erasure-decoded like a death's, and the checksum compare
     of raw vs reconstruction is what *detects* the corruption).
  4. *broadcast* — the root solves the ℓ×ℓ Cauchy system (host-computed
     float64 coefficients, applied as trace-static scalars), absorbs the
     reconstructed contributions into the result, and broadcasts it down a
     binomial tree to every data rank (dead data ranks are respawned into
     the result — the selfhealing contract) and every alive parity rank.

Fault semantics beyond the butterfly's:

  * **deaths** — up to ``c`` simultaneous deaths are tolerated *including
    deaths before any exchange* (the butterfly loses a rank-0-step death's
    contribution outright; parity already holds it).
  * **stragglers** (``FaultSpec.slow``) — not awaited: excluded from the
    gather, reconstructed from parity, handed the result in the broadcast.
  * **silent corruption** (``FaultSpec.corrupt``) — reconstructed *and*
    detected: the returned ``detected`` vector flags ranks whose raw
    payload disagrees with its parity reconstruction beyond the dtype's
    documented tolerance.
  * **over-budget erasures** (ℓ > alive parity lanes) — honest degradation:
    no routing exists, the plan is marked unrecoverable, every rank returns
    ``valid=False`` with NaN-poisoned payloads.  No silent garbage.

Reconstruction re-orders the combine (lost rows are absorbed after the
survivor fold) and rides float weights, so faulted results match the
fault-free value to a documented fp bound rather than bitwise — see
:func:`reconstruction_tol`; ℓ = 0 is bitwise.

SimComm-only, like :func:`~repro.collective.engine.ft_allreduce_jit`:
standalone compilation of the coded program implies the (W,)-leading
simulated layout.  The payload may be any pytree the inner combiner
accepts, including :class:`~repro.collective.combiners.StackedCombiner`
tuples — lane weights are scalars, applied tree-wide.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch as _dispatch

from .combiners import Combiner, get_combiner
from .comm import Comm, ShardMapComm, SimComm
from .engine import _poison, _wire_codec
from .faults import FaultSpec
from .instrument import InstrumentedComm
from .plan import leaf_bytes, payload_numel

__all__ = [
    "CodedCombiner",
    "CodedPlan",
    "coded_allreduce",
    "coded_allreduce_jit",
    "coded_weights",
    "encode_parity",
    "execute_coded",
    "make_coded_plan",
    "reconstruction_tol",
]

Pair = tuple[int, int]


def coded_weights(n_data: int, n_parity: int) -> np.ndarray:
    """The ``(c, P)`` Cauchy checksum-weight matrix ``w_{ji} = 1/(P+j−i)``.

    Node sets ``{P+j}`` and ``{i}`` are disjoint, so every square submatrix
    is nonsingular (the Cauchy determinant): any ℓ erasures are decodable
    from any ℓ surviving lanes.  Entries live in ``(0, 1]`` — parity stays
    at the payload's magnitude, unlike Vandermonde powers.
    """
    a = np.arange(n_data, n_data + n_parity, dtype=np.float64)
    b = np.arange(n_data, dtype=np.float64)
    return 1.0 / (a[:, None] - b[None, :])


def reconstruction_tol(dtype) -> float:
    """Documented fp bound for parity reconstruction, relative to payload
    magnitude: decode solves an ℓ×ℓ Cauchy system whose conditioning (ℓ ≤ c,
    small) amplifies rounding by a few orders of magnitude over machine eps.
    ``sqrt(eps) · 8`` covers the worst observed case with ~10× margin; it is
    also the threshold separating fp noise from genuine corruption in the
    checksum verification."""
    return float(np.sqrt(np.finfo(np.dtype(dtype)).eps) * 8.0)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class CodedPlan:
    """Host-computed static routing for one coded reduction.

    Mirrors :class:`~repro.collective.plan.Plan`'s contract: numpy fields,
    value-keyed hash (plans key jit/LRU caches), and exact communication
    accounting.  ``erased`` is the union of dead, slow, and corrupt *data*
    ranks — everything reconstructed from parity; ``parity_used`` the global
    ids of the lanes consumed; ``decode[e, t]`` the float64 coefficient of
    deficit ``t`` in the reconstruction of ``erased[e]``.
    """

    n_data: int
    n_parity: int
    death: np.ndarray            # (W,) effective death vector consumed
    erased: tuple[int, ...]      # data ranks reconstructed from parity
    corrupt: tuple[int, ...]     # alive data ranks verified against parity
    slow: tuple[int, ...]        # stragglers (reconstructed, not awaited)
    survivors: tuple[int, ...]   # data ranks in the gather tree
    parity_used: tuple[int, ...]  # global rank ids of consumed parity lanes
    root: int
    gather_rounds: tuple[tuple[Pair, ...], ...]
    bcast_rounds: tuple[tuple[Pair, ...], ...]
    final_valid: np.ndarray      # (W,) who holds the final value
    weights: np.ndarray          # (c, P) float64 checksum weights
    decode: np.ndarray           # (l, l) float64 erasure-decode coefficients
    recoverable: bool

    # -- value identity (hashable-static, same contract as Plan) ------------
    @functools.cached_property
    def _sig(self) -> tuple:
        return (
            self.n_data,
            self.n_parity,
            self.death.tobytes(),
            self.erased,
            self.corrupt,
            self.slow,
            self.survivors,
            self.parity_used,
            self.root,
            self.gather_rounds,
            self.bcast_rounds,
            self.final_valid.tobytes(),
            self.weights.tobytes(),
            self.decode.tobytes(),
            self.recoverable,
        )

    def __eq__(self, other) -> bool:
        return isinstance(other, CodedPlan) and self._sig == other._sig

    def __hash__(self) -> int:
        return hash(self._sig)

    @property
    def n_ranks(self) -> int:
        """World size ``W = P + c`` (the comm the plan executes over)."""
        return self.n_data + self.n_parity

    @property
    def n_erased(self) -> int:
        return len(self.erased)

    @functools.cached_property
    def is_fault_free(self) -> bool:
        return self.recoverable and not self.erased

    # -- communication accounting (the coded bench case hard-gates this) ----
    def message_count(self) -> int:
        """Point-to-point messages: gather + parity sends + raw sends +
        broadcast.  Zero when unrecoverable — nothing useful can ship."""
        if not self.recoverable:
            return 0
        return (
            (len(self.survivors) - 1)
            + len(self.parity_used)
            + len(self.corrupt)
            + self._n_bcast()
        )

    def round_count(self) -> int:
        """Serial communication rounds — the latency proxy.  Parity/raw
        sends serialize per message (all target the root)."""
        if not self.recoverable:
            return 0
        return (
            len(self.gather_rounds)
            + len(self.parity_used)
            + len(self.corrupt)
            + len(self.bcast_rounds)
        )

    def _n_bcast(self) -> int:
        return sum(len(r) for r in self.bcast_rounds)

    def payload_units(self) -> int:
        """Messages weighted by payload multiplicity: gather messages carry
        the result plus ℓ reconstruction lanes — ``(1+ℓ)`` payload units —
        everything else carries one.  This is the factor
        :meth:`bytes_on_wire` prices, and exactly what the executor ships
        (``InstrumentedComm`` observes the agreement)."""
        if not self.recoverable:
            return 0
        l = len(self.erased)
        return (
            (len(self.survivors) - 1) * (1 + l)
            + len(self.parity_used)
            + len(self.corrupt)
            + self._n_bcast()
        )

    def bytes_on_wire(
        self, n_cols: int, itemsize: int = 4, *, symmetric: bool = False
    ) -> int:
        """Total payload bytes moved by the plan (cf. ``Plan.bytes_on_wire``
        — but weighted per message by :meth:`payload_units`, since gather
        messages stack reconstruction lanes next to the result)."""
        return self.payload_units() * payload_numel(n_cols, symmetric) * itemsize

    def bytes_on_wire_stacked(self, leaves) -> int:
        """Exact wire bytes for a stacked / multi-leaf payload; ``leaves``
        are ``(rows, cols, itemsize, symmetric)`` specs as in
        ``Plan.bytes_on_wire_stacked``."""
        per_unit = sum(leaf_bytes(*spec) for spec in leaves)
        return self.payload_units() * per_unit


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def _binomial_gather(members: list[int]) -> tuple[tuple[Pair, ...], ...]:
    """Binomial gather to ``members[0]``; the receiver of every pair has the
    lower list index, so the combine is the same balanced in-order tree the
    butterfly computes (bitwise-identical result for a full power-of-two
    member list)."""
    rounds: list[tuple[Pair, ...]] = []
    n, s = len(members), 0
    while (1 << s) < n:
        pairs = [
            (members[i + (1 << s)], members[i])
            for i in range(0, n, 2 << s)
            if i + (1 << s) < n
        ]
        rounds.append(tuple(pairs))
        s += 1
    return tuple(rounds)


def _binomial_bcast(members: list[int]) -> tuple[tuple[Pair, ...], ...]:
    """Binomial broadcast from ``members[0]``: coverage doubles per round,
    ``len(members) − 1`` messages, unique sources and destinations."""
    rounds: list[tuple[Pair, ...]] = []
    n, have = len(members), 1
    while have < n:
        rounds.append(tuple(
            (members[i], members[i + have]) for i in range(min(have, n - have))
        ))
        have *= 2
    return tuple(rounds)


@functools.lru_cache(maxsize=512)
def _make_coded_plan_cached(
    n_data: int, n_parity: int, spec: FaultSpec
) -> CodedPlan:
    w = n_data + n_parity
    death = spec.death_vector(w)
    # The coded collective has no butterfly steps: a listed death, whatever
    # its step, is conservatively absent for the whole reduction (parity was
    # encoded at distribution time, before any death — the invariant that
    # makes even a step-0 death recoverable).
    dead = {r for r, _ in spec.deaths}
    slow = set(spec.slow)
    corrupt = set(spec.corrupt)
    for kind, rs in (("corrupt", corrupt), ("slow", slow)):
        bad = [r for r in rs if r >= w]
        if bad:
            raise ValueError(f"{kind} ranks {bad} out of range for W={w}")
    weights = coded_weights(n_data, n_parity)
    # Usable parity lanes: alive, on time, and themselves uncorrupted.  A
    # corrupt or slow parity rank is simply an unusable lane (there is no
    # second-order checksum to verify parity against).
    parity_ok = [
        r for r in range(n_data, w)
        if r not in dead and r not in slow and r not in corrupt
    ]
    erased = tuple(sorted(
        i for i in range(n_data) if i in dead or i in slow or i in corrupt
    ))
    corrupt_data = tuple(sorted(i for i in range(n_data) if i in corrupt))
    survivors = tuple(i for i in range(n_data) if i not in set(erased))
    l = len(erased)
    recoverable = l <= len(parity_ok) and len(survivors) > 0
    if not recoverable:
        return CodedPlan(
            n_data=n_data, n_parity=n_parity, death=death, erased=erased,
            corrupt=corrupt_data, slow=tuple(sorted(slow)),
            survivors=survivors, parity_used=(), root=-1,
            gather_rounds=(), bcast_rounds=(),
            final_valid=np.zeros(w, dtype=bool), weights=weights,
            decode=np.zeros((0, 0)), recoverable=False,
        )
    parity_used = tuple(parity_ok[:l])
    root = survivors[0]
    # Broadcast recipients: every data rank (dead data ranks are respawned
    # into the result — the selfhealing contract, so the blocked driver's
    # later panels see a full complement) plus every alive parity rank.
    recips = [
        r for r in range(w)
        if r != root and (r < n_data or r not in dead)
    ]
    if l:
        sub = weights[
            np.array([p - n_data for p in parity_used], dtype=np.intp)[:, None],
            np.array(erased, dtype=np.intp)[None, :],
        ]
        decode = np.linalg.inv(sub)
    else:
        decode = np.zeros((0, 0))
    final_valid = np.ones(w, dtype=bool)
    for r in range(n_data, w):
        final_valid[r] = r not in dead
    return CodedPlan(
        n_data=n_data, n_parity=n_parity, death=death, erased=erased,
        corrupt=corrupt_data, slow=tuple(sorted(slow)),
        survivors=survivors, parity_used=parity_used, root=root,
        gather_rounds=_binomial_gather(list(survivors)),
        bcast_rounds=_binomial_bcast([root] + recips),
        final_valid=final_valid, weights=weights, decode=decode,
        recoverable=True,
    )


def make_coded_plan(
    n_data: int,
    n_parity: int,
    fault_spec: FaultSpec | None = None,
) -> CodedPlan:
    """Host-plan a coded reduction over ``n_data`` data + ``n_parity``
    checksum ranks.  Memoized on ``(P, c, spec)`` like :func:`make_plan`;
    the returned plan is hashable-static and keys jit caches."""
    if n_data < 1:
        raise ValueError(f"need at least one data rank, got {n_data}")
    if n_parity < 1:
        raise ValueError(
            f"coded redundancy needs at least one parity rank, got {n_parity}"
        )
    return _make_coded_plan_cached(n_data, n_parity, fault_spec or FaultSpec.none())


# ---------------------------------------------------------------------------
# Encode / decode combiner family
# ---------------------------------------------------------------------------

def encode_parity(prepared, plan: CodedPlan):
    """Overwrite the ``c`` parity rows of a (W,)-leading prepared payload
    with the checksum linear combinations of the data rows.

    This is the distribution-time invariant: an on-device einsum, **outside**
    any ``comm.exchange`` — parity costs compute and storage, never priced
    wire (DESIGN.md §12).  Works leaf-wise over any payload pytree.
    """
    p = plan.n_data

    def enc(leaf):
        wts = jnp.asarray(plan.weights, dtype=leaf.dtype)
        parity = jnp.tensordot(wts, leaf[:p], axes=(1, 0))
        return leaf.at[p:].set(parity)

    return jax.tree.map(enc, prepared)


@dataclasses.dataclass(frozen=True)
class CodedCombiner(Combiner):
    """Encode/reduce/decode on the tree-payload protocol, generic over any
    inner combiner (sum/mean/max/gram_sum/qr, including stacked tuples).

    ``tree_prepare`` composes the inner prepare with the parity encode;
    ``tree_combine``/``tree_finalize`` delegate (finalize normalizes by the
    *data* rank count — parity adds no data).  The lane/decode/verify
    methods are the coded-specific algebra :func:`execute_coded` drives:
    reconstruction lanes are weighted sums (scalar weights applied
    tree-wide, so any inner payload structure works), decode applies the
    host-solved Cauchy coefficients, and ``absorb`` folds reconstructed
    contributions back through the inner combine.
    """

    inner: Combiner = None  # type: ignore[assignment]
    plan: CodedPlan = None  # type: ignore[assignment]
    name = "coded"

    def __post_init__(self):
        if self.inner is None or self.plan is None:
            raise ValueError("CodedCombiner needs an inner combiner and a plan")

    # -- tree-payload protocol ---------------------------------------------
    def tree_prepare(self, x):
        return encode_parity(self.inner.tree_prepare(x), self.plan)

    def tree_combine(self, lo, hi):
        return self.inner.tree_combine(lo, hi)

    def tree_finalize(self, x, n_ranks: int):
        return self.inner.tree_finalize(x, self.plan.n_data)

    def wire_pack_flags(self, val) -> list[bool]:
        return self.inner.wire_pack_flags(val)

    # -- per-leaf protocol has no meaning (encode is positional over ranks) -
    def prepare(self, x):
        raise TypeError("CodedCombiner operates at tree level")

    def combine(self, lo, hi):
        raise TypeError("CodedCombiner operates at tree level")

    def finalize(self, x, n_ranks: int):
        raise TypeError("CodedCombiner operates at tree level")

    # -- coded-specific algebra --------------------------------------------
    def make_lanes(self, val):
        """Per-rank reconstruction lanes: leaf ``(W, ...)`` → ``(W, ℓ, ...)``
        with lane ``t`` holding ``w_{t,i} · val_i`` on survivor rows (zero on
        erased and parity rows — they do not feed the gather)."""
        plan = self.plan
        w_, l = plan.n_ranks, len(plan.erased)
        lane_w = np.zeros((w_, l))
        for t, pr in enumerate(plan.parity_used):
            lane_w[: plan.n_data, t] = plan.weights[pr - plan.n_data]
        lane_w[list(plan.erased), :] = 0.0

        def mk(leaf):
            wv = jnp.asarray(lane_w, dtype=leaf.dtype)
            wv = wv.reshape((w_, l) + (1,) * (leaf.ndim - 1))
            return leaf[:, None] * wv

        return jax.tree.map(mk, val)

    def lane_combine(self, acc, recv):
        """Lanes are weighted sums: combine by addition (zeros from
        non-receivers are the identity)."""
        return jax.tree.map(jnp.add, acc, recv)

    def decode_erased(self, deficits):
        """Solve the erasure system: ``deficits[t] = p_t − q_t`` (payload
        trees) → ``{erased_rank: reconstructed contribution}``.  The decode
        coefficients are trace-static host float64 scalars."""
        dec = self.plan.decode
        out = {}
        for e_idx, er in enumerate(self.plan.erased):
            acc = None
            for t in range(len(deficits)):
                term = jax.tree.map(
                    lambda d, c=float(dec[e_idx, t]): c * d, deficits[t]
                )
                acc = term if acc is None else jax.tree.map(jnp.add, acc, term)
            out[er] = acc
        return out

    def absorb(self, res, reconstructed):
        """Fold the reconstructed contributions into the survivor result in
        erased-rank order (this re-orders the combine relative to the
        fault-free tree — the documented fp deviation)."""
        for er in self.plan.erased:
            res = self.inner.tree_combine(res, reconstructed[er])
        return res

    def verify(self, raw, reconstructed):
        """Checksum verification: does the raw payload of a declared-corrupt
        rank disagree with its parity reconstruction beyond fp noise?
        Returns a device bool."""
        err = None
        scale = None
        for a, b in zip(jax.tree.leaves(raw), jax.tree.leaves(reconstructed)):
            e = jnp.max(jnp.abs(a - b))
            s = jnp.max(jnp.abs(b))
            err = e if err is None else jnp.maximum(err, e)
            scale = s if scale is None else jnp.maximum(scale, s)
        dtypes = [leaf.dtype for leaf in jax.tree.leaves(raw)]
        tol = max(reconstruction_tol(dt) for dt in dtypes)
        return err > tol * (scale + 1.0)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def _base_comm(comm: Comm) -> Comm:
    return comm.inner if isinstance(comm, InstrumentedComm) else comm


def _pad_world(x, plan: CodedPlan):
    """Accept a data-only (P,)-leading payload and zero-extend the parity
    rows (they are overwritten by the encode)."""
    def pad(leaf):
        if leaf.shape[0] == plan.n_ranks:
            return leaf
        if leaf.shape[0] == plan.n_data:
            z = jnp.zeros((plan.n_parity,) + leaf.shape[1:], leaf.dtype)
            return jnp.concatenate([leaf, z], axis=0)
        raise ValueError(
            f"payload leading axis {leaf.shape[0]} matches neither P="
            f"{plan.n_data} nor W={plan.n_ranks}"
        )

    return jax.tree.map(pad, x)


def execute_coded(
    x,
    comm: Comm,
    plan: CodedPlan,
    combiner: Combiner | str,
    *,
    observed=None,
):
    """Run one coded reduction.  Returns ``(value, valid, detected)``.

    ``x`` is a pytree of per-rank payloads with a leading ``(P,)`` or
    ``(W,)`` axis (``SimComm(W)`` layout; parity rows are recomputed by the
    encode either way).  ``value`` is the un-finalized combine on every
    valid rank; ``valid`` the per-rank host-predicted validity
    (``plan.final_valid``); ``detected`` a ``(W,)`` device bool flagging
    ranks whose payload failed checksum verification.  Each phase issues
    its own exchanges, so observed traffic equals
    ``plan.bytes_on_wire{,_stacked}`` exactly — no validity byte ships.

    ``observed`` models silent data corruption faithfully: parity is
    encoded from ``x`` (the truth at distribution time, *before* any fault
    strikes — the ABFT invariant), while ranks contribute from ``observed``
    (what they actually hold now; defaults to ``x``).  A scenario injects
    SDC by mutating a declared-corrupt rank's row of ``observed`` only —
    the checksum compare of the raw observed payload against its parity
    reconstruction is then a *numerical* detection, not an echo of the
    fault spec.
    """
    with _dispatch.span(_dispatch.REDUCE, rounds=plan.round_count(),
                        messages=plan.message_count()):
        return _execute_coded(x, comm, plan, combiner, observed)


def _execute_coded(x, comm: Comm, plan: CodedPlan, combiner, observed):
    inner = get_combiner(combiner)
    if isinstance(inner, CodedCombiner):
        coded = inner
        inner = coded.inner
    else:
        coded = CodedCombiner(inner=inner, plan=plan)
    if isinstance(_base_comm(comm), ShardMapComm):
        raise ValueError(
            "coded collectives execute on the SimComm backend only: the "
            "root-side decode indexes rank rows of the (W,)-leading layout"
        )
    w_ = plan.n_ranks
    if comm.n_ranks != w_:
        raise ValueError(
            f"comm has {comm.n_ranks} ranks but the plan's world is "
            f"W = {plan.n_data} + {plan.n_parity} = {w_}"
        )
    x = _pad_world(x, plan)
    for leaf in jax.tree.leaves(x):
        if not jnp.issubdtype(leaf.dtype, jnp.inexact):
            raise TypeError(
                "coded redundancy requires an inexact payload dtype (the "
                f"checksum weights are non-integer), got {leaf.dtype}"
            )
    val = coded.tree_prepare(x)
    if observed is not None:
        # Data rows contribute what the ranks hold *now* (possibly silently
        # corrupted); parity rows keep the distribution-time encode of the
        # truth — corruption cannot strike data and checksum coherently.
        vobs = inner.tree_prepare(_pad_world(observed, plan))
        p = plan.n_data
        val = jax.tree.map(lambda t, o: o.at[p:].set(t[p:]), val, vobs)
    detected = jnp.zeros((w_,), dtype=bool)
    if not plan.recoverable:
        # Honest degradation: more erasures than parity lanes (or no data
        # survivor).  Nothing can ship; poison everything, validity False.
        return (
            jax.tree.map(_poison, val),
            comm.take(plan.final_valid),
            detected,
        )
    pack, unpack = _wire_codec(inner, val)
    l = len(plan.erased)
    root = plan.root
    # --- phase 1: binomial gather over survivors, result + ℓ lanes ---------
    lanes = None
    if l:
        lanes = coded.make_lanes(val)
        lpack, lunpack = _wire_codec(inner, lanes)
    for pairs in plan.gather_rounds:
        got = np.zeros(w_, dtype=bool)
        got[[d for _, d in pairs]] = True
        g = comm.take(got)
        if l:
            rv, rl = comm.exchange((pack(val), lpack(lanes)), pairs)
            lanes = coded.lane_combine(lanes, lunpack(rl))
        else:
            rv = comm.exchange(pack(val), pairs)
        comb = coded.tree_combine(val, unpack(rv))  # receiver is lo
        val = jax.tree.map(lambda c, v: comm.bwhere(g, c, v), comb, val)
    # --- phase 2: parity sends → deficits p_t − q_t ------------------------
    deficits = []
    for t, pr in enumerate(plan.parity_used):
        rv = unpack(comm.exchange(pack(val), ((pr, root),)))
        deficits.append(jax.tree.map(
            lambda r, ln, t=t: r[root] - ln[root, t], rv, lanes
        ))
    # --- phase 3: raw sends from declared-corrupt ranks --------------------
    raws = {}
    for ci in plan.corrupt:
        rv = unpack(comm.exchange(pack(val), ((ci, root),)))
        raws[ci] = jax.tree.map(lambda r: r[root], rv)
    # --- decode + absorb + verify (root-local compute, no wire) ------------
    res = jax.tree.map(lambda v: v[root], val)
    if l:
        reconstructed = coded.decode_erased(deficits)
        res = coded.absorb(res, reconstructed)
        for ci in plan.corrupt:
            detected = detected.at[ci].set(
                coded.verify(raws[ci], reconstructed[ci])
            )
    val = jax.tree.map(lambda v, r: v.at[root].set(r), val, res)
    # --- phase 4: binomial broadcast root → all recipients -----------------
    for pairs in plan.bcast_rounds:
        got = np.zeros(w_, dtype=bool)
        got[[d for _, d in pairs]] = True
        g = comm.take(got)
        rv = unpack(comm.exchange(pack(val), pairs))
        val = jax.tree.map(lambda r, v: comm.bwhere(g, r, v), rv, val)
    # Dead parity rows never receive: poison them so accidental use is loud.
    fv = comm.take(plan.final_valid)
    val = jax.tree.map(lambda v: comm.bwhere(fv, v, _poison(v)), val)
    return val, fv, detected


def coded_allreduce(
    x,
    comm: Comm,
    *,
    op: Combiner | str = "sum",
    n_parity: int | None = None,
    fault_spec: FaultSpec | None = None,
    plan: CodedPlan | None = None,
    observed=None,
):
    """Checksum-coded fault-tolerant all-reduce (cf. :func:`ft_allreduce`).

    ``comm`` spans the ``W = P + c`` world; pass either a prebuilt ``plan``
    or ``n_parity`` (with an optional ``fault_spec`` naming deaths /
    stragglers / corruptions in world coordinates).  Returns ``(value,
    valid, detected)`` with the finalized reduction of the ``P`` data
    contributions on every valid rank.  ``observed`` — see
    :func:`execute_coded`.
    """
    if plan is None:
        if n_parity is None:
            raise ValueError("coded_allreduce needs a plan or n_parity")
        plan = make_coded_plan(comm.n_ranks - n_parity, n_parity, fault_spec)
    combiner = get_combiner(op)
    val, valid, detected = execute_coded(
        x, comm, plan, combiner, observed=observed
    )
    val = combiner.tree_finalize(val, plan.n_data)
    return val, valid, detected


# ---------------------------------------------------------------------------
# Retrace-proof compiled entry point
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _coded_allreduce_compiled(comm: Comm, plan: CodedPlan, op):
    @jax.jit
    def fun(x, observed):
        _dispatch.note_trace("coded_allreduce")
        return coded_allreduce(x, comm, op=op, plan=plan, observed=observed)

    return fun


def coded_allreduce_jit(
    x,
    comm: Comm,
    *,
    op: Combiner | str = "sum",
    n_parity: int | None = None,
    fault_spec: FaultSpec | None = None,
    plan: CodedPlan | None = None,
    observed=None,
):
    """:func:`coded_allreduce` as a cached, zero-retrace device program —
    the same contract as :func:`~repro.collective.engine.ft_allreduce_jit`
    (SimComm only; the plan and combiner are hashable statics, so a repeat
    call with identical statics performs zero new traces — pinned by the CI
    retrace guard)."""
    if not isinstance(comm, SimComm):
        raise ValueError(
            "coded_allreduce_jit compiles a standalone program, which only "
            "the SimComm backend supports"
        )
    if plan is None:
        if n_parity is None:
            raise ValueError("coded_allreduce_jit needs a plan or n_parity")
        plan = make_coded_plan(comm.n_ranks - n_parity, n_parity, fault_spec)
    fun = _coded_allreduce_compiled(comm, plan, get_combiner(op))
    _dispatch.note_dispatch("coded_allreduce")
    return fun(x, observed)
