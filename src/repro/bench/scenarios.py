"""Declarative fault-scenario engine (DESIGN.md §5).

Scenario diversity beyond single-round Monte-Carlo: a scenario is a small
declarative schedule — which rank/replica fails at which butterfly step or
training step — executed deterministically and distilled into hard-gated
metrics.  Two scenario kinds:

* :class:`CollectiveScenario` — a sequence of :class:`ReduceRound`\\ s,
  each one ``ft_allreduce`` invocation over a
  :class:`~repro.collective.comm.SimComm` with (a) *masked* replicas
  (BLANK semantics: the rank participates but its contribution is zeroed)
  and (b) mid-reduce *deaths* (``{rank: butterfly_step}``, the paper's
  fail-stop model).  Survivor values are checked against the dense
  reduction of the masked inputs, and comm volume is measured through
  :class:`~repro.collective.instrument.InstrumentedComm`.

* :class:`TrainerScenario` — a :class:`~repro.runtime.trainer.FaultEvent`
  schedule driven through a real (tiny) :class:`Trainer` on a
  ``(data, model)`` mesh, exercising the SHRINK / REBUILD / BLANK
  semantics end to end; assertions read the trainer's structured
  ``fault_stats`` counters.  Needs enough (simulated) devices — the bench
  CLI forces 8 host devices; under-provisioned environments skip.

* :class:`BlockedQRScenario` — a :class:`~repro.qr.blocked.
  PanelFaultSchedule` driven through the general-matrix blocked QR
  (:mod:`repro.qr.blocked`): deaths during a panel's TSQR reduction or its
  trailing-update (W) butterfly, evaluated per panel against the variant's
  guarantee, with the one-trailing-sweep-per-panel HBM model measured
  through :mod:`repro.kernels.traffic`.

The stock :data:`SCENARIOS` sweep covers the scenario families the
single-round Monte-Carlo misses: **correlated** block wipes, **cascading**
step-after-step failures, **fail-during-rebuild** (a second failure while
the first rollback is still replaying), **BLANK-under-repeat** (masking +
mid-reduce faults across repeated reductions), and the per-panel blocked-QR
families (**death during panel k**, **death during the trailing update**,
**cascading panels**).
"""
from __future__ import annotations

import dataclasses
import tempfile
from collections.abc import Mapping

import numpy as np

from repro.bench.registry import BenchFailure, SkipCase, bench_case, require_devices
from repro.bench.schema import Metric

__all__ = [
    "BlockedQRScenario",
    "CollectiveScenario",
    "ReduceRound",
    "TrainerScenario",
    "case",
    "get_scenarios",
    "run_blocked_qr_scenario",
    "run_collective_scenario",
    "run_scenario",
    "run_trainer_scenario",
]


# ---------------------------------------------------------------------------
# Scenario formats
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReduceRound:
    """One all-reduce invocation inside a repeated-reduction scenario.

    ``corrupt`` / ``slow`` are only actionable under the coded scheme
    (``CollectiveScenario.scheme="coded"``): corrupted ranks have their
    *observed* payload silently perturbed (the rank does not know), and
    straggling ranks are excluded from the gather — both contributions are
    reconstructed from parity, and corruptions are flagged by checksum
    verification.  The butterfly planners ignore both fields by design.
    """

    deaths: tuple[tuple[int, int], ...] = ()   # (rank, butterfly step)
    masked: tuple[int, ...] = ()               # BLANK-masked replicas
    corrupt: tuple[int, ...] = ()              # silent data corruption (SDC)
    slow: tuple[int, ...] = ()                 # stragglers


@dataclasses.dataclass(frozen=True)
class CollectiveScenario:
    name: str
    p: int
    variant: str
    rounds: tuple[ReduceRound, ...] = (ReduceRound(),)
    op: str = "sum"
    scheme: str = "butterfly"                  # "butterfly" | "coded"
    parity: int = 2                            # checksum ranks (coded only)
    description: str = ""

    kind = "collective"


@dataclasses.dataclass(frozen=True)
class TrainerScenario:
    name: str
    on_failure: str                      # blank | shrink | rebuild
    events: tuple = ()                   # FaultEvent schedule
    data_width: int = 4
    model_width: int = 1
    steps: int = 8
    ckpt_every: int = 3
    buddy_levels: int = 1
    arch: str = "olmo-1b"                # any configs/ registry name
    optimizer: str = "adamw"             # adamw | powersgd | orthosgd | lowrank
    n_layers: int = 2
    expect: Mapping[str, int] = dataclasses.field(default_factory=dict)
    description: str = ""

    kind = "trainer"


@dataclasses.dataclass(frozen=True)
class BlockedQRScenario:
    """Deaths scheduled into a general-matrix blocked QR.

    ``panel_deaths`` / ``update_deaths`` map panel index →
    ``((rank, butterfly_step), …)`` for that panel's TSQR reduction (phase
    1) resp. its trailing-update W butterfly (phase 3).
    """

    name: str
    p: int
    variant: str
    m_local: int = 64
    n: int = 24
    panel_width: int = 8
    panel_deaths: tuple[tuple[int, tuple[tuple[int, int], ...]], ...] = ()
    update_deaths: tuple[tuple[int, tuple[tuple[int, int], ...]], ...] = ()
    description: str = ""

    kind = "blocked"


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------

def _run_coded_scenario(sc: CollectiveScenario, seed: int = 0) -> dict:
    """Coded-scheme executor: deaths, stragglers, and *injected* silent
    corruption (the observed payload is perturbed; parity still encodes the
    distribution-time truth) per round, with checksum-detection and
    wire-accounting hard gates."""
    import jax.numpy as jnp

    from repro.collective import (
        FaultSpec,
        InstrumentedComm,
        SimComm,
        coded_allreduce,
        make_coded_plan,
        reconstruction_tol,
    )

    rng = np.random.default_rng(seed)
    comm = InstrumentedComm(SimComm(sc.p + sc.parity))
    metrics: dict[str, Metric] = {}
    all_match = True
    all_survived = True
    all_detected = True
    honest = True
    expect_msgs = expect_bytes = 0
    for i, rnd in enumerate(sc.rounds):
        spec = FaultSpec.of(
            dict(rnd.deaths), corrupt=rnd.corrupt, slow=rnd.slow
        )
        plan = make_coded_plan(sc.p, sc.parity, spec)
        x = rng.normal(size=(sc.p, 4, 4)).astype(np.float32)
        x[list(rnd.masked)] = 0.0                  # BLANK: zero contribution
        observed = x.copy()
        observed[list(rnd.corrupt)] *= 3.0         # inject the SDC
        val, valid, det = coded_allreduce(
            jnp.asarray(x), comm, op=sc.op, plan=plan,
            observed=jnp.asarray(observed),
        )
        valid = np.asarray(valid)[: sc.p]
        det = np.asarray(det)[: sc.p]
        expect = x.sum(0)      # truth: erased contributions reconstructed
        tol = reconstruction_tol(np.float32)
        holders = np.nonzero(valid)[0]
        match = bool(holders.size) and all(
            np.allclose(np.asarray(val)[r], expect, rtol=tol, atol=tol)
            for r in holders
        )
        in_tol = plan.recoverable
        metrics[f"round{i}_survivors"] = Metric(
            int(valid.sum()), gate="hard", direction="exact"
        )
        metrics[f"round{i}_within_tolerance"] = Metric(
            in_tol, gate="hard", direction="exact"
        )
        if in_tol:                                 # guarantee applies
            all_match &= match
            all_survived &= bool(valid.any())
            all_detected &= bool(
                (np.flatnonzero(det) == np.asarray(rnd.corrupt)).all()
            )
        else:                                      # honest degradation
            honest &= not valid.any() and not match
        expect_msgs += plan.message_count()
        expect_bytes += plan.bytes_on_wire(4, 4)
    metrics["values_match"] = Metric(all_match, gate="hard", direction="exact")
    metrics["survived"] = Metric(all_survived, gate="hard", direction="exact")
    metrics["corruption_detected"] = Metric(
        all_detected, gate="hard", direction="exact"
    )
    metrics["honest_degradation"] = Metric(
        honest, gate="hard", direction="exact"
    )
    metrics["messages"] = Metric(
        comm.stats.messages, gate="hard", direction="exact"
    )
    metrics["wire_matches_plan"] = Metric(
        comm.stats.messages == expect_msgs
        and comm.stats.payload_bytes == expect_bytes,
        gate="hard", direction="exact",
    )
    metrics["payload_bytes"] = Metric(
        comm.stats.payload_bytes, gate="hard", direction="exact", unit="B"
    )
    return metrics


def run_collective_scenario(sc: CollectiveScenario, seed: int = 0) -> dict:
    """Execute every round; return metric dict (unprefixed names)."""
    import jax.numpy as jnp

    from repro.collective import (
        FaultSpec,
        InstrumentedComm,
        SimComm,
        ft_allreduce,
        ilog2,
        make_plan,
        within_tolerance,
    )

    if sc.scheme == "coded":
        return _run_coded_scenario(sc, seed)
    if any(rnd.corrupt or rnd.slow for rnd in sc.rounds):
        raise ValueError(
            f"scenario {sc.name}: corrupt/slow rounds need scheme='coded' "
            "(the butterfly planners ignore both fault kinds by design)"
        )
    rng = np.random.default_rng(seed)
    comm = InstrumentedComm(SimComm(sc.p))
    n_steps = ilog2(sc.p)
    metrics: dict[str, Metric] = {}
    all_match = True
    all_survived = True
    for i, rnd in enumerate(sc.rounds):
        spec = FaultSpec.of(dict(rnd.deaths))
        plan = make_plan(sc.variant, sc.p, spec)
        x = rng.normal(size=(sc.p, 4, 4)).astype(np.float32)
        x[list(rnd.masked)] = 0.0                      # BLANK: zero contribution
        val, valid = ft_allreduce(jnp.asarray(x), comm, op=sc.op, plan=plan)
        valid = np.asarray(valid)
        expect = x.sum(0)                              # full reduction over P
        holders = np.nonzero(valid)[0]
        match = bool(holders.size) and all(
            np.allclose(np.asarray(val)[r], expect, rtol=1e-5, atol=1e-5)
            for r in holders
        )
        in_tol = within_tolerance(sc.variant, spec, n_steps)
        metrics[f"round{i}_survivors"] = Metric(
            int(valid.sum()), gate="hard", direction="exact"
        )
        if in_tol:                                     # guarantee applies
            all_match &= match
            all_survived &= bool(valid.any())
        metrics[f"round{i}_within_tolerance"] = Metric(
            in_tol, gate="hard", direction="exact"
        )
    metrics["values_match"] = Metric(all_match, gate="hard", direction="exact")
    metrics["survived"] = Metric(all_survived, gate="hard", direction="exact")
    metrics["messages"] = Metric(
        comm.stats.messages, gate="hard", direction="exact"
    )
    metrics["comm_rounds"] = Metric(
        comm.stats.rounds, gate="hard", direction="exact"
    )
    metrics["payload_bytes"] = Metric(
        comm.stats.payload_bytes, gate="hard", direction="exact", unit="B"
    )
    return metrics


def run_blocked_qr_scenario(sc: BlockedQRScenario, seed: int = 0) -> dict:
    """Run the blocked QR under the death schedule; metric dict.

    Hard-gates: survivors match the host prediction, every strict
    survivor's R equals the dense oracle whenever the schedule is within
    the variant's per-panel tolerance, and the trailing block is swept
    exactly once per panel (the fused-pipeline HBM claim).
    """
    import jax.numpy as jnp

    from repro.kernels import traffic
    from repro.qr import PanelFaultSchedule, QRConfig, factorize

    rng = np.random.default_rng(seed)
    blocks = rng.standard_normal((sc.p, sc.m_local, sc.n)).astype(np.float32)
    sched = PanelFaultSchedule.of(
        panel={k: dict(deaths) for k, deaths in sc.panel_deaths},
        update={k: dict(deaths) for k, deaths in sc.update_deaths},
    )
    with traffic.track_traffic() as t:
        res = factorize(
            jnp.asarray(blocks),
            QRConfig(panel_width=sc.panel_width, variant=sc.variant),
            faults=sched,
        )
    in_tol = all(rep.within_tolerance for rep in res.reports)
    valid = np.asarray(res.valid)
    expect = np.ones(sc.p, dtype=bool)
    for rep in res.reports:
        expect &= rep.plan_r.final_valid
        if rep.plan_w is not None:
            expect &= rep.plan_w.final_valid
    from repro.core import ref

    truth = ref.qr_r(blocks.reshape(-1, sc.n).astype(np.float64))
    scale = max(1.0, np.abs(truth).max())
    holders = np.flatnonzero(valid)
    match = bool(holders.size) and all(
        np.abs(np.asarray(res.r)[r] - truth).max() / scale < 5e-4
        for r in holders
    )
    if in_tol and not match:
        raise BenchFailure(
            f"scenario {sc.name}: within-tolerance schedule but survivor R "
            "does not match the dense QR"
        )
    sweeps = t.sweeps_of("panel_cross", "trailing_update")
    if sweeps != res.n_panels:
        raise BenchFailure(
            f"scenario {sc.name}: {sweeps} trailing-block sweeps for "
            f"{res.n_panels} panels — the 1-sweep-per-panel claim failed"
        )
    return {
        "survivors": Metric(int(valid.sum()), gate="hard", direction="exact"),
        "survivors_match_plan": Metric(
            bool((valid == expect).all()), gate="hard", direction="exact"
        ),
        "within_tolerance": Metric(in_tol, gate="hard", direction="exact"),
        "values_match": Metric(match, gate="hard", direction="exact"),
        "recovered": Metric(
            sum(rep.recovered_r + rep.recovered_w for rep in res.reports),
            gate="hard", direction="exact",
        ),
        "n_panels": Metric(res.n_panels, gate="hard", direction="exact"),
        "trailing_sweeps": Metric(sweeps, gate="hard", direction="exact"),
        "sweeps_per_panel": Metric(
            sweeps / res.n_panels, gate="hard", direction="exact"
        ),
    }


def run_trainer_scenario(sc: TrainerScenario, ckpt_dir: str | None = None) -> dict:
    """Drive a tiny Trainer through the event schedule; metric dict.

    Raises :class:`~repro.bench.registry.SkipCase` when a CPU host has too
    few devices (:func:`~repro.bench.registry.require_devices`) — anything
    else (I/O errors, too few chips) propagates and fails the run loudly.
    """
    require_devices(sc.data_width * sc.model_width)
    from repro.compat import make_mesh
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(sc.arch).smoke(n_layers=sc.n_layers)
    mesh = make_mesh((sc.data_width, sc.model_width), ("data", "model"))
    own_dir = ckpt_dir is None
    ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix=f"bench_{sc.name}_")
    tcfg = TrainerConfig(
        steps=sc.steps, log_every=10**9, ckpt_every=sc.ckpt_every,
        ckpt_dir=ckpt_dir, optimizer=sc.optimizer,
        on_failure=sc.on_failure, buddy_levels=sc.buddy_levels, seed=0,
    )
    dc = DataConfig(
        vocab=cfg.vocab, seq_len=32, global_batch=2 * sc.data_width,
        family=cfg.family,
        enc_frames=cfg.enc_frames if cfg.family == "encdec" else 0,
        d_model=cfg.d_model,
    )
    tr = Trainer(cfg, tcfg, mesh, dc)
    p, o = tr.init_state()
    try:
        tr.run(p, o, fault_schedule=tuple(sc.events))
    finally:
        if own_dir:
            import shutil

            shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [m["loss"] for m in tr.metrics_log]
    metrics: dict[str, Metric] = {
        "completed_final_step": Metric(
            int(tr.metrics_log[-1]["step"]), gate="hard", direction="exact"
        ),
        "loss_finite": Metric(
            bool(np.isfinite(losses).all()), gate="hard", direction="exact"
        ),
        "final_replicas": Metric(
            int(tr.n_replicas), gate="hard", direction="exact"
        ),
    }
    for key, want in sc.expect.items():
        got = int(tr.fault_stats[key])
        metrics[f"stat_{key}"] = Metric(got, gate="hard", direction="exact")
        if got != want:
            raise BenchFailure(
                f"scenario {sc.name}: fault_stats[{key!r}] = {got}, "
                f"schedule expects {want} (events: "
                + "; ".join(tr.events_log[-6:]) + ")"
            )
    return metrics


def run_scenario(sc, **kw) -> dict:
    if sc.kind == "collective":
        return run_collective_scenario(sc, **kw)
    if sc.kind == "blocked":
        return run_blocked_qr_scenario(sc, **kw)
    return run_trainer_scenario(sc, **kw)


# ---------------------------------------------------------------------------
# The stock sweep
# ---------------------------------------------------------------------------

def _stock_scenarios() -> tuple:
    from repro.runtime.trainer import FaultEvent

    return (
        # Correlated: one 4-rank failure domain (a host) dies at once.  At
        # entry of exchange 3 there are 2^3 copies of every intermediate, so
        # Replace reroutes around the wiped block within tolerance.
        CollectiveScenario(
            name="correlated_block_wipe", p=16, variant="replace",
            rounds=(ReduceRound(deaths=((8, 3), (9, 3), (10, 3), (11, 3))),),
            description="ranks 8-11 (one failure domain) die at entry of "
                        "exchange 3; replace reroutes, 12 survivors",
        ),
        # Cascading: failures arriving at successive exchanges; Self-Healing
        # respawns between steps so every rank ends holding the result.
        CollectiveScenario(
            name="cascading_failures", p=16, variant="selfhealing",
            rounds=(ReduceRound(deaths=((1, 1), (6, 2), (9, 2), (12, 3))),),
            description="1 death at step 1, two at step 2, one at step 3 — "
                        "within the per-step 2^s−1 budget at every step",
        ),
        # BLANK under repeat: three successive reductions with a growing
        # masked set and mid-reduce deaths of the masked ranks — the
        # collective analogue of the trainer's blank semantics.
        CollectiveScenario(
            name="blank_under_repeat", p=8, variant="redundant",
            rounds=(
                ReduceRound(),
                ReduceRound(masked=(2,), deaths=((2, 2),)),
                ReduceRound(masked=(2, 5), deaths=((5, 1),)),
            ),
            description="repeated reductions; masked replicas contribute "
                        "zero, and also die mid-reduce within tolerance",
        ),
        # Straggler reconstruction: two slow ranks are excluded from the
        # coded gather and their contributions reconstructed from parity —
        # the reduction completes without awaiting them (the butterfly has
        # no choice but to wait).
        CollectiveScenario(
            name="straggler_reconstruction", p=8, variant="redundant",
            scheme="coded", parity=2,
            rounds=(ReduceRound(slow=(2, 5)),),
            description="ranks 2 and 5 straggle; the coded plan excludes "
                        "them from the gather and decodes both from the 2 "
                        "parity lanes — no waiting, values exact",
        ),
        # Silent corruption detected: a rank's observed payload is
        # perturbed (it participates normally, unaware); the coded plan
        # quarantines it, reconstructs the true contribution from parity,
        # and checksum-verifies the raw payload — replication would have
        # propagated the corruption silently.
        CollectiveScenario(
            name="silent_corruption_detected", p=8, variant="redundant",
            scheme="coded", parity=2,
            rounds=(ReduceRound(corrupt=(3,)), ReduceRound(corrupt=(1, 6))),
            description="SDC injected on ranks 3, then 1 and 6; detection "
                        "flags exactly the corrupted ranks and the result "
                        "matches the uncorrupted truth",
        ),
        # Over-parity death: more simultaneous deaths than parity lanes —
        # beyond the erasure budget.  Honest degradation: zero survivors,
        # NaN payloads, no silent garbage (and a recovered follow-up round
        # shows the same world succeeding within budget).
        CollectiveScenario(
            name="over_parity_death", p=8, variant="redundant",
            scheme="coded", parity=2,
            rounds=(
                ReduceRound(deaths=((1, 0), (4, 0), (6, 1))),
                ReduceRound(deaths=((1, 0), (4, 0))),
            ),
            description="3 deaths exceed the c=2 erasure budget (round 0: "
                        "all-invalid, no garbage); 2 deaths decode fine "
                        "(round 1)",
        ),
        # Fail during rebuild: disk-rollback REBUILD (no buddy store), and a
        # second replica fails while the first rollback is still replaying.
        TrainerScenario(
            name="fail_during_rebuild", on_failure="rebuild",
            buddy_levels=0, steps=10, ckpt_every=3,
            events=(
                FaultEvent(step=5, kind="fail", replica=0),
                FaultEvent(step=5, kind="fail", replica=1),
            ),
            expect={"failures": 2, "rollbacks": 2},
            description="replica 0 dies at step 5 → rollback to ckpt 3; "
                        "replica 1 dies when the replay re-reaches step 5",
        ),
        # Buddy-pair wipe: both members of an XOR buddy pair die in the same
        # step — the first recovers diskless from its buddy, the second finds
        # its only replica gone and must fall back to the disk rollback.
        TrainerScenario(
            name="buddy_pair_wipe", on_failure="rebuild",
            buddy_levels=1, steps=8, ckpt_every=3,
            events=(
                FaultEvent(step=5, kind="fail", replica=0),
                FaultEvent(step=5, kind="fail", replica=1),
            ),
            expect={"failures": 2, "buddy_restores": 1, "rollbacks": 1},
            description="replicas 0 and 1 (level-1 buddies) die together; "
                        "first recovers diskless, second needs the disk",
        ),
        # Blocked QR, death during panel k: two ranks die inside panel 1's
        # TSQR butterfly; Replace reroutes to replicas within the cumulative
        # 2^s−1 budget and the panel's R stays exact on every survivor.
        BlockedQRScenario(
            name="panel_death_midsweep", p=8, variant="replace",
            m_local=48, n=20, panel_width=6,
            panel_deaths=((1, ((3, 1), (6, 2))),),
            description="ranks 3 and 6 die at exchanges 1 and 2 of panel 1's "
                        "TSQR; replace reroutes, R exact on all 6 survivors",
        ),
        # Blocked QR, death during the trailing update: the W butterfly of
        # panel 0 loses a rank; the redundant variant's coset goes invalid
        # but survivors hold the exact block row, and the dead rank's W is
        # restored from a replica so later panels stay clean.
        BlockedQRScenario(
            name="death_during_trailing_update", p=8, variant="redundant",
            m_local=48, n=20, panel_width=6,
            update_deaths=((0, ((5, 1),)),),
            description="rank 5 dies during panel 0's trailing-update "
                        "reduction; its step-1 coset invalidates, replica "
                        "fetch re-arms the pipeline",
        ),
        # Blocked QR, cascading panels: a fresh death in each of the first
        # three panels; self-healing respawns within every butterfly so all
        # ranks stay valid through the whole factorization.
        BlockedQRScenario(
            name="cascading_panels", p=8, variant="selfhealing",
            m_local=48, n=20, panel_width=6,
            panel_deaths=((0, ((1, 1),)), (1, ((6, 2),)), (2, ((3, 1),))),
            description="one death per panel across panels 0-2, each within "
                        "the per-step budget; selfhealing keeps all 8 valid",
        ),
        # SHRINK then REBUILD: elastic round trip through the mesh layer.
        TrainerScenario(
            name="shrink_then_rebuild", on_failure="shrink",
            steps=8, ckpt_every=0,
            events=(
                FaultEvent(step=3, kind="fail", replica=1),
                FaultEvent(step=6, kind="rejoin"),
            ),
            expect={"failures": 1, "shrinks": 1, "rejoins": 1},
            description="lose a replica at step 3 (mesh 4→2), replacement "
                        "hardware rejoins at step 6 (mesh 2→4)",
        ),
    )


_CACHE: list = []


def get_scenarios() -> tuple:
    """The stock sweep (built lazily: FaultEvent's module imports jax)."""
    if not _CACHE:
        _CACHE.append(_stock_scenarios())
    return _CACHE[0]


def case(include_trainer: bool = True, seed: int = 0):
    metrics: dict[str, Metric] = {}
    n_run = 0
    for sc in get_scenarios():
        if sc.kind == "trainer" and not include_trainer:
            continue
        try:
            sub = run_scenario(
                sc,
                **({"seed": seed} if sc.kind in ("collective", "blocked")
                   else {}),
            )
        except SkipCase as e:   # too few CPU devices; real errors propagate
            metrics[f"{sc.name}.skipped"] = Metric(
                True, gate="warn", direction="exact"
            )
            print(f"[bench]   scenario {sc.name} skipped: {e}")
            continue
        n_run += 1
        for k, m in sub.items():
            metrics[f"{sc.name}.{k}"] = m
    metrics["n_scenarios_run"] = Metric(n_run, gate="hard", direction="higher")
    return metrics


bench_case(
    "fault_scenarios",
    tags=("robustness", "scenarios"),
    params={
        "smoke": {"include_trainer": True, "seed": 0},
        "full": {"include_trainer": True, "seed": 0},
    },
)(case)
