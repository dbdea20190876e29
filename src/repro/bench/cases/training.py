"""The training loop as ONE fault-tolerant compiled program — hard-gated.

The closing claim of ROADMAP item 3 (DESIGN.md §14), measured four ways:

  * **one dispatch per warm train step** — PowerSGD's butterfly reductions
    + FT-TSQR and OrthoSGD's FT-CQR2 Gram butterflies are traced *inline*
    into the jitted step, so a warm step launches exactly one XLA program
    (``train_step``) and adds zero traces;
  * **zero retraces across elastic recovery** — a shrink→rebuild round
    trip compiles one program per mesh *equivalence class* (two total),
    and a post-rebuild step — plus an explicit ``rebuild_mesh`` of the
    template — adds **zero** new traces: the rebuilt mesh hits the same
    jit cache entry as the original (``compat.mesh_fingerprint``);
  * **loss parity with the non-FT baseline** — the same optimizer with
    every in-step collective replaced by its dense equivalent
    (``ft_grad_allreduce=False, ft_in_step=False``) must land within
    ``PARITY_TOL`` relative on the final loss: the butterfly changes fp
    association order, never the mathematics;
  * **the model zoo survives the stock fault scenarios** — MoE / SSM
    (smoke; + hybrid / multimodal at full tier) through elastic
    shrink→rebuild, cascading failures, and BLANK-under-repeat, with
    survivor/recovery counters hard-gated via ``Trainer.fault_stats``.

Needs ≥ 4 simulated devices (the bench CLI forces 8); skips otherwise.
"""
from __future__ import annotations

import shutil
import tempfile

import numpy as np

from repro.bench.registry import BenchFailure, bench_case, require_devices
from repro.bench.schema import Metric

__all__ = ["case", "PARITY_TOL"]

# FT vs dense-baseline final-loss tolerance.  Both runs do the same
# mathematics; the butterfly only reassociates fp sums (per-replica
# value_and_grad + tree combine vs one fused reduction), which over a
# handful of optimizer steps stays well inside 1e-3 relative.
PARITY_TOL = 5e-3

_DATA_WIDTH = 4


def _mk(arch="olmo-1b", optimizer="adamw", *, n_layers=1, steps=6,
        on_failure="blank", ft=True, seed=0, ckpt_dir=None):
    from repro.compat import make_mesh
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(arch).smoke(n_layers=n_layers)
    mesh = make_mesh((_DATA_WIDTH, 1), ("data", "model"))
    tcfg = TrainerConfig(
        steps=steps, log_every=10**9, ckpt_every=0, optimizer=optimizer,
        on_failure=on_failure, ckpt_dir=ckpt_dir or tempfile.mkdtemp(
            prefix="bench_training_"),
        ft_grad_allreduce=ft, ft_in_step=ft, seed=seed,
    )
    dc = DataConfig(
        vocab=cfg.vocab, seq_len=32, global_batch=2 * _DATA_WIDTH,
        family=cfg.family,
        enc_frames=cfg.enc_frames if cfg.family == "encdec" else 0,
        d_model=cfg.d_model,
    )
    return Trainer(cfg, tcfg, mesh, dc), dc


def _one_dispatch_warm(optimizer: str) -> dict:
    """Train 2 steps, then measure a warm third step."""
    from repro.data.pipeline import SyntheticCorpus
    from repro.kernels import dispatch as disp

    tr, dc = _mk(optimizer=optimizer, steps=2)
    try:
        p, o = tr.init_state()
        p, o = tr.run(p, o)
        batch = tr._device_batch(SyntheticCorpus(dc).batch(7))
        before = disp.trace_count("train_step")
        with disp.track_dispatch() as d:
            p, o, metrics = tr.step_fn(p, o, batch)
        return {
            "trace_delta": disp.trace_count("train_step") - before,
            "dispatches": d.dispatches.get("train_step", 0),
            "total_dispatches": d.n_dispatches,
            "loss": float(metrics["loss"]),
        }
    finally:
        shutil.rmtree(tr.tcfg.ckpt_dir, ignore_errors=True)


def _loss_parity(optimizer: str, steps: int) -> dict:
    losses = {}
    for ft in (True, False):
        tr, _ = _mk(optimizer=optimizer, steps=steps, ft=ft)
        try:
            p, o = tr.init_state()
            tr.run(p, o)
            series = [m["loss"] for m in tr.metrics_log]
            if not np.isfinite(series).all():
                raise BenchFailure(
                    f"{optimizer} ({'FT' if ft else 'baseline'}) produced "
                    f"non-finite losses: {series}"
                )
            losses[ft] = series
        finally:
            shutil.rmtree(tr.tcfg.ckpt_dir, ignore_errors=True)
    final_ft, final_base = losses[True][-1], losses[False][-1]
    rel = abs(final_ft - final_base) / max(abs(final_base), 1e-9)
    return {"final_ft": final_ft, "final_base": final_base, "rel": rel}


def _elastic_zero_retrace(optimizer: str) -> dict:
    """Shrink→rebuild under real events: one trace per mesh class, and a
    rebuilt mesh (plus an extra explicit rebuild) re-uses the warm cache."""
    import time

    from repro.data.pipeline import SyntheticCorpus
    from repro.kernels import dispatch as disp
    from repro.runtime.elastic import rebuild_mesh
    from repro.runtime.trainer import FaultEvent

    tr, dc = _mk(optimizer=optimizer, steps=8, on_failure="shrink")
    try:
        p, o = tr.init_state()
        before = disp.trace_count("train_step")
        t0 = time.perf_counter()
        p, o = tr.run(p, o, fault_schedule=(
            FaultEvent(step=3, kind="fail", replica=1),
            FaultEvent(step=6, kind="rejoin"),
        ))
        wall = time.perf_counter() - t0
        traces_run = disp.trace_count("train_step") - before
        # the template mesh rebuilt once more, plus a warm step on it,
        # must not compile anything
        before = disp.trace_count("train_step")
        p, o = tr._remesh(p, o, rebuild_mesh(tr._template_mesh))
        batch = tr._device_batch(SyntheticCorpus(dc).batch(11))
        with disp.track_dispatch() as d:
            p, o, _ = tr.step_fn(p, o, batch)
        losses = [m["loss"] for m in tr.metrics_log]
        return {
            "traces_across_elastic": traces_run,
            "post_rebuild_trace_delta": disp.trace_count("train_step") - before,
            "post_rebuild_dispatches": d.n_dispatches,
            "step_cache_entries": len(tr._step_cache),
            "fault_stats": dict(tr.fault_stats),
            "loss_finite": bool(np.isfinite(losses).all()),
            "steps_per_sec": tr.tcfg.steps / wall,
        }
    finally:
        shutil.rmtree(tr.tcfg.ckpt_dir, ignore_errors=True)


def _zoo_scenarios(archs: tuple) -> dict:
    """The stock elastic / cascading / BLANK-under-repeat schedules, per
    model-zoo architecture, via the declarative scenario engine."""
    from repro.bench.scenarios import TrainerScenario, run_trainer_scenario
    from repro.runtime.trainer import FaultEvent

    out = {}
    for arch in archs:
        slug = arch.split("-")[0]
        schedules = (
            TrainerScenario(
                name=f"{slug}_elastic", on_failure="shrink",
                arch=arch, n_layers=1, steps=8, ckpt_every=0,
                events=(FaultEvent(step=3, kind="fail", replica=1),
                        FaultEvent(step=6, kind="rejoin")),
                expect={"failures": 1, "shrinks": 1, "rejoins": 1},
            ),
            TrainerScenario(
                name=f"{slug}_cascading", on_failure="blank",
                arch=arch, n_layers=1, steps=8, ckpt_every=0,
                events=(FaultEvent(step=2, kind="fail", replica=1),
                        FaultEvent(step=4, kind="fail", replica=2),
                        FaultEvent(step=6, kind="recover", replica=1),
                        FaultEvent(step=6, kind="recover", replica=2)),
                expect={"failures": 2, "recoveries": 2, "masked_steps": 4},
            ),
            TrainerScenario(
                name=f"{slug}_blank_repeat", on_failure="blank",
                arch=arch, n_layers=1, steps=8, ckpt_every=0,
                events=(FaultEvent(step=2, kind="fail", replica=1),
                        FaultEvent(step=4, kind="recover", replica=1),
                        FaultEvent(step=5, kind="fail", replica=2),
                        FaultEvent(step=7, kind="recover", replica=2)),
                expect={"failures": 2, "recoveries": 2, "masked_steps": 4},
            ),
        )
        for sc in schedules:
            for k, m in run_trainer_scenario(sc).items():
                out[f"{sc.name}.{k}"] = m
    return out


def case(archs: tuple = ("qwen2-moe-a2.7b", "mamba2-2.7b"),
         parity_steps: int = 6) -> dict:
    require_devices(_DATA_WIDTH)
    hard = dict(gate="hard", direction="exact")
    metrics: dict[str, Metric] = {}

    # -- one dispatch per warm train step, both FT optimizers ---------------
    for opt in ("powersgd", "orthosgd"):
        w = _one_dispatch_warm(opt)
        if w["trace_delta"] != 0 or w["total_dispatches"] != 1:
            raise BenchFailure(
                f"{opt}: warm train step traced {w['trace_delta']}x and "
                f"launched {w['total_dispatches']} program(s) — must be "
                "0 traces / 1 dispatch"
            )
        metrics[f"{opt}.warm_trace_delta"] = Metric(w["trace_delta"], **hard)
        metrics[f"{opt}.warm_dispatches"] = Metric(
            w["total_dispatches"], **hard
        )

    # -- loss parity: FT collectives vs dense baseline ----------------------
    for opt in ("powersgd", "orthosgd"):
        pr = _loss_parity(opt, parity_steps)
        if pr["rel"] > PARITY_TOL:
            raise BenchFailure(
                f"{opt}: FT final loss {pr['final_ft']:.6f} deviates from "
                f"dense baseline {pr['final_base']:.6f} by {pr['rel']:.2e} "
                f"rel (tolerance {PARITY_TOL:.0e})"
            )
        metrics[f"{opt}.loss_parity_ok"] = Metric(True, **hard)
        metrics[f"{opt}.loss_parity_rel"] = Metric(
            pr["rel"], gate="warn", direction="lower"
        )

    # -- elastic shrink→rebuild: zero warm retraces -------------------------
    el = _elastic_zero_retrace("powersgd")
    if el["traces_across_elastic"] != 2:
        raise BenchFailure(
            f"elastic run compiled {el['traces_across_elastic']} train-step "
            "programs — must be exactly 2 (one per mesh equivalence class)"
        )
    if el["post_rebuild_trace_delta"] != 0 or el["post_rebuild_dispatches"] != 1:
        raise BenchFailure(
            "a rebuilt template mesh did not hit the warm jit cache "
            f"(traces {el['post_rebuild_trace_delta']}, dispatches "
            f"{el['post_rebuild_dispatches']})"
        )
    for k, want in (("failures", 1), ("shrinks", 1), ("rejoins", 1)):
        if el["fault_stats"][k] != want:
            raise BenchFailure(
                f"elastic run fault_stats[{k!r}] = {el['fault_stats'][k]}, "
                f"expected {want}"
            )
    metrics["elastic.traces_across_elastic"] = Metric(
        el["traces_across_elastic"], **hard
    )
    metrics["elastic.post_rebuild_trace_delta"] = Metric(
        el["post_rebuild_trace_delta"], **hard
    )
    metrics["elastic.mesh_classes_compiled"] = Metric(
        el["step_cache_entries"], **hard
    )
    metrics["elastic.loss_finite"] = Metric(el["loss_finite"], **hard)
    metrics["elastic.steps_per_sec"] = Metric(
        el["steps_per_sec"], gate="warn", direction="higher", unit="steps/s"
    )

    # -- model zoo under the stock fault schedules --------------------------
    metrics.update(_zoo_scenarios(tuple(archs)))
    return metrics


bench_case(
    "training",
    tags=("robustness", "training", "compile"),
    params={
        "smoke": {"archs": ("qwen2-moe-a2.7b", "mamba2-2.7b"),
                  "parity_steps": 6},
        "full": {"archs": ("qwen2-moe-a2.7b", "mamba2-2.7b",
                           "zamba2-7b", "qwen2-vl-72b"),
                 "parity_steps": 8},
    },
)(case)
