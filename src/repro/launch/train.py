"""Training launcher.

Smoke-scale by default (reduced config, 1-device mesh — runs on this CPU
container); ``--mesh single|multi`` selects the production meshes for
dry-run-style launches on a real fleet.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 100
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --on-failure rebuild --fail "10:0" --straggle "20:1:3"

``--faults <name>`` replays a stock trainer scenario from
:mod:`repro.bench.scenarios` (event schedule, mesh width, recovery policy,
and expected fault-stat counts) against any ``--arch`` / ``--optimizer`` —
the CLI twin of the ``fault_scenarios`` bench case, exiting non-zero when
the run's fault stats miss the scenario's expectations:

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
      --optimizer powersgd --faults shrink_then_rebuild
"""
from __future__ import annotations

import argparse

from repro.launch.env import enable_compile_cache, force_host_devices


def parse_events(fail: str, straggle: str, recover: str):
    from repro.runtime.trainer import FaultEvent

    events = []
    for spec, kind in ((fail, "fail"), (recover, "recover")):
        for item in filter(None, spec.split(",")):
            step, rep = item.split(":")
            events.append(FaultEvent(step=int(step), kind=kind, replica=int(rep)))
    for item in filter(None, straggle.split(",")):
        parts = item.split(":")
        step, rep = int(parts[0]), int(parts[1])
        dur = int(parts[2]) if len(parts) > 2 else 1
        events.append(FaultEvent(step=step, kind="straggle", replica=rep, duration=dur))
    return tuple(events)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="full published config (needs a real fleet)")
    ap.add_argument("--mesh", default="auto",
                    help="auto | dxm (e.g. 2x2) | single | multi")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--on-failure", default="blank",
                    choices=["blank", "shrink", "rebuild"])
    ap.add_argument("--optimizer", default=None,
                    choices=["adamw", "powersgd", "orthosgd", "lowrank"],
                    help="default adamw (or the --faults scenario's choice)")
    ap.add_argument("--faults", default="",
                    help="stock trainer scenario name from "
                         "repro.bench.scenarios (overrides the event "
                         "schedule, mesh width, and recovery policy)")
    ap.add_argument("--fail", default="", help="step:replica[,...]")
    ap.add_argument("--recover", default="", help="step:replica[,...]")
    ap.add_argument("--straggle", default="", help="step:replica[:dur][,...]")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    sc = None
    if args.faults:
        # Stock schedules need their full replica width; on the CPU mirror
        # the bench CLI and pin 8 host devices before the first jax import.
        force_host_devices(8)
        from repro.bench.scenarios import get_scenarios

        stock = {s.name: s for s in get_scenarios() if s.kind == "trainer"}
        if args.faults not in stock:
            raise SystemExit(
                f"unknown --faults scenario {args.faults!r}; trainer "
                "scenarios: " + ", ".join(sorted(stock))
            )
        sc = stock[args.faults]

    import jax

    enable_compile_cache()

    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_production_mesh, make_smoke_mesh
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if sc is not None:
        mesh = make_smoke_mesh(data=sc.data_width, model=sc.model_width)
    elif args.mesh == "single":
        mesh = make_production_mesh(multi_pod=False)
    elif args.mesh == "multi":
        mesh = make_production_mesh(multi_pod=True)
    elif args.mesh == "auto":
        n = len(jax.devices())
        mesh = make_smoke_mesh(data=n, model=1)
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_smoke_mesh(data=d, model=m)

    tcfg = TrainerConfig(
        steps=sc.steps if sc is not None else args.steps,
        microbatches=args.microbatches,
        on_failure=sc.on_failure if sc is not None else args.on_failure,
        optimizer=args.optimizer or (sc.optimizer if sc is not None
                                     else "adamw"),
        ckpt_dir=args.ckpt_dir,
        ckpt_every=sc.ckpt_every if sc is not None else args.ckpt_every,
        buddy_levels=sc.buddy_levels if sc is not None else 1,
        lr=args.lr,
    )
    dcfg = DataConfig(
        vocab=cfg.vocab,
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        family=cfg.family,
        enc_frames=cfg.enc_frames if cfg.family == "encdec" else 0,
        d_model=cfg.d_model,
    )
    trainer = Trainer(cfg, tcfg, mesh, dcfg)
    params, opt = trainer.init_state()
    schedule = (tuple(sc.events) if sc is not None
                else parse_events(args.fail, args.straggle, args.recover))
    trainer.run(params, opt, fault_schedule=schedule)
    print("\n".join(trainer.events_log))
    print(f"final loss: {trainer.metrics_log[-1]['loss']:.4f}")
    if sc is not None:
        stats = {k: int(v) for k, v in trainer.fault_stats.items() if v}
        print(f"fault stats: {stats}")
        missed = {k: (int(trainer.fault_stats[k]), want)
                  for k, want in sc.expect.items()
                  if int(trainer.fault_stats[k]) != want}
        if missed:
            raise SystemExit(
                f"scenario {sc.name}: fault stats missed expectations "
                f"(got, want) = {missed}"
            )
        print(f"scenario {sc.name}: fault stats match expectations")


if __name__ == "__main__":
    main()
