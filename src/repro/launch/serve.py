"""Serving launcher — two serving paths behind one entry point.

Model serving (batched prefill + decode loop, smoke scale on CPU):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --batch 4 --prompt-len 32 --gen 16

QR-as-a-service (shape-bucketed continuous batching over the batched
fault-tolerant pipeline — DESIGN.md §11):

  PYTHONPATH=src python -m repro.launch.serve --mode qr \
      --requests 24 --fault-period 3
"""
from __future__ import annotations

import argparse
import time


def _serve_model(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import api

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    key = jax.random.key(0)
    params = api.init(key, cfg)
    s_max = args.prompt_len + args.gen
    batch = api.synth_batch(key, cfg, "prefill", args.batch, args.prompt_len)

    prefill = jax.jit(lambda p, b: api.prefill(p, b, cfg, s_max=s_max))
    decode = jax.jit(lambda p, c, t: api.decode_step(p, c, t, cfg))

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    toks = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for _ in range(args.gen):
        toks.append(tok)
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    tok.block_until_ready()
    t_decode = time.perf_counter() - t0

    out = jnp.concatenate(toks, axis=1)
    print(f"arch={cfg.name} prefill({args.batch}x{args.prompt_len})="
          f"{t_prefill*1e3:.1f}ms decode {args.gen} steps="
          f"{t_decode*1e3:.1f}ms ({t_decode/args.gen*1e3:.2f} ms/tok)")
    print("generated ids[0]:", out[0].tolist())


def _serve_qr(args) -> None:
    import numpy as np

    from repro.serve import (
        BucketSpec,
        CostModel,
        PeriodicFaultInjector,
        QRServer,
    )

    buckets = (BucketSpec(256, 32), BucketSpec(512, 64))
    injector = None
    if args.fault_period:
        injector = PeriodicFaultInjector.sampled(
            args.fault_period, variant="redundant", p=args.p, seed=args.seed
        )
    server = QRServer(
        buckets, p=args.p,
        model=CostModel(max_batch_cap=args.max_batch),
        fault_injector=injector,
    )
    print("planner decisions:")
    for plan in server.planner_decisions():
        print(f"  bucket {plan['bucket']}: panel_width={plan['panel_width']} "
              f"local_r={plan['local_r']} max_batch={plan['max_batch']}")
    t0 = time.perf_counter()
    traces = server.prewarm()
    print(f"prewarm: {sum(traces.values())} trace(s) "
          f"in {time.perf_counter() - t0:.2f}s {traces}")

    rng = np.random.default_rng(args.seed)
    mats = []
    for i in range(args.requests):
        spec = buckets[i % len(buckets)]
        n = int(rng.integers(max(2, spec.n_pad // 2), spec.n_pad + 1))
        m = int(rng.integers(n, spec.m_pad - (spec.n_pad - n) + 1))
        mats.append(rng.standard_normal((m, n)).astype(np.float32))

    t0 = time.perf_counter()
    responses = server.serve(mats)
    wall = time.perf_counter() - t0
    lat_us = np.array([r.latency_s for r in responses]) * 1e6
    s = server.stats
    print(f"served {s.served} requests in {wall:.2f}s "
          f"({s.served / wall:.1f} req/s), {s.drains} drains "
          f"({s.faulted_drains} faulted, {s.reserved} re-served, "
          f"{s.filler_slots} filler slots)")
    print(f"dispatches/drain: {sorted(set(s.dispatches_per_drain))} "
          f"latency p50={np.percentile(lat_us, 50) / 1e3:.1f}ms "
          f"p99={np.percentile(lat_us, 99) / 1e3:.1f}ms")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("model", "qr"), default="model")
    # model serving
    ap.add_argument("--arch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    # QR serving
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--fault-period", type=int, default=3,
                    help="strike every Nth drain (0 disables injection)")
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.env import enable_compile_cache

    enable_compile_cache()
    if args.mode == "qr":
        _serve_qr(args)
    else:
        if not args.arch:
            raise SystemExit("--arch is required for --mode model")
        _serve_model(args)


if __name__ == "__main__":
    main()
