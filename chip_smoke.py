"""Smoke run of the fault-tolerant QR engine on a TPU.

Drives the main path once through the entry points a user calls
(``repro.qr.factorize`` and ``repro.serve.QRServer``) at the sizes of the
paper's own workloads (``repro.configs.tsqr_paper``), with the compiled
Mosaic kernels, and checks every phase against a float64 numpy QR of the
same seeded matrix:

  * TSQR at the paper rows, 2^20 x 32 as four simulated ranks, for each of
    the four variants, fault-free and with one injected death;
  * TSQR with the CholeskyQR2 Pallas kernels at the PowerSGD panel row,
    2^22 x 128;
  * blocked QR at 4096 x 512 / panel 128 with the Pallas kernels: the
    one-dispatch scan pipeline, and the eager driver under a panel death;
  * a ``QRServer`` draining a mixed stream with periodic mid-flight deaths.

    python chip_smoke.py              # one chip: the phases above
    python chip_smoke.py --chips 4    # the row-sharded mesh path on four
                                      # chips, against simulated ranks

Each phase prints one JSON line.  The last line of a passing run is
``{"ok": true, "device": {...}}``.  Where JAX finds no TPU, or any check
fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# The CPU tests' R tolerance (rtol = atol = 5e-4), applied normwise:
# max |R - R_ref| / max(1, max |R_ref|).  At 2^20 rows the diagonal of R
# reaches ~1e3, where one float32 ulp is 1.2e-4, so an elementwise 5e-4
# would demand four ulps of the largest entry; a backward-stable QR
# promises its error relative to the size of A, which is this measure.
TOL = 5e-4
SEED = 0
VARIANTS = ("tree", "redundant", "replace", "selfhealing")
P = 4


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def seeded(shape, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(shape, dtype=np.float32)


def posdiag(r: np.ndarray) -> np.ndarray:
    """Rows scaled so the diagonal is non-negative (R is unique up to this)."""
    s = np.where(np.diagonal(r) < 0, -1.0, 1.0)
    return r * s[:, None]


def reference_r(a: np.ndarray) -> np.ndarray:
    """float64 numpy QR of the (m, n) matrix, R with a non-negative diagonal."""
    return posdiag(np.linalg.qr(a.astype(np.float64), mode="r"))


def rel_err(r, r_ref: np.ndarray) -> float:
    r = posdiag(np.asarray(r, np.float64))
    return float(np.abs(r - r_ref).max() / max(1.0, np.abs(r_ref).max()))


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn):
    """(result, seconds) of ``fn()``, the clock stopped on block_until_ready."""
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def cold_warm(fn):
    """(result, cold_s, warm_s): the first call compiles, the second reuses
    the compiled programs."""
    out, cold = timed(fn)
    _, warm = timed(fn)
    return out, cold, warm


def lowered_has_mosaic(fn, *args) -> bool:
    """Does the program ``fn`` lowers to hold a compiled Mosaic kernel?"""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def preflight(*, compiled: bool = True) -> None:
    """The kernels resolve to compiled Mosaic, and the Pallas programs of the
    CholeskyQR2 TSQR and the blocked pipeline hold Mosaic calls."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.backend import resolve_backend
    from repro.qr import QRConfig, factorize

    kind = resolve_backend(None).kind
    if compiled:
        check(kind == "tpu-mosaic", f"kernels resolve to {kind}, not tpu-mosaic")
    tsqr = QRConfig(local_r="cqr2_pallas")
    blocked = QRConfig(panel_width=128, use_pallas=True)
    a_t = jax.ShapeDtypeStruct((P, 1024, 32), jnp.float32)
    a_b = jax.ShapeDtypeStruct((P, 256, 256), jnp.float32)
    mosaic = {
        "cqr2_pallas_tsqr": lowered_has_mosaic(lambda a: factorize(a, tsqr).r, a_t),
        "blocked_use_pallas": lowered_has_mosaic(lambda a: factorize(a, blocked).r, a_b),
    }
    if compiled:
        check(all(mosaic.values()), f"a Pallas program lowered without Mosaic: {mosaic}")
    emit("preflight", backend=kind, tpu_custom_call=mosaic)


def tsqr_paper_rows(m: int = 1 << 20, n: int = 32) -> None:
    """The four paper variants at 2^20 x 32 with the default config (local
    Householder R), fault-free and with rank 1 dead at the entry of
    exchange 1."""
    import jax.numpy as jnp

    from repro.collective import FaultSpec, make_plan
    from repro.qr import QRConfig, factorize

    a = seeded((m, n), SEED)
    r_ref = reference_r(a)
    blocks = jnp.asarray(a.reshape(P, m // P, n))
    death = FaultSpec.of({1: 1})
    for variant in VARIANTS:
        cfg = QRConfig(variant=variant)
        for faults in (None, death):
            spec = faults or FaultSpec.none()
            res, cold, warm = cold_warm(
                lambda: factorize(blocks, cfg, faults=faults))
            valid = np.asarray(res.valid)
            want = make_plan(variant, P, spec).final_valid
            check(bool((valid == want).all()),
                  f"{variant} {spec}: valid {valid} != plan {want}")
            check(bool(valid.any()), f"{variant} {spec}: no rank holds R")
            r = np.asarray(res.r)
            errs = [rel_err(r[i], r_ref) for i in np.flatnonzero(valid)]
            err = max(errs)
            check(err <= TOL, f"{variant} {spec}: R error {err:.3e} > {TOL}")
            emit("tsqr", variant=variant, deaths=list(spec.deaths),
                 shape=[P, m // P, n], valid=valid.tolist(), max_err=err,
                 cold_s=cold, warm_s=warm, peak_bytes_in_use=peak_bytes())


def tsqr_cqr2(m: int = 1 << 22, n: int = 128) -> None:
    """CholeskyQR2 on the Pallas kernels at the PowerSGD panel row."""
    import jax.numpy as jnp

    from repro.qr import QRConfig, factorize

    emit("note", text=(
        "the PowerSGD panel row runs with local_r='cqr2_pallas', not the "
        "default local_r='jnp': that vmaps a Householder QR over the "
        "simulated ranks, whose axis the chip's layout pads from 4 to 128 "
        "lanes, asking for a 64 GiB buffer on a 16 GiB chip at 2^22 x 128"))
    a = seeded((m, n), SEED + 1)
    r_ref = reference_r(a)
    blocks = jnp.asarray(a.reshape(P, m // P, n))
    del a
    cfg = QRConfig(local_r="cqr2_pallas")
    res, cold, warm = cold_warm(lambda: factorize(blocks, cfg))
    valid = np.asarray(res.valid)
    check(bool(valid.all()), f"cqr2 fault-free: valid {valid}")
    r = np.asarray(res.r)
    err = max(rel_err(r[i], r_ref) for i in range(P))
    check(err <= TOL, f"cqr2: R error {err:.3e} > {TOL}")
    emit("tsqr_cqr2", shape=[P, m // P, n], valid=valid.tolist(), max_err=err,
         cold_s=cold, warm_s=warm, peak_bytes_in_use=peak_bytes())


def blocked_qr(m: int = 4096, n: int = 512, panel: int = 128) -> None:
    """Blocked QR with the Pallas kernels: the fault-free scan pipeline, then
    a death in panel 1's reduction through the eager driver with replica
    recovery."""
    import jax.numpy as jnp

    from repro.qr import PanelFaultSchedule, QRConfig, factorize

    a = seeded((m, n), SEED + 2)
    r_ref = reference_r(a)
    blocks = jnp.asarray(a.reshape(P, m // P, n))
    cfg = QRConfig(panel_width=panel, use_pallas=True)
    death = PanelFaultSchedule.of(panel={1: {2: 1}})
    for faults in (None, death):
        res, cold, warm = cold_warm(lambda: factorize(blocks, cfg, faults=faults))
        check(res.recoverable, f"blocked {faults}: not recoverable")
        r = np.asarray(res.r)
        err = max(rel_err(r[i], r_ref) for i in range(P))
        check(err <= TOL, f"blocked {faults}: R error {err:.3e} > {TOL}")
        emit("blocked", path="eager" if faults else "scan_pipeline",
             shape=[P, m // P, n], panel_width=panel,
             valid=np.asarray(res.valid).tolist(),
             recovered=[rep.recovered_r for rep in res.reports],
             max_err=err, cold_s=cold, warm_s=warm, peak_bytes_in_use=peak_bytes())


def serving(n_requests: int = 24, fault_period: int = 3) -> None:
    """A QRServer with the launcher's two buckets under periodic mid-flight
    deaths: every request is answered, every R matches numpy, and each
    re-served R equals a fault-free eager re-run bit for bit."""
    import jax.numpy as jnp

    from repro.qr import Pipeline, factorize
    from repro.serve import BucketSpec, CostModel, PeriodicFaultInjector, QRServer
    from repro.serve.buckets import block_rows, extract_r, pad_request

    buckets = (BucketSpec(256, 32), BucketSpec(512, 64))
    server = QRServer(
        buckets, p=P, model=CostModel(max_batch_cap=6),
        fault_injector=PeriodicFaultInjector.sampled(
            fault_period, variant="redundant", p=P, seed=SEED),
    )
    traces, prewarm_s = timed(server.prewarm)
    rng = np.random.default_rng(SEED + 3)
    mats = []
    for i in range(n_requests):
        spec = buckets[i % len(buckets)]
        k = int(rng.integers(spec.n_pad // 2, spec.n_pad + 1))
        rows = int(rng.integers(k, spec.m_pad - (spec.n_pad - k) + 1))
        mats.append(rng.standard_normal((rows, k)).astype(np.float32))
    t0 = time.perf_counter()
    responses = server.serve(mats)
    wall = time.perf_counter() - t0
    check([r.rid for r in responses] == list(range(n_requests)),
          "not every request was answered")
    err = max(rel_err(r.r, reference_r(mats[r.rid])) for r in responses)
    check(err <= TOL, f"serving: R error {err:.3e} > {TOL}")
    reserved = [r for r in responses if r.served_via == "reserved"]
    check(server.stats.faulted_drains >= 1 and reserved, "no drain was struck")
    for resp in reserved:
        cfg = dataclasses.replace(server.configs[resp.bucket], pipeline=Pipeline.OFF)
        a = mats[resp.rid]
        ref = factorize(jnp.asarray(block_rows(pad_request(a, resp.bucket), P)), cfg)
        r_ref = extract_r(np.asarray(ref.r[0]), a.shape[1])
        check(np.array_equal(resp.r, r_ref),
              f"re-served request {resp.rid} differs from a fault-free re-run")
    s = server.stats
    emit("serving", requests=n_requests, drains=s.drains,
         faulted_drains=s.faulted_drains, reserved=s.reserved,
         reserve_bitwise=True, max_err=err, prewarm_traces=traces,
         prewarm_s=prewarm_s, wall_s=wall, peak_bytes_in_use=peak_bytes())


def mesh_path(devices, *, tsqr_m: int = 1 << 20, tsqr_n: int = 32,
              blocked_m: int = 4096, blocked_n: int = 512, panel: int = 128) -> None:
    """The row-sharded path over a 4-device ("rows",) mesh: redundant TSQR
    fault-free and with one death, and blocked QR with the Pallas kernels.
    Each is compared with the same factorization on four simulated ranks
    on the first device and with numpy, and every output must span all
    four devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.collective import FaultSpec, make_plan
    from repro.compat import mesh_from_devices
    from repro.qr import QRConfig, factorize

    check(len(devices) == P, f"the mesh path needs {P} devices, got {len(devices)}")
    mesh = mesh_from_devices(np.asarray(devices), ("rows",))
    rows = NamedSharding(mesh, PartitionSpec("rows"))
    first = devices[0]

    def run(a, cfg, name, faults=None, plan_valid=None):
        r_ref = reference_r(a)
        sharded = jax.device_put(a, rows)
        res, cold, warm = cold_warm(lambda: factorize(
            sharded, cfg, mesh=mesh, axis="rows", faults=faults))
        sim_blocks = jax.device_put(a.reshape(P, -1, a.shape[1]), first)
        sim = factorize(sim_blocks, cfg, faults=faults)
        for out in (res.r, res.valid):
            n_dev = len(out.sharding.device_set)
            check(n_dev == P, f"{name}: an output lies on {n_dev} device(s), not {P}")
        valid = np.asarray(res.valid)
        check(bool((valid == np.asarray(sim.valid)).all()),
              f"{name}: sharded valid {valid} != simulated {np.asarray(sim.valid)}")
        if plan_valid is not None:
            check(bool((valid == plan_valid).all()),
                  f"{name}: valid {valid} != plan {plan_valid}")
        r, r_sim = np.asarray(res.r), np.asarray(sim.r)
        live = np.flatnonzero(valid) if plan_valid is not None else range(P)
        err = max(rel_err(r[i], r_ref) for i in live)
        vs_sim = max(rel_err(r[i], posdiag(r_sim[i].astype(np.float64))) for i in live)
        check(err <= TOL, f"{name}: R error {err:.3e} > {TOL}")
        check(vs_sim <= TOL, f"{name}: sharded vs simulated {vs_sim:.3e} > {TOL}")
        emit("mesh", case=name, shape=list(a.shape), devices=P,
             valid=valid.tolist(), max_err=err, max_diff_vs_sim=vs_sim,
             cold_s=cold, warm_s=warm, peak_bytes_in_use=peak_bytes())

    a = seeded((tsqr_m, tsqr_n), SEED)
    tsqr = QRConfig(variant="redundant")
    for faults in (None, FaultSpec.of({1: 1})):
        spec = faults or FaultSpec.none()
        run(a, tsqr, f"tsqr_redundant deaths={list(spec.deaths)}", faults=faults,
            plan_valid=make_plan("redundant", P, spec).final_valid)
    run(seeded((blocked_m, blocked_n), SEED + 2),
        QRConfig(panel_width=panel, use_pallas=True), "blocked_use_pallas")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the row-sharded mesh path (and only it)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this run needs the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.env import enable_compile_cache

    emit("setup", compile_cache=enable_compile_cache(),
         device_kind=devices[0].device_kind, devices=len(devices))
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_path(devices[:4])
        else:
            preflight()
            tsqr_paper_rows()
            tsqr_cqr2()
            blocked_qr()
            serving()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
