"""The control of the benchmark's comparison, and the readings its limits
are set from.

The control is the plain reference put in the program's place and
computed one step below the precision the configuration states.  The
configurations state float32 with every product at ``highest``; the step
below is ``high``: three bfloat16 passes (hi*hi + hi*lo + lo*hi, the low
parts' product dropped).  :func:`dot_high` spells those passes out, so the
control computes the same on any platform.  The reference put in the
program's place is a Householder TSQR in ``jax.numpy``: a Householder R of
each rank's row block, then the R factors combined pairwise, as the
butterfly combines them.  Every product of it goes through ``dot``.

:func:`in_program_place` puts it there: inside the context, each
``repro.qr.factorize`` call of the harness returns the control's R on
every rank in place of the program's, so the harness's own comparison
judges it.

    python chipbench/control.py --workload <cell> --seeds <s> ... --seconds <s>

runs, on the chip and in one process, the cell's own timed path for a
short window on each seed (the lower reading: the program's largest R
error), and on the first three seeds the same window with the control in
the program's place, at ``high`` (the upper reading: its smallest R error)
and at ``highest`` (a witness that the control's code is sound).  It
prints one JSON line per seed and a summary.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def dot_high(x, y):
    """``x @ y`` in three bfloat16 passes with float32 accumulation.

    The parts are rounded with ``reduce_precision``, which the compiler
    keeps: a float32 -> bfloat16 -> float32 round trip may be dropped
    where XLA allows excess precision, and on the TPU it was (measured on
    one v5e chip: the control read the same as ``highest`` to the last
    digit).  The
    products of bfloat16 parts are exact in float32 at ``highest``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def split(z):
        hi = lax.reduce_precision(z, exponent_bits=8, mantissa_bits=7)
        return hi, lax.reduce_precision(z - hi, exponent_bits=8, mantissa_bits=7)

    (xh, xl), (yh, yl) = split(x), split(y)

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    return mm(xh, yh) + mm(xh, yl) + mm(xl, yh)


def dot_highest(x, y):
    """``x @ y`` at the precision the configurations state."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


DOTS = {"high": dot_high, "highest": dot_highest}
CONTROL_SEEDS = 3  # the control runs on the first three seeds


def householder_r(a, dot):
    """R of the (m, n) matrix ``a`` by Householder reflections, m >= n."""
    import jax.numpy as jnp
    from jax import lax

    m, n = a.shape
    rows = jnp.arange(m)[:, None]

    def step(k, a):
        x = jnp.where(rows >= k, lax.dynamic_slice_in_dim(a, k, 1, axis=1), 0.0)
        xk = lax.dynamic_slice_in_dim(x, k, 1, axis=0)[0, 0]
        alpha = -jnp.where(xk >= 0, 1.0, -1.0) * jnp.sqrt(dot(x.T, x)[0, 0])
        v = x - alpha * (rows == k)
        vv = dot(v.T, v)[0, 0]
        v = v * jnp.where(vv > 0, jnp.sqrt(2.0 / jnp.where(vv > 0, vv, 1.0)), 0.0)
        return a - dot(v, dot(v.T, a))

    return jnp.triu(lax.fori_loop(0, n, step, a)[:n])


def tsqr_r(blocks, dot):
    """R of the matrix whose P row blocks are ``blocks`` (P, m/P, n): each
    block's R, then pairs combined, log2 P rounds."""
    import jax.numpy as jnp

    rs = [householder_r(b, dot) for b in blocks]
    while len(rs) > 1:
        rs = [householder_r(jnp.concatenate([rs[i], rs[i + 1]]), dot)
              for i in range(0, len(rs), 2)]
    return rs[0]


@functools.lru_cache(maxsize=None)
def _control(p: int, precision: str):
    import jax

    def fun(a):
        n = a.shape[-1]
        return tsqr_r(a.reshape(p, -1, n), DOTS[precision])

    return jax.jit(fun)


def control_r(a, p: int, precision: str = "high"):
    """The control's R of the matrix ``a`` ((m, n), or its P row blocks)
    on P ranks, on the device."""
    return _control(p, precision)(a)


@contextlib.contextmanager
def in_program_place(precision: str = "high"):
    """Every ``repro.qr.factorize`` call inside returns the control's R,
    broadcast to the ranks, with the program's validity."""
    import jax.numpy as jnp

    import repro.qr

    real = repro.qr.factorize

    def factorize(a, config, **kw):
        res = real(a, config, **kw)
        r = control_r(a, res.r.shape[0], precision)
        return dataclasses.replace(res, r=jnp.broadcast_to(r, res.r.shape))

    repro.qr.factorize = factorize
    try:
        yield
    finally:
        repro.qr.factorize = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import run, spec

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache(ROOT)
    cell = spec.load_cell(args.workload, ROOT)

    def one(seed):
        return run.run_cell(cell, seed, args.seconds, False, root=ROOT, devices=devices,
                            t0=time.perf_counter(), say=lambda s: None)

    program, control = [], []
    for k, seed in enumerate(args.seeds):
        res = one(seed)
        row = {"seed": seed, "program_r_err": res["checks"]["r_err"]["value"],
               "correct": res["correct"], "calls": res["attempted"],
               "checked": res["checks"]["failed_calls"]["value"]}
        program.append(row["program_r_err"])
        if k < CONTROL_SEEDS:
            for precision in ("high", "highest"):
                t = time.perf_counter()
                with in_program_place(precision):
                    ctl = one(seed)
                row[f"control_{precision}_r_err"] = ctl["checks"]["r_err"]["value"]
                row[f"control_{precision}_correct"] = ctl["correct"]
                row[f"control_{precision}_s"] = time.perf_counter() - t
            control.append(row["control_high_r_err"])
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "lower": max(program),
                      "upper": min(control) if control else None,
                      "seeds": len(program), "control_seeds": len(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
