"""Thin shim — logic lives in :mod:`repro.bench.cases.dispatch` and is
registered as the ``dispatch`` bench case (``python -m repro.bench run``),
hard-gating the single-program blocked-QR claims: 1 trace after a repeat
call, 1 device dispatch per factorization independent of the panel count,
1 dispatch for a B-matrix batch, and bit-identity to the eager driver.

Run with ``PYTHONPATH=src`` for the standalone numbers, or with ``--guard``
for the CI tier-1 retrace guard (exits non-zero if any guarded entry point
re-traces on a second call with identical shapes)."""
from repro.launch.env import force_host_devices

force_host_devices(8)                  # on the CPU; precedes the first jax import

from repro.bench.cases.dispatch import case, guard, main, run  # noqa: E402,F401

if __name__ == "__main__":
    raise SystemExit(main())
