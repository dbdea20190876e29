"""Thin shim — logic lives in :mod:`repro.bench.cases.training` and is
registered as the ``training`` bench case (``python -m repro.bench run``),
hard-gating the closed training loop: one dispatch per warm train step
(PowerSGD + OrthoSGD with their orthogonalization collectives traced
inline), zero retraces across an elastic shrink→rebuild cycle, loss parity
with the dense non-FT baseline, and survivor/recovery counts for the model
zoo under the cascading and BLANK-under-repeat schedules.

Run with ``PYTHONPATH=src`` (needs ≥ 4 devices; the bench CLI forces 8)."""
from repro.launch.env import force_host_devices

force_host_devices(8)                  # on the CPU; precedes the first jax import

from repro.bench.cases.training import PARITY_TOL, case  # noqa: E402,F401

if __name__ == "__main__":
    for name, metric in case().items():
        print(f"{name}: {metric.value}{metric.unit or ''}")
