"""Tiny copies of the benchmark that run on the CPU.

They serve the benchmark's tests, and a rehearsal of a run before it goes
to the chip: the same harness, configurations, traffic and metric readers,
with every configuration cut to a shape the CPU runs in a second.  No
number from such a run is a device number.

    tiny_root(dst, root)      # copy BENCHMARK.json and chipbench/ to dst, cut
    run_tiny(dst, "tsqr_500Mx50.sim", traced=True)
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def shrink(config: dict) -> dict:
    """The configuration at a tiny shape: at most 64 columns, 16 rows per
    column on each rank, and panels cut in proportion."""
    out = dict(config)
    cols = min(config["cols"], 64)
    out["cols"] = cols
    out["rows"] = min(config["rows"], 16 * cols * config["ranks"])
    qc = dict(config["qr_config"])
    if qc.get("panel_width"):
        qc["panel_width"] = max(8, qc["panel_width"] * cols // config["cols"])
    out["qr_config"] = qc
    return out


def tiny_root(dst: str, root: str = ROOT) -> str:
    """Copy the benchmark under ``root`` to ``dst``, every configuration
    cut by :func:`shrink` and every call compared; returns ``dst``."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(root, "chipbench"), os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"),
                    dirs_exist_ok=True)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        _rewrite(os.path.join(dst, c["file"]), shrink)
    for path in glob.glob(os.path.join(dst, "chipbench", "traffic", "*.json")):
        # a short window at a tiny size: every call is compared
        _rewrite(path, lambda t: {**t, "check_every": 1})
    return dst


def _rewrite(path: str, change) -> None:
    with open(path) as f:
        data = json.load(f)
    with open(path, "w") as f:
        json.dump(change(data), f, indent=1)


def run_tiny(root: str, workload: str, *, seed: int = 2 ** 31 + 7, seconds: float = 0.3,
             traced: bool = False) -> dict:
    """One run of ``workload`` from the benchmark under ``root`` on the
    devices JAX has here; returns the result."""
    import jax

    from chipbench import run, spec

    cell = spec.load_cell(workload, root)
    return run.run_cell(cell, seed, seconds, traced, root=root, devices=jax.devices(),
                        t0=time.perf_counter(), say=lambda s: None)
