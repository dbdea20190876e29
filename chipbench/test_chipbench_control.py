"""The comparison that decides ``correct`` fails what it has to fail.

  * The control, the plain reference computed one step below the stated
    precision (three bfloat16 passes for float32 at ``highest``), reads
    above each configuration's limit, at a size a test run holds.
  * A run whose timed path is broken underneath comes out not correct,
    once for each fault a QR cell can have: an answer altered where it is
    produced (R, or the validity), and the exchange between ranks left
    out, on simulated ranks and on a mesh.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from chipbench import control, rehearsal, spec

ROOT = rehearsal.ROOT


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return rehearsal.tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def fresh_programs():
    """Programs traced under a patch are dropped before and after."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", ["tsqr_500Mx50.sim", "blocked_150Mx100.clean",
                                      "blocked_150Mx100.panel_death"])
def test_control_reads_above_the_limit(tiny, workload):
    """Put in the program's place, the control fails the harness's own
    comparison at ``high``, and passes it at ``highest``: the limit lies
    between the two, and the control's code is sound."""
    program = rehearsal.run_tiny(tiny, workload)
    with control.in_program_place("high"):
        high = rehearsal.run_tiny(tiny, workload)
    with control.in_program_place("highest"):
        highest = rehearsal.run_tiny(tiny, workload)
    limit = spec.load_cell(workload, ROOT).config["limits"]["r_err"]
    assert program["correct"] and highest["correct"], (program["checks"], highest["checks"])
    assert high["attempted"] > 0 and not high["correct"]
    assert high["checks"]["r_err"]["value"] > limit
    assert high["checks"]["r_err"]["value"] > 3 * program["checks"]["r_err"]["value"]


def test_dot_high_is_three_bfloat16_passes():
    import jax.numpy as jnp

    x = np.random.default_rng(1).standard_normal((64, 64)).astype(np.float32)
    exact = x.astype(np.float64) @ x.astype(np.float64)
    high = np.asarray(control.dot_high(jnp.asarray(x), jnp.asarray(x)))
    highest = np.asarray(control.dot_highest(jnp.asarray(x), jnp.asarray(x)))
    err_high = np.abs(high - exact).max() / np.abs(exact).max()
    err_highest = np.abs(highest - exact).max() / np.abs(exact).max()
    assert 1e-6 < err_high < 1e-3
    assert err_highest < err_high / 10


def altered_r(res):
    r = res.r
    return dataclasses.replace(res, r=r.at[0, 0, 1].add(1e-4 * abs(r[0, 0, 0])))


def altered_valid(res):
    return dataclasses.replace(res, valid=res.valid | True)


@pytest.mark.parametrize("workload,fault", [
    ("tsqr_500Mx50.sim", altered_r),
    ("blocked_150Mx100.clean", altered_r),
    ("blocked_150Mx100.panel_death", altered_r),
    ("blocked_150Mx100.panel_death", altered_valid),
])
def test_an_answer_altered_where_produced_is_not_correct(tiny, monkeypatch, workload, fault):
    import repro.qr

    real = repro.qr.factorize
    monkeypatch.setattr(repro.qr, "factorize", lambda *a, **k: fault(real(*a, **k)))
    res = rehearsal.run_tiny(tiny, workload)
    assert res["attempted"] > 0 and not res["correct"]
    assert res["failed"] > 0


@pytest.mark.parametrize("workload", [
    "tsqr_500Mx50.sim", "blocked_150Mx100.clean", "blocked_150Mx100.panel_death"])
def test_exchange_left_out_is_not_correct(tiny, monkeypatch, fresh_programs, workload):
    from repro.collective.comm import SimComm

    monkeypatch.setattr(SimComm, "exchange", lambda self, x, perm: x)
    res = rehearsal.run_tiny(tiny, workload)
    assert res["attempted"] > 0 and not res["correct"]


MESH_RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from chipbench import rehearsal
    from repro.collective.comm import ShardMapComm
    out = {{"sound": rehearsal.run_tiny({tiny!r}, "tsqr_500Mx50.mesh4")}}
    ShardMapComm.exchange = lambda self, x, perm: x
    import jax
    jax.clear_caches()
    out["broken"] = rehearsal.run_tiny({tiny!r}, "tsqr_500Mx50.mesh4", seed=12345)
    print(json.dumps({{k: [v["correct"], v["attempted"], v["checks"]] for k, v in out.items()}}))
""")


def with_mesh_cell(root):
    """The 4-chip TSQR cell, added to the copy where BENCHMARK.json lacks it."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    if "tsqr_500Mx50.mesh4" not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append({"name": "tsqr_500Mx50.mesh4", "config": "tsqr_500Mx50",
                                   "traffic": "mesh4", "chips": 4, "why": "the mesh path"})
        with open(path, "w") as f:
            json.dump(bench, f)
    return root


def test_mesh_exchange_left_out_is_not_correct(tmp_path):
    tiny = with_mesh_cell(rehearsal.tiny_root(str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_RUN.format(root=ROOT, src=os.path.join(ROOT, "src"), tiny=tiny)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["sound"][0] and got["sound"][1] > 0, got
    assert not got["broken"][0] and got["broken"][1] > 0, got
