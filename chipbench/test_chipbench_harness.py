"""The harness: BENCHMARK.json keeps the benchmark's contract, every piece
is found by name, a cell added as new files and entries runs without an
edit to any file that is there, and a run without a TPU prints no result."""
import hashlib
import json
import math
import os
import re
import subprocess
import sys

import pytest

from chipbench import generator, peaks, rehearsal, spec

ROOT = rehearsal.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_units_and_bounds(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert spec.metric_reader(m["name"], ROOT)
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert spec.metric_reader(m["name"], ROOT)
        # every cell the metric lists reports the end-to-end metric it moves
        for cell in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in spec.load_cell(cell, ROOT).end_to_end}


def test_cells_name_existing_pieces(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.per_layer and any(m["name"] != "setup_s" for m in cell.end_to_end)
        assert spec.promise(cell.variant, ROOT).valid(cell.config["ranks"], {}).all()
        assert cell.traffic["pool"] == 1 or math.gcd(cell.traffic["check_every"],
                                                     cell.traffic["pool"]) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 2)
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)
    for c in configs.values():
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"] and config["reduced"] == c["reduced"]
        assert config["limits"]["r_err"] > 0


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


MIX_MODULE = """
import jax.numpy as jnp
import numpy as np

from chipbench import generator


def make_pool(cell, seed, devices):
    \"\"\"The general pool, its columns scaled from 1 down to 1e-3.\"\"\"
    pool, mesh = generator.make_pool(cell, seed, devices)
    scale = jnp.asarray(np.logspace(0, -3, cell.shape[1]), pool[0].dtype)
    return tuple(a * scale for a in pool), mesh
"""


def test_a_cell_added_as_files_and_entries_runs(tmp_path, monkeypatch):
    """A configuration, three traffic mixes (a closed loop with a death, an
    open loop, and a mix with a generator module of its own), a per-layer
    metric and an end-to-end metric, added as new files and entries."""
    root = rehearsal.tiny_root(str(tmp_path))
    before = digest(root)
    cb = os.path.join(root, "chipbench")
    files = {
        "configs/tsqr_tiny.json": {
            "name": "tsqr_tiny", "source": "a tiny TSQR", "rows": 512, "cols": 8,
            "ranks": 4, "dtype": "float32", "qr_config": {"variant": "redundant"},
            "reduced": [], "limits": {"r_err": 1e-5}},
        "traffic/one_rank_dead.json": {
            "loop": "closed", "pool": 2, "layout": "sim", "faults": {"deaths": {"3": 1}},
            "warmup_calls": 2, "check_every": 1, "trace_seconds": 0.3},
        "traffic/open_arrivals.json": {
            "loop": "open", "rate_per_s": 40, "pool": 2, "layout": "sim", "faults": None,
            "warmup_calls": 2, "check_every": 3, "trace_seconds": 0.3},
        "traffic/scaled_columns.json": {
            "loop": "closed", "pool": 3, "layout": "sim", "faults": None,
            "warmup_calls": 1, "check_every": 1, "trace_seconds": 0.3},
    }
    for name, data in files.items():
        with open(os.path.join(cb, name), "w") as f:
            json.dump(data, f)
    sources = {
        "traffic/scaled_columns.py": MIX_MODULE,
        "metrics/busy_ms_per_call.py":
            "def read(red, ctx):\n    return red.busiest().busy_ns / 1e6 / ctx.calls\n",
        "metrics/factorize_p50_ms.py":
            "import numpy as np\n\n\ndef read(red, ctx):\n"
            "    return float(np.median(ctx.latencies_s)) * 1e3\n",
    }
    for name, text in sources.items():
        with open(os.path.join(cb, name), "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tsqr_tiny", "source": "a tiny TSQR",
                             "file": "chipbench/configs/tsqr_tiny.json",
                             "reduced": [], "why": "a tiny TSQR"})
    cells = [f"tsqr_tiny.{mix}" for mix in ("one_rank_dead", "open_arrivals", "scaled_columns")]
    for cell in cells:
        bench["workloads"].append({"name": cell, "config": "tsqr_tiny",
                                   "traffic": cell.split(".")[1], "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "busy_ms_per_call", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device",
                               "moves": "factorize_p50_ms", "workloads": cells})
    bench["end_to_end"].append({"name": "factorize_p50_ms", "unit": "ms", "better": "lower",
                                "bound": 0.05, "source": "host_clock", "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = digest(root)
    changed = [p for p in before if before[p] != after[p]]
    assert changed == ["BENCHMARK.json"]

    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops_per_s": 1e11, "hbm_bytes_per_s": 1e10,
                                             "source": "a stand-in for the CPU rehearsal"})
    for cell in cells:
        res = rehearsal.run_tiny(root, cell, traced=True)
        assert res["correct"], res["checks"]
        # the cell reads the metrics whose workloads name it: here the new one
        assert set(res["metrics"]) == {"busy_ms_per_call"}
        assert res["metrics"]["busy_ms_per_call"]["value"] > 0
        res = rehearsal.run_tiny(root, cell)
        assert res["correct"], res["checks"]
        assert list(res)[-1] == "checks"
        # the end-to-end metrics without a workloads list, and the one that lists it
        assert set(res["metrics"]) == {"factorize_p50_ms", "peak_hbm_gib", "setup_s"}
        if "open" in cell:  # 40 calls a second: a 0.3 s window is due 12 calls
            assert res["attempted"] == 12


def test_an_open_loop_is_due_the_same_arrivals_in_another_order():
    t = {"rate_per_s": 50}
    a, b = generator.arrivals(t, 2 ** 33 + 1, 4.0), generator.arrivals(t, 7, 4.0)
    assert len(a) == len(b) == 200 and a[-1] == pytest.approx(b[-1])
    assert not (a == b).all()
    assert (generator.arrivals(t, 7, 4.0) == b).all()


def test_a_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
         "tsqr_500Mx50.sim", "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("no_such.cell", ROOT)
