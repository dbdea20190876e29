"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the root of the checkout: a
configuration (``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<traffic>.json``), on 1 or 4 chips.  Set-up makes the
cell's matrices on the device from ``--seed`` and warms up every program
its calls run; then one caller calls ``repro.qr.factorize`` in the
traffic's loop for ``--seconds`` seconds, each call ending in
``block_until_ready`` on its R and validity (``chipbench/generator.py``).
Once the window has closed and the peak memory is read, a sample of the
calls drawn from the seed is copied to the host and compared with a
float64 R of the same matrix (``chipbench/reference.py``).

With ``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics; with ``--trace 1`` the window is traced by the
JAX profiler, for at most the traffic's ``trace_seconds``, and the result
holds the per-layer metrics read from the trace, and a breakdown.  The
numbers compared, each with its limit, are the result's last key and the
last lines of standard error.

A run on a machine whose JAX finds no TPU, or fewer chips than the cell
asks for, exits with code 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import reference, spec, trace, work  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    """Counts JAX's compiles (``compiles``) and its traces of Python
    functions to programs (``traces``), through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        self.compiles += event == COMPILE_EVENT
        self.traces += event == TRACE_EVENT


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    keeping every program however fast it compiled."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(trace.SPAN_PREFIX + name)


class GcPauses:
    """Python's garbage collections while it is entered: their number by
    generation and the longest and total pause, for the ``info`` line."""

    def __enter__(self):
        self.counts, self.longest_s, self.total_s = [0, 0, 0], 0.0, 0.0
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        pause = time.perf_counter() - self._t
        self.counts[info["generation"]] += 1
        self.longest_s = max(self.longest_s, pause)
        self.total_s += pause

    def notes(self) -> dict:
        return {"gc_collections": self.counts, "gc_longest_s": self.longest_s,
                "gc_total_s": self.total_s}


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def compare(cell, pool, sampled, limit, promise, deaths):
    """Each sampled call's R and validity against the reference and the
    variant's promise (``chipbench/promises/<variant>.py``) under
    ``deaths``, one mapping per reduction a fault strikes.

    Returns (largest R error, calls with the wrong validity, calls that
    fail)."""
    p = cell.config["ranks"]
    per_reduction = [promise.valid(p, d) for d in deaths]
    want_valid = np.logical_and.reduce(per_reduction + [np.ones(p, bool)])
    blocked = cell.config["qr_config"].get("panel_width") is not None
    ranks = promise.ranks_with_r(want_valid, blocked)
    refs = {}
    worst, bad_valid, failed = 0.0, 0, 0
    for i, (r, valid) in sorted(sampled.items()):
        j = i % len(pool)
        if j not in refs:
            refs[j] = reference.reference_r(np.asarray(pool[j]).reshape(cell.shape))
        errs = [reference.rel_err(r[q], refs[j]) for q in ranks]
        err = max(errs) if all(e == e for e in errs) else float("inf")
        worst = max(worst, err)
        wrong_valid = not np.array_equal(np.asarray(valid, bool), want_valid)
        bad_valid += wrong_valid
        failed += wrong_valid or not err <= limit
    return worst, bad_valid, failed


class Context:
    """What a metric reader knows of the run besides the trace."""

    def __init__(self, cell, window, peak_bytes, setup_s, device_kind):
        self.cell = cell
        self.calls = len(window.latencies_s)
        self.latencies_s = window.latencies_s
        self.window_s = window.window_s
        self.peak_bytes = peak_bytes
        self.setup_s = setup_s
        self.device_kind = device_kind
        self.notes = {}

    def least_time(self) -> work.LeastTime:
        m, n = self.cell.shape
        itemsize = np.dtype(self.cell.config["dtype"]).itemsize
        return work.least_time(m, n, itemsize, self.cell.chips, self.device_kind)


def traced_window(drive, seconds):
    """``drive(seconds, traced)`` under the profiler; returns the window
    and the reduction of its trace."""
    import jax

    # The host's Python calls are not traced: that tracer slows the host
    # loop the window times, and no metric reads it.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            with span("window", True):
                win = drive(seconds, True)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        return win, trace.reduce_file(path)


def run_cell(cell, seed: int, seconds: float, traced: bool, *, root: str, devices,
             t0: float, say=print) -> dict:
    """One run of ``cell``; returns the result (the last line's object)."""
    compiles = CompileCounter()
    used = devices[:cell.chips]
    t = cell.traffic
    gen = spec.generator(cell.traffic_name, root)
    promise = spec.promise(cell.variant, root)
    marks = [time.perf_counter()]
    pool, mesh = gen.make_pool(cell, seed, used)
    call = gen.make_call(cell, mesh)
    marks.append(time.perf_counter())
    for i in range(int(t["warmup_calls"])):
        call(pool[i % len(pool)])
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t0
    phases = dict(zip(("start_s", "data_s", "warmup_s"),
                      np.diff([t0] + marks).tolist()))

    def drive(secs, on):
        return gen.drive(cell, call, pool, secs, seed, lambda name: span(name, on))

    n_compiles, n_traces = compiles.compiles, compiles.traces
    with GcPauses() as pauses:
        if traced:
            win, red = traced_window(drive, min(seconds, float(t["trace_seconds"])))
        else:
            win, red = drive(seconds, False), None
    compiles_in_window = compiles.compiles - n_compiles
    traces_in_window = compiles.traces - n_traces
    peak = peak_bytes(used)

    t_check = time.perf_counter()
    sampled = {i: (np.asarray(r), np.asarray(v)) for i, (r, v) in win.kept.items()}
    win.kept = None
    del call
    gc.collect()
    limit = float(cell.config["limits"]["r_err"])
    r_err, bad_valid, failed = compare(cell, pool, sampled, limit, promise,
                                      gen.expected_deaths(cell))
    del pool
    check_s = time.perf_counter() - t_check
    failed += len(win.raised)
    checks = {
        "r_err": {"value": r_err, "limit": limit},
        "valid_mismatches": {"value": bad_valid, "limit": 0},
        "failed_calls": {"value": failed, "limit": 0},
    }
    calls = len(win.latencies_s)
    correct = bool(calls) and bool(sampled) and failed == 0 and r_err <= limit

    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    info = {"workload": cell.name, "seed": seed, "calls": calls,
            "p95_samples": calls, "window_s": win.window_s, "checked_calls": len(sampled),
            "check_s": check_s,
            "compiles_in_window": compiles_in_window, "traces_in_window": traces_in_window,
            "setup_s": setup_s, "setup_phases": phases, **pauses.notes()}
    ctx = Context(cell, win, peak, setup_s, dev.device_kind)
    metrics = {}
    for m_ in (cell.per_layer if traced else cell.end_to_end):
        value = spec.metric_reader(m_["name"], root)(red, ctx)
        if value is not None:
            metrics[m_["name"]] = {"value": value, "unit": m_["unit"]}
    info.update(ctx.notes)
    result = {"correct": correct, "attempted": calls, "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=red.mean_busy_s(), window_s=red.window_ns / 1e9)
        result["breakdown"] = {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
    result["checks"] = checks
    say(json.dumps({"info": info}))
    for r in win.raised[:5]:
        print(r, file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: JAX found no TPU ({devices[0].platform}); "
              "a measurement needs the chip", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), root=ROOT,
                      devices=devices, t0=T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
