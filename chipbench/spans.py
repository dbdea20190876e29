"""The program's own spans in a profiler trace, read beside the harness's
reduction (:mod:`chipbench.trace`).

``repro`` opens a ``jax.profiler.TraceAnnotation`` named ``repro.<layer>``
around each layer of a ``factorize`` call (``repro.kernels.dispatch.span``),
its counts riding on the span as stats; inside a compiled body the same
names enter the device operations' ``op_name`` metadata.  From one traced
window this module reads:

  * the program spans: the ``repro.`` events of the host planes that
    overlap the window, with their stats and thread;
  * per layer, the union of one span name's intervals over the calls, in
    ms a call (:func:`per_call_ms`);
  * where the time goes: each instant of the window is put down to the
    innermost span of either kind open then (a harness span keeps its
    name without ``chipbench.``, a program span keeps ``repro.``), over
    the host's time inside calls and over the busiest device's idle time;
  * the longest idle gaps, labelled so;
  * device time per ``repro`` scope (own time) on the busiest device,
    where the trace carries each operation's ``op_name``.

    python chipbench/spans.py --workload <cell> --seed <n> --seconds <s> [--out <dir>]

runs the cell's set-up and a traced window of ``--seconds``, as
``chipbench/run.py --trace 1`` does, then an untraced window as long, and
prints the reading as one JSON line; ``--out`` keeps the trace there.  It
compares no result.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace  # noqa: E402

PROGRAM_PREFIX = "repro."
CALL_SPAN = trace.SPAN_PREFIX + "call"

# The per-layer readings the program spans give, by the span each reads.
METRICS = {
    "host_factorize_ms": "factorize",
    "host_plan_ms": "plan",
    "exchange_ms": "exchange",
    "recover_ms": "recover",
}

# The stat of an operation's event metadata that holds its op_name.
OP_NAME_STAT = "tf_op"


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span: its name (``repro.<layer>``), interval in ns, the
    counts it carries and the host thread it ran on."""

    name: str
    start: int
    end: int
    stats: dict
    thread: str


def program_spans(profile, window) -> list[Span]:
    """The ``repro.`` spans of the host planes that overlap ``window``,
    in order of start (an outer span before the spans nested in it)."""
    lo, hi = window
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name.startswith(PROGRAM_PREFIX) and e > lo and s < hi:
                    out.append(Span(ev.name, s, e, trace._stats(ev), line.name))
    return sorted(out, key=lambda sp: (sp.start, -sp.end))


def union_ns(spans, name: str, window) -> int:
    """Length of the union of the intervals of the spans ``repro.<name>``
    inside ``window``."""
    iv = [(sp.start, sp.end) for sp in spans if sp.name == PROGRAM_PREFIX + name]
    return sum(e - s for s, e in trace.union(trace.clip(iv, *window)))


def per_call_ms(spans, name: str, window, calls: int):
    """:func:`union_ns` ÷ ``calls``, in ms; None where the window holds no
    such span."""
    ns = union_ns(spans, name, window)
    return ns / 1e6 / calls if ns and calls else None


def _short(name: str) -> str:
    if name.startswith(trace.SPAN_PREFIX):
        return name[len(trace.SPAN_PREFIX):]
    return name


def segments(red, spans):
    """The window cut where any span opens or closes: sorted, disjoint
    ``(start, end, label)``, the label the innermost (shortest) span open
    there, or ``outside``."""
    lo, hi = red.window
    ivs = [(n, s, e) for n, s, e in red.spans] + [(sp.name, sp.start, sp.end) for sp in spans]
    ivs = [(n, max(s, lo), min(e, hi), e - s) for n, s, e in ivs if e > lo and s < hi]
    edges = sorted({lo, hi, *(t for _, s, e, _ in ivs for t in (s, e))})
    opening = sorted(ivs, key=lambda iv: iv[1])
    out, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(opening) and opening[i][1] <= a:
            active.append(opening[i])
            i += 1
        active = [iv for iv in active if iv[2] > a]
        best = min(active, key=lambda iv: iv[3], default=None)
        out.append((a, b, _short(best[0]) if best else "outside"))
    return out


def _label_at(segs, t) -> str:
    i = bisect.bisect_right([s for s, _, _ in segs], t) - 1
    return segs[i][2] if i >= 0 and t < segs[i][1] else "outside"


def time_by_label(segs, intervals) -> dict:
    """Nanoseconds of ``intervals`` (sorted, disjoint) put down to each
    label of ``segs``."""
    out: dict = {}
    j = 0
    for s, e in intervals:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            out[name] = out.get(name, 0) + min(b, e) - max(a, s)
            k += 1
    return out


def idle_gaps(red, segs, k: int = 10):
    """[label, seconds] of the longest idle gaps on the busiest device,
    each labelled by the innermost span open at its middle."""
    gaps = sorted(red.busiest().gaps, key=lambda g: g[0] - g[1])[:k]
    return [[_label_at(segs, (s + e) / 2), (e - s) / 1e9] for s, e in gaps]


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def op_names(path: str, plane_name: str) -> dict:
    """{operation text: op_name} of the plane ``plane_name`` of the
    ``.xplane.pb`` at ``path``.  A TPU trace keeps each operation's
    ``op_name`` in the ``tf_op`` stat of its event metadata, which
    ``ProfileData`` does not expose, so the file is read here (XSpace
    ``planes`` = 1; XPlane ``name`` = 2, ``event_metadata`` = 4,
    ``stat_metadata`` = 5; map entries ``key`` = 1, ``value`` = 2;
    XEventMetadata ``name`` = 2, ``stats`` = 5; XStat ``metadata_id`` = 1,
    ``str_value`` = 5; XStatMetadata ``id`` = 1, ``name`` = 2)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    for field, plane in _fields(data):
        if field != 1:
            continue
        events, stat_ids, name = [], set(), None
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
                if name != plane_name:
                    break
            elif pf == 4:
                events.append(dict(_fields(value)).get(2, b""))
            elif pf == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                if bytes(meta.get(2, b"")).decode() == OP_NAME_STAT:
                    stat_ids.add(meta.get(1, 0))
        if name != plane_name:
            continue
        out = {}
        for meta in events:
            text, op = None, None
            for mf, value in _fields(meta):
                if mf == 2:
                    text = bytes(value).decode()
                elif mf == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in stat_ids and 5 in stat:
                        op = bytes(stat[5]).decode()
            if text and op:
                out[text] = op
        return out
    return {}


def scope_of(op_name: str) -> str:
    """The innermost ``repro.`` component of an ``op_name``, or ``-``."""
    parts = [p for p in op_name.split("/") if p.startswith(PROGRAM_PREFIX)]
    return parts[-1] if parts else "-"


def device_scopes(profile, red, names: dict):
    """[scope, seconds] of own device time per ``repro`` scope on the
    busiest device, longest first (``-`` for operations under no scope),
    from ``names`` (:func:`op_names`); None where no operation is under
    one (no op_name in the trace, or only programs launched eagerly)."""
    lo, hi = red.window
    (plane,) = [p for p in profile.planes if p.name == red.busiest().name]
    ops = [(scope_of(names.get(ev.name, "")), max(ev.start_ns, lo),
            min(ev.start_ns + ev.duration_ns, hi))
           for line in plane.lines if line.name == "XLA Ops" for ev in line.events
           if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
    own: dict = {}
    for scope, t in trace.self_times(ops):
        own[scope] = own.get(scope, 0) + t
    if set(own) <= {"-"}:
        return None
    return [[scope, ns / 1e9] for scope, ns in sorted(own.items(), key=lambda kv: -kv[1])]


def reading(profile, red, calls: int, names: dict | None = None) -> dict:
    """Everything this module reads from one traced window of ``calls``;
    ``names`` are the operations' op_names (:func:`op_names`)."""
    spans = program_spans(profile, red.window)
    segs = segments(red, spans)
    in_calls = trace.union(trace.clip([(s, e) for n, s, e in red.spans if n == CALL_SPAN],
                                      *red.window))
    host = time_by_label(segs, in_calls)
    call_ns = sum(e - s for s, e in in_calls)
    idle = time_by_label(segs, red.busiest().gaps)
    idle_ns = sum(idle.values())
    layers = sorted({sp.name[len(PROGRAM_PREFIX):] for sp in spans})
    counts: dict = {}
    for sp in spans:
        c = counts.setdefault(sp.name, {"spans": 0})
        c["spans"] += 1
        for key, v in sp.stats.items():
            if isinstance(v, int) and key != "call":
                c[key] = c.get(key, 0) + v

    def program_share(ns_by_label, total):
        inside = sum(t for name, t in ns_by_label.items() if name.startswith(PROGRAM_PREFIX))
        return inside / total if total else None

    def ranked(ns_by_label, scale):
        return [[name, t / scale] for name, t in sorted(ns_by_label.items(),
                                                         key=lambda kv: -kv[1])]

    return {
        "metrics": {m: per_call_ms(spans, name, red.window, calls)
                    for m, name in METRICS.items()},
        "span_ms_per_call": {n: per_call_ms(spans, n, red.window, calls) for n in layers},
        "spans_per_call": len(spans) / calls if calls else None,
        "counts": counts,
        "host_in_call_ms_per_call": ranked(host, 1e6 * max(calls, 1)),
        "host_in_program_span": program_share(host, call_ns),
        "idle_s": ranked(idle, 1e9),
        "idle_in_program_span": program_share(idle, idle_ns),
        "idle_share": red.idle_share(),
        "idle_gaps": idle_gaps(red, segs),
        "device_scopes": device_scopes(profile, red, names or {}),
        "device_ops": red.top_ops(),
        "programs_per_call": red.busiest().programs / calls if calls else None,
    }


def span_cost_us(n: int = 20000) -> float:
    """Host time of one two-count span with no profiler session, in µs."""
    from repro.kernels import dispatch

    def one():
        with dispatch.span(dispatch.PANEL, k=1, rounds=2):
            pass

    return timeit.timeit(one, number=n) / n * 1e6


def traced_reading(cell, seed: int, seconds: float, *, root: str, devices,
                   out: str | None = None) -> dict:
    """Set up ``cell``, run a traced and then an untraced window of
    ``seconds`` each, and return the reading of the traced one."""
    import jax
    from jax.profiler import ProfileData

    from chipbench import run, spec

    gen = spec.generator(cell.traffic_name, root)
    pool, mesh = gen.make_pool(cell, seed, devices[:cell.chips])
    call = gen.make_call(cell, mesh)
    for i in range(int(cell.traffic["warmup_calls"])):
        call(pool[i % len(pool)])
    cost = span_cost_us()

    def drive(on):
        return gen.drive(cell, call, pool, seconds, seed, lambda name: run.span(name, on))

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            with run.span("window", True):
                traced = drive(True)
        finally:
            jax.profiler.stop_trace()
        plain = drive(False)
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        if out:
            os.makedirs(out, exist_ok=True)
            shutil.copy(path, os.path.join(out, f"{cell.name}.{seed}.xplane.pb"))
        profile = ProfileData.from_file(path)
        red = trace.reduce_profile(profile)
        names = op_names(path, red.busiest().name)
    calls = len(traced.latencies_s)
    dev = devices[0]
    return {
        "workload": cell.name, "seed": seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
        "calls": calls, "window_s": traced.window_s,
        "untraced_ms_per_call": plain.window_s / len(plain.latencies_s) * 1e3,
        "traced_ms_per_call": traced.window_s / calls * 1e3,
        "span_cost_us": cost,
        "raised": len(plain.raised) + len(traced.raised),
        **reading(profile, red, calls, names),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="directory to keep the trace in")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    from chipbench import run, spec

    cell = spec.load_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    run.enable_compile_cache(ROOT)
    result = traced_reading(cell, args.seed, args.seconds, root=ROOT, devices=devices,
                            out=args.out)
    result["run_s"] = time.perf_counter() - t0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
