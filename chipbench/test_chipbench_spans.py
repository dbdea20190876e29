"""The program's spans in a profiler trace: recorded on the CPU around a
small faulted blocked factorize and a small TSQR, and hand-built as a
TPU's trace is laid out."""
import glob
import os
import tempfile
import types

import numpy as np
import pytest

from chipbench import spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
CPU_TRACE = os.path.join(HERE, "testdata", "cpu_tsqr_3calls.xplane.pb")

P, M, N, PANEL = 4, 64, 20, 8
CALLS = 2

# The span each program span may sit in directly (PERF.md §3).
PARENTS = {
    "repro.factorize": {"chipbench.call"},
    "repro.plan": {"repro.factorize"},
    "repro.launch": {"repro.factorize"},
    "repro.panel": {"repro.factorize"},
    "repro.trailing_update": {"repro.factorize", "repro.panel"},
    "repro.local_r": {"repro.factorize", "repro.panel"},
    "repro.reduce": {"repro.factorize", "repro.panel", "repro.form_q", "repro.block_row"},
    "repro.exchange": {"repro.reduce", "repro.recover"},
    "repro.recover": {"repro.panel"},
    "repro.form_q": {"repro.panel"},
    "repro.block_row": {"repro.panel"},
}


def profile_calls(calls):
    """Each function of ``calls`` called in a ``chipbench.call`` span, all
    in one ``chipbench.window`` span, under the profiler."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            with TraceAnnotation("chipbench.window"):
                for f in calls:
                    with TraceAnnotation("chipbench.call"):
                        f()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        return ProfileData.from_file(path)


@pytest.fixture(scope="module")
def recorded():
    import jax
    import jax.numpy as jnp

    from repro.qr import PanelFaultSchedule, QRConfig, factorize

    rng = np.random.default_rng(7)
    blocked = jnp.asarray(rng.standard_normal((P, M, N)).astype(np.float32))
    tall = jnp.asarray(rng.standard_normal((P, M, PANEL)).astype(np.float32))
    cfg = QRConfig(panel_width=PANEL, use_pallas=True)
    faults = PanelFaultSchedule.of(panel={1: {2: 1}})   # rank 2 dies in panel 1
    results = []

    def faulted():
        results.append(factorize(blocked, cfg, faults=faults))
        jax.block_until_ready(results[-1].r)

    def tsqr():
        results.append(factorize(tall, QRConfig()))
        jax.block_until_ready(results[-1].r)

    faulted(), tsqr()                                      # compile first
    results.clear()
    prof = profile_calls([faulted] * CALLS + [tsqr])
    red = trace.reduce_profile(prof)
    return prof, red, spans.program_spans(prof, red.window), results


def parents(all_spans):
    """{span: its innermost enclosing span} over (name, start, end) that
    nest on one thread."""
    out, stack = {}, []
    for sp in sorted(all_spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= sp[1]:
            stack.pop()
        out[sp] = stack[-1][0] if stack else None
        stack.append(sp)
    return out


def by_call(recorded):
    """The program spans of each factorize call, in order."""
    _, _, prog, _ = recorded
    calls = sorted({sp.stats["call"] for sp in prog if sp.name == "repro.factorize"})
    out = []
    for c in calls:
        (top,) = [sp for sp in prog if sp.name == "repro.factorize" and sp.stats["call"] == c]
        out.append([sp for sp in prog if top.start <= sp.start and sp.end <= top.end])
    return out


def test_spans_nest_as_specified(recorded):
    _, red, prog, _ = recorded
    everything = [(n, s, e) for n, s, e in red.spans] + [(sp.name, sp.start, sp.end)
                                                         for sp in prog]
    parent = parents(everything)
    assert {sp.name for sp in prog} == set(PARENTS) - {"repro.launch"}
    for sp in prog:
        assert parent[(sp.name, sp.start, sp.end)] in PARENTS[sp.name], sp
    assert len({sp.thread for sp in prog}) == 1


def test_factorize_span_counts(recorded):
    _, _, prog, _ = recorded
    top = [sp.stats for sp in prog if sp.name == "repro.factorize"]
    assert [s["panels"] for s in top] == [3] * CALLS + [0]
    assert [s["deaths"] for s in top] == [1] * CALLS + [0]
    calls = [s["call"] for s in top]
    assert calls == list(range(calls[0], calls[0] + CALLS + 1))


def test_blocked_counts_agree_with_the_host_plan(recorded):
    *_, results = recorded
    from repro.collective.plan import make_plan

    reports = results[0].reports
    polish = make_plan("redundant", P)          # each panel's one polish pass
    want = sorted([(rep.plan_r.round_count(), rep.plan_r.message_count()) for rep in reports]
                  + [(polish.round_count(), polish.message_count())] * len(reports))
    invalid = sum(int((~rep.plan_r.final_valid).sum()) for rep in reports)
    assert invalid == 2
    for call in by_call(recorded)[:CALLS]:
        got = sorted((sp.stats["rounds"], sp.stats["messages"]) for sp in call
                     if sp.name == "repro.reduce")
        assert got == want
        recover = [sp.stats for sp in call if sp.name == "repro.recover"]
        assert sum(s["restored"] for s in recover) == invalid
        assert [s["rounds"] for s in recover] == [1]
        assert sorted(sp.stats["k"] for sp in call if sp.name == "repro.panel") == [0, 1, 2]
        local = [sp.stats for sp in call if sp.name == "repro.local_r"]
        assert local == [{"ranks": P, "rows": M, "cols": c} for c in (8, 8, 4)]


def test_tsqr_counts_agree_with_the_host_plan(recorded):
    *_, results = recorded
    plan = results[-1].plan
    call = by_call(recorded)[-1]
    (reduce_,) = [sp.stats for sp in call if sp.name == "repro.reduce"]
    assert reduce_ == {"rounds": plan.round_count(), "messages": plan.message_count()}
    (local,) = [sp.stats for sp in call if sp.name == "repro.local_r"]
    assert local == {"ranks": P, "rows": M, "cols": PANEL}
    exchanges = [sp.stats["messages"] for sp in call if sp.name == "repro.exchange"]
    assert sum(exchanges) == plan.message_count()


def test_reading_of_a_recorded_window(recorded):
    prof, red, _, _ = recorded
    r = spans.reading(prof, red, CALLS + 1)
    assert set(r["metrics"]) == set(spans.METRICS)
    assert all(v > 0 for v in r["metrics"].values())
    assert r["metrics"]["host_factorize_ms"] >= r["metrics"]["exchange_ms"]
    assert r["counts"]["repro.recover"]["restored"] == 2 * CALLS
    # the host's time inside calls is put down whole, most of it to the program
    host = sum(t for _, t in r["host_in_call_ms_per_call"])
    calls = trace.union([(s, e) for n, s, e in red.spans if n == "chipbench.call"])
    assert host == pytest.approx(sum(e - s for s, e in calls) / 1e6 / (CALLS + 1))
    assert r["host_in_program_span"] > 0.5
    labels = {label for label, _ in r["idle_gaps"]}
    assert labels <= {"window", "call", "outside"} | set(PARENTS)
    assert r["device_scopes"] is None            # a CPU trace carries no op_name


def test_traced_reading_of_a_tiny_cell(tmp_path):
    import jax

    from chipbench import rehearsal, spec

    root = rehearsal.tiny_root(str(tmp_path / "bench"))
    cell = spec.load_cell("blocked_150Mx100.panel_death", root)
    r = spans.traced_reading(cell, 2 ** 33 + 1, 0.3, root=root, devices=jax.devices(),
                             out=str(tmp_path / "traces"))
    assert r["calls"] > 0 and r["raised"] == 0
    assert r["counts"]["repro.factorize"]["spans"] == r["calls"]
    assert r["counts"]["repro.recover"]["restored"] == 2 * r["calls"]
    assert all(v > 0 for v in r["metrics"].values())
    assert r["span_cost_us"] > 0 and r["device_scopes"] is None
    assert os.listdir(tmp_path / "traces") == [f"{cell.name}.{2 ** 33 + 1}.xplane.pb"]


def test_readings_are_none_without_program_spans():
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(CPU_TRACE)
    red = trace.reduce_file(CPU_TRACE)
    r = spans.reading(prof, red, 3)
    assert r["metrics"] == {m: None for m in spans.METRICS}
    assert r["span_ms_per_call"] == {} and r["device_scopes"] is None
    # with no program span the labels are the harness's own
    assert spans.idle_gaps(red, spans.segments(red, [])) == red.idle_gaps()


def test_harness_reduction_of_the_recorded_trace_is_unchanged():
    red = trace.reduce_file(CPU_TRACE)
    (dev,) = red.devices
    assert red.window == (13320, 5721485)
    assert (dev.busy_ns, dev.programs, len(dev.gaps)) == (157739, 234, 271)
    assert red.top_ops(3) == [["jit_qr/geqrf.3", 5.7224e-05],
                              ["jit_qr/copy_copy_fusion", 1.2269e-05],
                              ["jit_convert_element_type/copy", 9.096e-06]]
    assert red.idle_gaps(2) == [["call", 0.000132689], ["call", 0.000114402]]


# -- a trace laid out as a TPU's --------------------------------------------

def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


WHILE = "%while.1 = f32[8] while(f32[8] %a)"
FUSION = "%fusion.2 = f32[8] fusion(f32[8] %a)"
COPY = "%copy.3 = f32[8] copy(f32[8] %a)"
SLICE = "%slice.4 = f32[8] slice(f32[8] %a)"
OP_NAMES = {
    WHILE: "jit(fn)/jit(main)/while/body/repro.trailing_update/pallas_call",
    FUSION: "jit(fn)/jit(main)/while/body/repro.trailing_update/repro.local_r/dot",
    COPY: "jit(fn)/jit(main)/copy",
}


def tpu_profile():
    """One call: a program launched inside repro.launch, then an idle gap
    inside repro.form_q."""
    ops = [ev(WHILE, 100, 200), ev(FUSION, 120, 50), ev(COPY, 320, 30), ev(SLICE, 700, 40)]
    device = plane("/device:TPU:0", [line("XLA Modules", [ev("jit_fn(1)", 100, 250)]),
                                     line("XLA Ops", ops)])
    host = plane("/host:CPU", [line("python", [
        ev("chipbench.window", 50, 950),
        ev("chipbench.call", 60, 900),
        ev("repro.factorize", 70, 880, call=0, panels=4, deaths=0),
        ev("repro.launch", 80, 100),
        ev("repro.form_q", 400, 300, reorth=1),
        ev("repro.exchange", 400, 50, messages=4),
    ])])
    return types.SimpleNamespace(planes=[device, host])


def test_gap_inside_a_program_span_takes_its_label():
    prof = tpu_profile()
    red = trace.reduce_profile(prof)
    prog = spans.program_spans(prof, red.window)
    segs = spans.segments(red, prog)
    # gaps [50, 100), [300, 320), [350, 700) and [740, 1000), each labelled
    # at its middle
    assert spans.idle_gaps(red, segs, 3) == [["repro.form_q", pytest.approx(350e-9)],
                                            ["repro.factorize", pytest.approx(260e-9)],
                                            ["repro.factorize", pytest.approx(50e-9)]]
    assert red.idle_gaps(1) == [["call", pytest.approx(350e-9)]]   # the harness's label
    # every idle ns put down to the innermost span open then
    assert spans.time_by_label(segs, red.busiest().gaps) == {
        "window": 10 + 40, "call": 10 + 10, "repro.factorize": 10 + 20 + 50 + 210,
        "repro.launch": 20, "repro.exchange": 50, "repro.form_q": 250}


def test_device_scopes_from_op_name():
    prof = tpu_profile()
    red = trace.reduce_profile(prof)
    # own times: while 200 - 50, fusion 50, copy 30, slice 40 (no op_name)
    assert spans.device_scopes(prof, red, OP_NAMES) == [
        ["repro.trailing_update", pytest.approx(150e-9)], ["-", pytest.approx(70e-9)],
        ["repro.local_r", pytest.approx(50e-9)]]
    assert spans.device_scopes(prof, red, {COPY: OP_NAMES[COPY]}) is None
    assert spans.device_scopes(prof, red, {}) is None
    assert spans.scope_of("jit(f)/repro.reduce/repro.exchange/ppermute") == "repro.exchange"
    assert spans.scope_of("jit(f)/while/body/dot_general") == "-"


def _pb(*fields):
    """A protobuf message of (field number, int | bytes | str) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def test_op_names_from_event_metadata(tmp_path):
    def event_meta(i, text, op):
        stats = [(5, _pb((1, 26), (5, op)))] if op else []
        return (4, _pb((1, i), (2, _pb((1, i), (2, text), *stats))))

    device = _pb((1, 7), (2, "/device:TPU:0"), (3, _pb((1, 1), (2, "XLA Ops"))),
                 event_meta(1, FUSION, OP_NAMES[FUSION]), event_meta(2, SLICE, None),
                 event_meta(3, COPY, OP_NAMES[COPY]),
                 (5, _pb((1, 26), (2, _pb((1, 26), (2, "tf_op"))))),
                 (5, _pb((1, 3), (2, _pb((1, 3), (2, "hlo_op"))))))
    other = _pb((1, 8), (2, "/host:CPU"), event_meta(1, WHILE, OP_NAMES[WHILE]),
                (5, _pb((1, 26), (2, _pb((1, 26), (2, "tf_op"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, other), (1, device)))
    assert spans.op_names(str(path), "/device:TPU:0") == {FUSION: OP_NAMES[FUSION],
                                                         COPY: OP_NAMES[COPY]}
    assert spans.op_names(str(path), "/device:TPU:1") == {}


def test_per_call_ms_is_a_union():
    sp = [spans.Span(name, s, e, {}, "t") for name, s, e in
          [("repro.exchange", 0, 10), ("repro.exchange", 5, 20), ("repro.plan", 30, 40)]]
    assert spans.union_ns(sp, "exchange", (0, 100)) == 20
    assert spans.union_ns(sp, "exchange", (8, 100)) == 12
    assert spans.per_call_ms(sp, "exchange", (0, 100), 2) == 10e-6
    assert spans.per_call_ms(sp, "recover", (0, 100), 2) is None
