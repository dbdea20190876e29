"""The trace reduction: on a small trace recorded on the CPU, and on a
hand-built trace laid out as a TPU's is."""
import os
import types

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
CPU_TRACE = os.path.join(HERE, "testdata", "cpu_tsqr_3calls.xplane.pb")


@pytest.fixture(scope="module")
def cpu_reduction():
    # Three calls of a 1024 x 8 simulated-rank TSQR, each in a
    # chipbench.call span and followed by a chipbench.check span.
    return trace.reduce_file(CPU_TRACE)


def test_cpu_trace_counts_programs_of_every_call(cpu_reduction):
    (dev,) = cpu_reduction.devices
    assert dev.programs == 3 * 78


def test_cpu_trace_busy_within_window(cpu_reduction):
    (dev,) = cpu_reduction.devices
    assert 0 < dev.busy_ns < cpu_reduction.window_ns
    assert 0 < cpu_reduction.idle_share() < 1
    assert dev.collective_ns == 0


def test_cpu_trace_gaps_and_busy_fill_window(cpu_reduction):
    (dev,) = cpu_reduction.devices
    idle = sum(e - s for s, e in dev.gaps)
    assert idle + dev.busy_ns == pytest.approx(cpu_reduction.window_ns)


def test_cpu_trace_breakdown(cpu_reduction):
    ops = cpu_reduction.top_ops()
    assert 0 < len(ops) <= 10
    assert ops[0][0] == "jit_qr/geqrf.3"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = cpu_reduction.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert {label for label, _ in gaps} <= {"window", "call", "check"}
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


def test_cpu_trace_spans(cpu_reduction):
    names = [n for n, _, _ in cpu_reduction.spans]
    assert names.count("chipbench.call") == 3
    assert names.count("chipbench.window") == 1


# -- a trace laid out as a TPU's --------------------------------------------

def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def tpu_profile(shift=0):
    """Two calls of one program; the second device busier by ``shift``."""
    modules = [ev("jit_step(123)", 100, 300), ev("jit_step(123)", 600, 300)]
    ops = []
    for t0 in (100, 600):
        ops += [
            ev("%while.3 = f32[8] while(f32[8] %a)", t0, 200 + shift),
            ev("%fusion.1 = f32[8] fusion(f32[8] %x)", t0 + 10, 50),
            ev("%collective-permute-start.1 = f32[8] collective-permute-start(f32[8] %y)",
               t0 + 220, 10),
            ev("%collective-permute-done.1 = f32[8] collective-permute-done(f32[8] %z)",
               t0 + 260, 40),
        ]
    asyncs = [ev("%collective-permute-start.1 = f32[8] collective-permute-start(f32[8] %y)",
                 t0 + 220, 70) for t0 in (100, 600)]
    return [
        plane("/device:TPU:0", [line("XLA Modules", modules), line("XLA Ops", ops),
                                line("Async XLA Ops", asyncs)]),
        plane("/device:CUSTOM:Megascale Trace", [line("XLA Ops", [ev("x", 0, 10)])]),
    ]


def fake(*device_planes):
    host = plane("/host:CPU", [line("main", [
        ev("chipbench.window", 50, 950),
        ev("chipbench.call", 60, 450),
        ev("chipbench.call", 560, 430),
        ev("other", 0, 5),
    ])])
    return types.SimpleNamespace(planes=[*device_planes, host])


def test_tpu_layout_reduces():
    red = trace.reduce_profile(fake(*tpu_profile()))
    (dev,) = red.devices
    assert red.window == (50, 1000)
    assert dev.programs == 2
    # per call: [t0, t0+200) while, [t0+220, t0+230), [t0+260, t0+300)
    assert dev.busy_ns == 2 * (200 + 10 + 40)
    # collectives: the union of [t0+220, t0+290) and [t0+260, t0+300)
    assert dev.collective_ns == 2 * 80
    assert dev.op_ns["jit_step/while.3"] == 2 * 150  # its own time, body excluded
    assert dev.op_ns["jit_step/fusion.1"] == 2 * 50
    assert red.top_ops(1) == [["jit_step/while.3", 300e-9]]
    assert red.idle_gaps(1) == [["call", 200e-9]]  # [400, 600): the first call's tail


def test_busiest_device_and_idle_share():
    p0, _ = tpu_profile()
    p1, _ = tpu_profile(shift=50)
    p1.name = "/device:TPU:1"
    red = trace.reduce_profile(fake(p0, p1))
    assert red.busiest().name == "/device:TPU:1"
    assert red.idle_share() == pytest.approx(1 - 500 / 950)
    assert red.mean_busy_s() == pytest.approx((500 + 580) / 2 / 1e9)


def test_trace_without_window_span_is_an_error():
    prof = types.SimpleNamespace(planes=tpu_profile())
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce_profile(prof)


@pytest.mark.parametrize("text,want", [
    ("%collective-permute-start.1 = f32[8] collective-permute-start(f32[8] %y)", True),
    ("%all-reduce.3 = f32[8] all-reduce(f32[8] %y), to_apply=%add", True),
    ("%all-gather-done = f32[8] all-gather-done(f32[8] %y)", True),
    ("%fusion.1 = f32[8] fusion(f32[8] %x), calls=%fused_computation", False),
    ("%copy-start = (f32[8]) copy-start(f32[8] %x)", False),
    ("geqrf.3", False),
])
def test_is_collective(text, want):
    assert trace.is_collective(text) is want


def test_op_names():
    assert trace.op_name("%fusion.12 = f32[8] fusion(f32[8] %x), kind=kLoop") == "fusion.12"
    assert trace.op_name('%custom-call.3 = f32[8] custom-call(f32[8] %x), '
                         'custom_call_target="tpu_custom_call"') == "custom-call.3:tpu_custom_call"
    assert trace.op_name("geqrf.3") == "geqrf.3"
    assert trace.module_name("jit_qr(8548429971832385276)") == "jit_qr"


def test_union_and_self_times():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    own = dict(trace.self_times([("outer", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 5, 6)]))
    assert own == {"outer": 10 - 2 - 5, "a": 2, "b": 5 - 1, "c": 1}
