"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Per device of the trace, within the traced window:

  * busy time: the union of the intervals in which an operation ran;
  * idle gaps: the holes in that union, each labelled by the benchmark's
    own host span (``chipbench.<name>``) that was open at its middle;
  * device time per operation, its own time without the operations
    nested in it (a ``while`` holds its body's operations), named
    ``<program>/<operation>``;
  * program executions;
  * device time of the collective operations (the union of their
    intervals, asynchronous transfers included).

A TPU trace has one plane per chip (``/device:TPU:<i>``).  Its
``XLA Ops`` line holds the operations, each named by its HLO text
(``%fusion.12 = f32[...] fusion(...)``); ``Async XLA Ops`` holds the
asynchronous transfers; ``XLA Modules`` holds one event per program
execution, named ``jit_<name>(<fingerprint>)``.  A CPU trace has no
device plane: its operations are host events that carry an ``hlo_op``
stat, and a program execution is one ``run_id`` of one ``hlo_module``.
Both reduce alike.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"

_DEVICE_PLANE = re.compile(r"^/device:(?!CUSTOM)[A-Z]+:\d+$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_MODULE_NAME = re.compile(r"^([^(]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?[.(\s]"
)


def is_collective(text: str) -> bool:
    """Is the operation (its name or HLO text) an exchange between chips?"""
    return bool(_COLLECTIVE.search(text + " "))


def op_name(text: str) -> str:
    """The HLO name of an operation event: ``fusion.12`` of
    ``%fusion.12 = f32[...] fusion(...)``; a custom call adds its target,
    ``custom-call.3:tpu_custom_call``."""
    m = _HLO_NAME.match(text)
    if not m:
        return text
    target = _TARGET.search(text)
    return m.group(1) + (":" + target.group(1) if target else "")


def module_name(text: str) -> str:
    """``jit_qr`` of ``jit_qr(8548429971832385276)``."""
    m = _MODULE_NAME.match(text)
    return m.group(1) if m else text


def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def self_times(ops):
    """Each operation's own time: its interval less those nested in it.
    ``ops`` are (name, start, end) on one timeline."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [e - s for _, s, e in ops]
    stack = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(name, t) for (name, _, _), t in zip(ops, own)]


@dataclasses.dataclass
class Device:
    """One device's reduction over the traced window (times in ns)."""

    name: str
    busy_ns: int
    op_ns: dict
    programs: int
    collective_ns: int
    gaps: list


@dataclasses.dataclass
class Reduction:
    """The reduction of one trace: the window, its devices, the host spans."""

    window: tuple
    devices: list
    spans: list

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busiest(self) -> Device:
        return max(self.devices, key=lambda d: d.busy_ns)

    def idle_share(self) -> float:
        """Largest idle share of the window over the devices, in [0, 1]."""
        return max(1.0 - d.busy_ns / self.window_ns for d in self.devices)

    def mean_busy_s(self) -> float:
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def top_ops(self, k: int = 10):
        """[name, seconds] of the operations that took most time on the
        busiest device."""
        ops = sorted(self.busiest().op_ns.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in ops]

    def label(self, t: float) -> str:
        """The innermost benchmark span open at time ``t``."""
        best = None
        for name, s, e in self.spans:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0][len(SPAN_PREFIX):] if best else "outside"

    def idle_gaps(self, k: int = 10):
        """[span, seconds] of the longest idle gaps on the busiest device."""
        gaps = sorted(self.busiest().gaps, key=lambda g: g[0] - g[1])[:k]
        return [[self.label((s + e) / 2), (e - s) / 1e9] for s, e in gaps]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]


def _device(plane, lo, hi):
    """(ops, async ops, program executions) of a TPU plane, each op named
    ``<program>/<operation>``."""
    lines = {line.name: _events(line) for line in plane.lines}
    modules = sorted(lines.get("XLA Modules", []), key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def named(text, s, e):
        i = bisect.bisect_right(starts, s) - 1
        prog = module_name(modules[i][0]) + "/" if i >= 0 and s < modules[i][2] else ""
        return prog + op_name(text), text, s, e

    ops = [named(*ev) for ev in lines.get("XLA Ops", []) if ev[2] > lo and ev[1] < hi]
    async_ops = [(op_name(t), t, s, e) for t, s, e in lines.get("Async XLA Ops", [])
                 if e > lo and s < hi]
    programs = sum(1 for _, s, e in modules if e > lo and s < hi)
    return ops, async_ops, programs


def _host(plane, lo, hi):
    """(ops, [], program executions) of a CPU trace's host plane."""
    ops, runs = [], set()
    for line in plane.lines:
        for ev in line.events:
            st = _stats(ev)
            if "hlo_op" not in st:
                continue
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > lo and s < hi:
                ops.append((f"{st.get('hlo_module')}/{ev.name}", ev.name, s, e))
                runs.add((st.get("hlo_module"), st.get("run_id")))
    return ops, [], len(runs)


def _reduce(name, ops, async_ops, programs, lo, hi) -> Device:
    busy = union(clip([(s, e) for _, _, s, e in ops], lo, hi))
    op_ns: dict = {}
    for n, t in self_times([(n, max(s, lo), min(e, hi)) for n, _, s, e in ops]):
        op_ns[n] = op_ns.get(n, 0) + t
    coll = union(clip([(s, e) for _, text, s, e in ops + async_ops if is_collective(text)],
                      lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return Device(name=name, busy_ns=sum(e - s for s, e in busy), op_ns=op_ns,
                  programs=programs, collective_ns=sum(e - s for s, e in coll), gaps=gaps)


def reduce_profile(profile) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` over the benchmark's window span."""
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    planes = [p for p in profile.planes if _DEVICE_PLANE.match(p.name)]
    read = _device
    if not planes:
        planes = [p for p in profile.planes if p.name.startswith("/host:")]
        read = _host
    devices = []
    for plane in planes:
        ops, async_ops, programs = read(plane, lo, hi)
        if ops:
            devices.append(_reduce(plane.name, ops, async_ops, programs, lo, hi))
    if not devices:
        raise ValueError("the trace holds no device operation in its window")
    return Reduction(window=(lo, hi), devices=devices, spans=spans)


def reduce_file(path: str) -> Reduction:
    """Reduce the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
