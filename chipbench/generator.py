"""The general generator of the benchmark's traffic: ``factorize`` calls
over a pool of seeded matrices, from one caller, in a closed or an open
loop.

A traffic file (``chipbench/traffic/<mix>.json``) gives:

  * ``loop``: ``"closed"``, each call started when the last has ended, for
    the window's seconds; or ``"open"``, calls due at Poisson arrivals of
    ``rate_per_s`` over the window, served in order, each call's latency
    taken from when it was due.  Every seed draws the same gaps between
    arrivals, in another order;
  * ``pool``: how many distinct matrices the loop cycles through;
  * ``layout``: ``"sim"``, the matrix as (P, m/P, n) row blocks on one
    chip, its ranks simulated; or ``"mesh"``, the global (m, n) matrix
    row-sharded over a ``("rows",)`` mesh of the cell's chips;
  * ``faults``: null, or the deaths injected into every call: for the
    single-panel TSQR ``{"deaths": {rank: exchange}}``, for the blocked
    driver ``{"panel": {panel: {rank: exchange}}}`` (and ``"update"``);
  * ``warmup_calls``: calls made in set-up, over the pool, so that every
    program the loop runs is compiled before the window;
  * ``check_every``: one call in this many is compared with the
    reference, from an offset drawn from the seed.  Take it coprime to
    ``pool``, so that the sampled calls cover every matrix of the pool;
  * ``trace_seconds``: the length of the traced window of a ``--trace 1`` run.

A mix that needs code of its own puts a module beside its file,
``chipbench/traffic/<mix>.py``: each function of :data:`API` that it
defines takes the place of this module's for that mix
(:func:`chipbench.spec.generator`).

The matrices are standard normal, made on the device in one jitted call
from the seed; the program receives only them.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

API = ("make_pool", "make_call", "drive", "expected_deaths")


def seed_words(seed: int) -> tuple[int, int]:
    """Two 31-bit words from a seed of any size."""
    s = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return int(s[0]) & 0x7FFFFFFF, int(s[1]) & 0x7FFFFFFF


def check_offset(seed: int, every: int) -> int:
    return int(np.random.default_rng(seed_words(seed)).integers(every))


def mesh_of(devices):
    import jax

    return jax.sharding.Mesh(np.asarray(devices), ("rows",))


def make_pool(cell, seed: int, devices):
    """The pool of matrices, on the device, and the mesh (or None)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    m, n = cell.shape
    p = cell.config["ranks"]
    t = cell.traffic
    if t["layout"] == "sim":
        mesh, shape = None, (p, m // p, n)
        sharding = SingleDeviceSharding(devices[0])
    elif t["layout"] == "mesh":
        mesh, shape = mesh_of(devices[:cell.chips]), (m, n)
        if mesh.shape["rows"] != p:
            raise ValueError(f"a mesh of {mesh.shape['rows']} chips for {p} ranks")
        sharding = NamedSharding(mesh, PartitionSpec("rows"))
    else:
        raise ValueError(f"unknown layout {t['layout']!r}")
    k = int(t["pool"])
    dtype = jnp.dtype(cell.config["dtype"])

    # The seed enters as an argument, not a constant of the program, so
    # every seed runs the one program the persistent cache holds.
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        return tuple(jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
                     for i in range(k))

    words = np.asarray(seed_words(seed), np.uint32)
    pool = jax.jit(make, out_shardings=(sharding,) * k)(words)
    return jax.block_until_ready(pool), mesh


def make_call(cell, mesh):
    """``call(a)``: one ``factorize`` of the cell, ending in
    ``block_until_ready`` on its R and validity; returns the two."""
    import jax

    from repro.qr import QRConfig, factorize

    cfg = QRConfig(**cell.config["qr_config"])
    faults = fault_arg(cell)
    kw = {} if mesh is None else {"mesh": mesh, "axis": "rows"}

    def call(a):
        res = factorize(a, cfg, faults=faults, **kw)
        return jax.block_until_ready((res.r, res.valid))

    return call


@dataclasses.dataclass
class Window:
    """What a window of calls left: every call's latency in seconds, the
    sampled calls' (R, validity) as the program returned them, keyed by
    the call's number, the calls that raised, and the window's length."""

    latencies_s: list
    kept: dict
    raised: list
    window_s: float


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop."""
    rate = float(traffic["rate_per_s"])
    gaps = np.random.default_rng(0).exponential(1.0 / rate, max(1, round(rate * seconds)))
    return np.cumsum(np.random.default_rng(seed_words(seed)).permutation(gaps))


def drive(cell, call, pool, seconds: float, seed: int, span) -> Window:
    """The window: calls over the pool in the traffic's loop.  The sampled
    results stay on the device until the window has closed, so that
    copying them to the host is no part of the time measured."""
    t = cell.traffic
    every = int(t["check_every"])
    offset = check_offset(seed, every)
    if t["loop"] == "open":
        due = arrivals(t, seed, seconds)
    elif t["loop"] != "closed":
        raise ValueError(f"unknown loop {t['loop']!r}")
    lat, kept, raised = [], {}, []
    w0 = time.perf_counter()
    i = 0
    while True:
        if t["loop"] == "closed":
            start = time.perf_counter()
            if i and start - w0 >= seconds:
                break
        else:
            if i == len(due):
                break
            start = w0 + due[i]
            if start > time.perf_counter():
                time.sleep(start - time.perf_counter())
        try:
            with span("call"):
                out = call(pool[i % len(pool)])
        except Exception as e:  # a call that raises is a failed call
            out = None
            raised.append(f"call {i}: {type(e).__name__}: {e}")
        lat.append(time.perf_counter() - start)
        if out is not None and i % every == offset:
            kept[i] = out
        i += 1
    return Window(lat, kept, raised, time.perf_counter() - w0)


def fault_arg(cell):
    """The ``faults=`` argument of every call, or None."""
    f = cell.traffic.get("faults")
    if not f:
        return None
    from repro.collective import FaultSpec
    from repro.qr import PanelFaultSchedule

    def spec(d):
        return {int(r): int(s) for r, s in d.items()}

    if cell.config["qr_config"].get("panel_width") is None:
        return FaultSpec.of(spec(f["deaths"]))
    return PanelFaultSchedule.of(
        panel={int(k): spec(v) for k, v in f.get("panel", {}).items()},
        update={int(k): spec(v) for k, v in f.get("update", {}).items()},
    )


def expected_deaths(cell) -> list[dict[int, int]]:
    """Each butterfly's deaths, as the reference reads them: one mapping
    per reduction that a fault strikes (empty where none does)."""
    f = cell.traffic.get("faults") or {}
    out = []
    if "deaths" in f:
        out.append({int(r): int(s) for r, s in f["deaths"].items()})
    for group in ("panel", "update"):
        for v in f.get(group, {}).values():
            out.append({int(r): int(s) for r, s in v.items()})
    return out
