"""The scan-compiled single-program blocked QR (DESIGN.md §9):

  * hypothesis sweep — the fixed-shape pipeline is **bit-identical** to the
    eager per-panel driver over ragged m/n/panel widths/dtypes on both the
    jnp and Pallas kernel paths (the padded trailing width and the shifted
    layout must be numerically invisible);
  * fault scenarios still route to the general driver with unchanged
    semantics and ``PanelReport``s;
  * zero-retrace contracts — the guarded entry points (sim pipeline,
    batched, both shard_map drivers, both TSQR shard entry points,
    ``ft_allreduce_jit``) perform no new traces on a repeat call with
    identical statics and shapes;
  * batched throughput — B independent factorizations under one dispatch,
    fp-tight against per-matrix runs, and ``jax.vmap`` over the
    pytree-registered results;
  * the supporting machinery: value-keyed ``Plan`` hashing, memoized
    ``make_plan``, cached ``Plan.is_fault_free``, the ``pad_cross`` kernel
    vs its oracle, and the dispatch/trace counters.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.collective import FaultSpec, SimComm, ft_allreduce_jit, make_plan
from repro.kernels import dispatch, traffic
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.qr import (
    PanelFaultSchedule,
    blocked_qr_batched,
    blocked_qr_shard_map,
    blocked_qr_sim,
    tsqr_gram_shard_map,
    tsqr_shard_map,
    tsqr_sim,
)
from repro.qr.blocked import PIPELINE_NAME

VARIANTS_FF = ("redundant", "replace", "selfhealing")


def _blocks(rng, p, m_local, n, dt=np.float32):
    return jnp.asarray(
        rng.standard_normal((p, m_local, n)).astype(np.float32), dtype=dt
    )


def _assert_bitwise(res_a, res_b):
    assert (np.asarray(res_a.r) == np.asarray(res_b.r)).all()
    assert (np.asarray(res_a.valid) == np.asarray(res_b.valid)).all()
    if res_a.q is not None or res_b.q is not None:
        assert (np.asarray(res_a.q) == np.asarray(res_b.q)).all()


# ---------------------------------------------------------------------------
# Bit-identity: pipeline vs eager driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", VARIANTS_FF)
def test_pipeline_bit_identical_basic(rng, variant):
    a = _blocks(rng, 4, 48, 20)
    for use_pallas in (False, True):
        eager = blocked_qr_sim(
            a, panel_width=6, variant=variant, compute_q=True,
            use_pallas=use_pallas, pipeline="off",
        )
        pipe = blocked_qr_sim(
            a, panel_width=6, variant=variant, compute_q=True,
            use_pallas=use_pallas, pipeline="on",
        )
        _assert_bitwise(eager, pipe)


def test_pipeline_bit_identical_hypothesis(rng):
    """The satellite sweep: ragged m/n/panel widths/dtypes, both backends."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property-based sweeps need the hypothesis "
        "extra (pip install -r requirements-dev.txt)"
    )
    del hypothesis
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(
        p=st.sampled_from([2, 4, 8]),
        m_local=st.integers(8, 80),
        n=st.integers(2, 36),
        pw=st.integers(1, 40),
        dt=st.sampled_from([jnp.float32, jnp.bfloat16]),
        use_pallas=st.booleans(),
        compute_q=st.booleans(),
        local_r=st.sampled_from(["chol", "jnp"]),
        seed=st.integers(0, 2**16),
    )
    @settings(
        max_examples=25, deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large],
    )
    def sweep(p, m_local, n, pw, dt, use_pallas, compute_q, local_r, seed):
        pw = min(pw, n)
        m_local = max(m_local, pw)
        a = _blocks(np.random.default_rng(seed), p, m_local, n, dt)
        kw = dict(
            panel_width=pw, compute_q=compute_q, use_pallas=use_pallas,
            local_r=local_r,
        )
        _assert_bitwise(
            blocked_qr_sim(a, pipeline="off", **kw),
            blocked_qr_sim(a, pipeline="on", **kw),
        )

    sweep()


def test_pipeline_acceptance_shape_bit_identical(rng):
    """The acceptance criterion: 4096×512 at panel width 128 on 8 ranks —
    single program, bit-identical (Q, R, valid), one dispatch, K traced
    sweeps."""
    blocks = _blocks(rng, 8, 512, 512)
    eager = blocked_qr_sim(
        blocks, panel_width=128, compute_q=True, pipeline="off"
    )
    t0 = dispatch.trace_count(PIPELINE_NAME)
    with dispatch.track_dispatch() as d, traffic.track_traffic() as t:
        pipe = blocked_qr_sim(
            blocks, panel_width=128, compute_q=True, pipeline="on"
        )
    _assert_bitwise(eager, pipe)
    assert d.dispatches[PIPELINE_NAME] == 1
    assert t.sweeps_of("panel_cross", "pad_cross", "trailing_update") == 4
    # warm repeat: zero new traces
    t1 = dispatch.trace_count(PIPELINE_NAME)
    blocked_qr_sim(blocks, panel_width=128, compute_q=True, pipeline="on")
    assert dispatch.trace_count(PIPELINE_NAME) == t1
    assert t1 - t0 <= 1


def _eqns(jaxpr, in_scan=False):
    """Every equation of ``jaxpr`` and of the bodies nested in it (not
    inside Pallas kernels), with whether a ``scan`` body holds it."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(
                        inner, in_scan or eqn.primitive.name == "scan"
                    )


@pytest.mark.parametrize("fuse", ["auto", "off"])
def test_pipeline_shifts_inside_the_update_kernel(fuse):
    """The scan pipeline shifts its padded working matrix inside the
    trailing-update kernel: neither the scan body nor the static stage
    after it slices out the trailing block or pads the result back to the
    working width — the two full passes over HBM per panel that the
    separate slice and concatenate made."""
    from repro.qr import QRConfig, factorize

    p, m_local, n, pw = 4, 512, 20, 6
    n_pad = pw * 4                                 # widths (6, 6, 6, 2)
    cfg = QRConfig(panel_width=pw, use_pallas=True, interpret=True,
                   pipeline="on", fuse=fuse)
    a = jnp.zeros((p, m_local, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: factorize(x, cfg).r)(a).jaxpr
    working = {(p, m_local, n_pad), (p, m_local, n_pad - pw)}
    moves, kernels, in_scan = [], [], False
    for eqn, scanned in _eqns(jaxpr):
        in_scan |= scanned
        name = eqn.primitive.name
        shapes = {tuple(v.aval.shape) for v in eqn.outvars}
        if name in ("pad", "concatenate", "slice", "dynamic_slice") and (
            shapes & working
        ):
            moves.append((name, shapes))
        if name == "pallas_call" and eqn.params["name"] == (
            "trailing_update_shift"
        ):
            kernels.append(scanned)
    assert in_scan and not moves
    # one shifted update traced into the scan body; the fused schedule's
    # static penultimate stage runs one more outside it
    assert sorted(kernels) == ([False, True] if fuse == "auto" else [True])

    with traffic.track_traffic() as t:
        factorize(_blocks(np.random.default_rng(0), p, 64, n), cfg)
    assert t.shifted_sweeps == 3
    assert t.sweeps_of("pad_cross", "trailing_update") == 4
    # the jnp path slices and re-pads around an unshifted update
    with traffic.track_traffic() as t:
        factorize(_blocks(np.random.default_rng(0), p, 64, n),
                  dataclasses.replace(cfg, use_pallas=False))
    assert t.shifted_sweeps == 0
    assert t.sweeps_of("pad_cross", "trailing_update") == 4
    # the acceptance shape: still one sweep per panel, 3 of them shifted
    with traffic.track_traffic() as t:
        factorize(_blocks(np.random.default_rng(1), 8, 512, 512),
                  QRConfig(panel_width=128, pipeline="on", use_pallas=True,
                           interpret=True, fuse=fuse))
    assert t.sweeps_of("panel_cross", "pad_cross", "trailing_update") == 4
    assert t.shifted_sweeps == 3


# ---------------------------------------------------------------------------
# Fault routing: the general driver is untouched
# ---------------------------------------------------------------------------

def test_faults_route_to_general_driver(rng):
    a = _blocks(rng, 8, 32, 15)
    sched = PanelFaultSchedule.of(panel={1: {2: 1}})
    with traffic.track_traffic() as t:
        auto = blocked_qr_sim(
            a, panel_width=4, variant="replace", faults=sched
        )
    # eager per-panel kernels ran (one prime + one update per non-final
    # panel as separate dispatches), not the single-program pipeline
    assert t.dispatches == auto.n_panels
    forced = blocked_qr_sim(
        a, panel_width=4, variant="replace", faults=sched, pipeline="off"
    )
    _assert_bitwise(auto, forced)
    assert auto.reports == forced.reports
    rep = auto.reports[1]
    assert rep.within_tolerance and rep.recovered_r == 1


def test_pipeline_on_rejects_faults(rng):
    a = _blocks(rng, 4, 16, 8)
    with pytest.raises(ValueError, match="fault-free"):
        blocked_qr_sim(
            a, panel_width=4, faults=PanelFaultSchedule.of(panel={0: {1: 1}}),
            pipeline="on",
        )
    with pytest.raises(ValueError, match="pipeline"):
        blocked_qr_sim(a, panel_width=4, pipeline="maybe")


def test_tree_variant_routes_to_general_driver(rng):
    """tree's fault-free plans leave non-roots invalid — not pipeline
    eligible; the general driver (with its replica fetch) still serves."""
    a = _blocks(rng, 4, 32, 12)
    with traffic.track_traffic() as t:
        res = blocked_qr_sim(a, panel_width=4, variant="tree")
    assert t.dispatches == res.n_panels      # eager kernels, not 1 program
    assert np.asarray(res.valid).sum() == 1


# ---------------------------------------------------------------------------
# Zero-retrace contracts
# ---------------------------------------------------------------------------

def test_sim_pipeline_zero_retrace(rng):
    a = _blocks(rng, 4, 56, 21)
    blocked_qr_sim(a, panel_width=6)
    before = dispatch.trace_count(PIPELINE_NAME)
    blocked_qr_sim(a, panel_width=6)
    assert dispatch.trace_count(PIPELINE_NAME) == before
    # a different static config compiles separately, once
    blocked_qr_sim(a, panel_width=7)
    mid = dispatch.trace_count(PIPELINE_NAME)
    blocked_qr_sim(a, panel_width=7)
    assert dispatch.trace_count(PIPELINE_NAME) == mid


def test_tsqr_shard_map_zero_retrace(rng):
    """The satellite regression: the old per-call ``jax.jit(shard)`` rebuilt
    the compile cache every call; the second call must not trace."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    a = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    tsqr_shard_map(a, mesh=mesh, axis="x", compute_q=True)
    before = dispatch.trace_count("tsqr_shard_map")
    # …even through a *fresh but equal* mesh object (value-keyed caches)
    mesh2 = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    res = tsqr_shard_map(a, mesh=mesh2, axis="x", compute_q=True)
    assert dispatch.trace_count("tsqr_shard_map") == before
    assert res.q is not None

    tsqr_gram_shard_map(a, mesh=mesh, axis="x")
    before = dispatch.trace_count("tsqr_gram_shard_map")
    tsqr_gram_shard_map(a, mesh=mesh, axis="x")
    assert dispatch.trace_count("tsqr_gram_shard_map") == before


def test_blocked_shard_map_zero_retrace(rng):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    a = jnp.asarray(rng.standard_normal((64, 12)).astype(np.float32))
    # pipeline path
    blocked_qr_shard_map(a, mesh=mesh, axis="x", panel_width=5)
    before = dispatch.trace_count(PIPELINE_NAME)
    res = blocked_qr_shard_map(a, mesh=mesh, axis="x", panel_width=5)
    assert dispatch.trace_count(PIPELINE_NAME) == before
    assert np.asarray(res.valid).all()
    # general (faulted) path: same statics → cached program
    sched = PanelFaultSchedule.of(panel={0: {0: 99}})   # no-op death step
    blocked_qr_shard_map(
        a, mesh=mesh, axis="x", panel_width=5, faults=sched
    )
    before = dispatch.trace_count("blocked_qr_shard_map")
    blocked_qr_shard_map(
        a, mesh=mesh, axis="x", panel_width=5, faults=sched
    )
    assert dispatch.trace_count("blocked_qr_shard_map") == before


def test_ft_allreduce_jit_zero_retrace(rng):
    x = jnp.asarray(rng.standard_normal((4, 6)).astype(np.float32))
    comm = SimComm(4)
    v1, ok1 = ft_allreduce_jit(x, comm, op="sum")
    before = dispatch.trace_count("ft_allreduce")
    v2, ok2 = ft_allreduce_jit(x, comm, op="sum")
    assert dispatch.trace_count("ft_allreduce") == before
    assert (np.asarray(v1) == np.asarray(v2)).all()
    ve, _ = ft_allreduce_jit(x, comm, op="mean")       # different combiner
    np.testing.assert_allclose(np.asarray(ve) * 4, np.asarray(v1), rtol=1e-6)
    from repro.collective import ShardMapComm, ft_allreduce

    np.testing.assert_allclose(
        np.asarray(v1), np.asarray(ft_allreduce(x, comm, op="sum")[0]),
        rtol=0, atol=0,
    )
    with pytest.raises(ValueError, match="shard_map"):
        ft_allreduce_jit(x, ShardMapComm(4, "x"), op="sum")


# ---------------------------------------------------------------------------
# Batched throughput
# ---------------------------------------------------------------------------

def test_batched_one_dispatch_fp_tight(rng):
    ab = jnp.asarray(
        rng.standard_normal((5, 4, 40, 20)).astype(np.float32)
    )
    with dispatch.track_dispatch() as d:
        bres = blocked_qr_batched(ab, panel_width=6, compute_q=True)
    assert d.dispatches[PIPELINE_NAME] == 1
    assert bres.r.shape == (5, 4, 20, 20)
    assert np.asarray(bres.valid).all()
    for i in range(5):
        single = blocked_qr_sim(ab[i], panel_width=6, compute_q=True)
        scale = np.abs(np.asarray(single.r)).max()
        assert np.abs(
            np.asarray(bres.r)[i] - np.asarray(single.r)
        ).max() / scale < 1e-5
        assert np.abs(np.asarray(bres.q)[i] - np.asarray(single.q)).max() < 1e-5
    # warm batched repeat: zero traces
    before = dispatch.trace_count(PIPELINE_NAME)
    blocked_qr_batched(ab, panel_width=6, compute_q=True)
    assert dispatch.trace_count(PIPELINE_NAME) == before


def test_batched_validation(rng):
    with pytest.raises(ValueError, match="B, P"):
        blocked_qr_batched(
            jnp.zeros((4, 16, 8), jnp.float32), panel_width=4
        )
    # tree's fault-free plans leave non-roots invalid — the pipeline has no
    # validity machinery, so the batched entry must refuse rather than
    # report every rank valid on a NaN-polluted result
    with pytest.raises(ValueError, match="pipeline-eligible"):
        blocked_qr_batched(
            jnp.zeros((2, 4, 16, 8), jnp.float32), panel_width=4,
            variant="tree",
        )


def test_results_are_vmappable(rng):
    """The pytree registration satellite: results flow through jax.vmap."""
    ab = jnp.asarray(rng.standard_normal((3, 4, 24, 8)).astype(np.float32))
    vb = jax.vmap(lambda x: blocked_qr_sim(x, panel_width=4))(ab)
    direct = blocked_qr_batched(ab, panel_width=4)
    assert (np.asarray(vb.r) == np.asarray(direct.r)).all()
    assert vb.reports == direct.reports

    vt = jax.vmap(lambda x: tsqr_sim(x, compute_q=True))(ab)
    assert vt.r.shape == (3, 4, 8, 8)
    s0 = tsqr_sim(ab[0], compute_q=True)
    np.testing.assert_allclose(
        np.asarray(vt.r)[0], np.asarray(s0.r), rtol=1e-5, atol=1e-5
    )
    assert vt.plan == s0.plan


# ---------------------------------------------------------------------------
# Supporting machinery
# ---------------------------------------------------------------------------

def test_plan_hashable_and_memoized():
    p1 = make_plan("redundant", 8)
    p2 = make_plan("redundant", 8)
    assert p1 is p2                       # memoized
    p3 = make_plan("redundant", 8, FaultSpec.of({1: 0}))
    assert p1 == p2 and hash(p1) == hash(p2)
    assert p1 != p3
    assert len({p1, p2, p3}) == 2
    assert p1.is_fault_free and not p3.is_fault_free
    # cached_property: computed once, stored on the instance
    assert "is_fault_free" in p1.__dict__
    assert make_plan("tree", 8) != make_plan("redundant", 8)


def test_pad_cross_kernel_matches_oracle(rng):
    for m, n, split, out_w in [(50, 12, 5, 16), (64, 8, 8, 8), (7, 3, 1, 9)]:
        a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        a_pad, s = kops.pad_cross(a, split=split, out_width=out_w,
                                  use_pallas=True)
        ra, rs = kref.pad_cross(a, split=split, out_width=out_w)
        assert a_pad.shape == (m, out_w) and s.shape == (split, out_w)
        np.testing.assert_array_equal(np.asarray(a_pad), np.asarray(ra))
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=1e-6, atol=1e-6)
        # pad columns are exact zeros; real columns bit-match panel_cross
        assert (np.asarray(s)[:, n:] == 0).all()
        plain = kops.panel_cross(a, split=split, use_pallas=True)
        np.testing.assert_array_equal(
            np.asarray(s)[:, :n], np.asarray(plain)
        )


def test_dispatch_counters(rng):
    with dispatch.track_dispatch() as d:
        dispatch.note_dispatch("x")
        dispatch.note_trace("y")
    assert d.n_dispatches == 1 and d.n_traces == 1
    assert d.as_dict() == {"traces": {"y": 1}, "dispatches": {"x": 1}}
    # traffic records carry dispatches/traces alongside bytes
    a = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    with traffic.track_traffic() as t:
        kops.gram(a, use_pallas=True)
        kops.gram(a, use_pallas=True)
        traffic.note(
            "panel_reduce", dispatches=0, rounds=2, wire_bytes=64,
            overlapped=1,
        )
    assert t.dispatches == 2
    assert {"dispatches", "traces", "rounds", "wire_bytes"} <= set(
        t.records[0]
    )
    assert t.as_dict()["dispatches"] == 2
    assert t.collective_rounds == 2 and t.rounds_of("panel_reduce") == 2
    assert t.wire_bytes == 64 and t.overlapped == 1


def test_span_inert_without_profiler():
    """No profiler session: a span records nothing, changes no result and,
    under jit, only names the ops traced inside it."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    x = jnp.arange(8.0)
    with dispatch.span(dispatch.EXCHANGE, messages=4):
        eager = x * 2.0 + 1.0
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(x * 2.0 + 1.0))

    def body(v):
        with dispatch.span(dispatch.REDUCE, rounds=2):
            return v * 2.0 + 1.0

    fun = jax.jit(body)
    np.testing.assert_array_equal(np.asarray(fun(x)), np.asarray(eager))
    assert 'op_name="jit(body)/repro.reduce/' in fun.lower(x).compile().as_text()
    with dispatch.span(dispatch.LAUNCH):       # a warm call under a span: no retrace
        before = fun._cache_size()
        fun(x)
        assert fun._cache_size() == before


def test_dispatch_bench_case_runs():
    from repro.bench.cases.dispatch import run

    rows = run(p=2, m_local=24, n=10, panel_width=4, batch=2, repeats=1)
    assert rows["bit_identical_eager"] and rows["bit_identical_warm"]
    assert rows["traces_second"] == 0
    assert rows["dispatches_cold"] == 1
    assert rows["dispatches_half_width"] == 1
    assert rows["dispatches_batched"] == 1
    assert rows["allreduce_retrace"] == 0
    assert rows["batch_rel_err"] < 1e-5
