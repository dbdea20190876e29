"""Property-based coverage (hypothesis) for the blocked-QR hot path:

  * the fused trailing-update Pallas kernel against the unfused kernel
    composition (bit-identical — the lookahead ``S`` accumulator uses the
    same panel boundaries and cast points as ``panel_cross`` re-run on the
    stored output) and the pure-jnp oracle (tolerance), across dtypes
    (bf16/f32), ragged shapes (m, n_trail, panel widths not multiples of
    the block size), streaming block sizes, and batch dims;
  * the shifted update the scan pipeline runs against slice → update →
    zero pad, bit for bit, on both kernel paths;
  * the blocked driver end-to-end against the dense numpy QR over ragged
    m/n/panel-width combinations.

Mirrors tests/test_fused_property.py; runs in interpret mode on CPU
(backend auto-detection), compiles under Mosaic on TPU.
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property-based sweeps need the hypothesis extra "
    "(pip install -r requirements-dev.txt)"
)
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.trailing_update import (  # noqa: E402
    panel_cross,
    trailing_update,
)

SET = settings(
    max_examples=25,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)

DTYPES = [jnp.float32, jnp.bfloat16]


def _arr(seed, shape, dt):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dt)


# ---------------------------------------------------------------------------
# trailing_update: ragged shapes, dtypes, block sizes — bit-level fusion
# ---------------------------------------------------------------------------

@given(
    m=st.integers(1, 500),
    nt=st.integers(1, 40),
    b=st.integers(1, 24),
    next_frac=st.floats(0.0, 1.0),
    block_rows=st.sampled_from([8, 32, 136, 1024]),
    dt=st.sampled_from(DTYPES),
    seed=st.integers(0, 2**16),
)
@SET
def test_fused_lookahead_bit_matches_separate_cross(
    m, nt, b, next_frac, block_rows, dt, seed
):
    """One fused sweep == update then ``panel_cross`` on the stored output,
    bit for bit, at any raggedness (edge-tile masking) and panel height."""
    next_width = max(1, round(next_frac * nt))
    a = _arr(seed, (m, nt), dt)
    q = _arr(seed + 1, (m, b), dt)
    w = _arr(seed + 2, (b, nt), dt)
    a_new, s = trailing_update(
        a, q, w, next_width=next_width, block_rows=block_rows
    )
    a_sep = trailing_update(a, q, w, block_rows=block_rows)
    s_sep = panel_cross(a_sep, split=next_width, block_rows=block_rows)
    assert a_new.shape == (m, nt) and s.shape == (next_width, nt)
    assert np.array_equal(
        np.asarray(a_new, np.float32), np.asarray(a_sep, np.float32)
    )
    assert np.array_equal(np.asarray(s), np.asarray(s_sep))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("dt", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "lead,b,n_pad,n,next_width",
    [
        ((), 1, 3, 3, 1),
        ((4,), 1, 4, 4, 1),
        ((), 6, 18, 18, 6),
        ((4,), 6, 24, 20, 6),
        ((), 32, 96, 96, 32),
        ((4,), 32, 128, 100, 32),     # the 100-column, 32-panel layout
        ((), 6, 24, 20, 0),           # no next panel
        ((4,), 32, 128, 100, 0),
    ],
)
def test_shifted_update_bit_matches_slice_then_pad(
    use_pallas, dt, lead, b, n_pad, n, next_width
):
    """The scan pipeline's update at ``shift=b`` on its ``n_pad``-wide
    working matrix (zero past column ``n``) == the trailing block sliced
    out, updated, and padded back with ``b`` zero columns — bit for bit,
    in ``A_new`` and ``S``, at a row count ragged against the panels."""
    m, block_rows = 77, 32
    awork = _arr(b * n, lead + (m, n_pad), dt).at[..., n:].set(0)
    q = _arr(b + 1, lead + (m, b), dt)
    w = _arr(b + 2, lead + (b, n_pad - b), dt)
    kw = dict(next_width=next_width, use_pallas=use_pallas,
              block_rows=block_rows)
    got = ops._trailing_update_raw(awork, q, w, shift=b, **kw)
    want = ops._trailing_update_raw(awork[..., b:], q, w, **kw)
    if not next_width:
        got, want = (got,), (want,)
    for g, x in zip(got, want):
        x = jnp.concatenate([x, jnp.zeros(x.shape[:-1] + (b,), x.dtype)], -1)
        assert g.shape == x.shape and g.dtype == x.dtype
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(x, np.float32))


@given(
    m=st.integers(1, 500),
    nt=st.integers(1, 32),
    b=st.integers(1, 16),
    dt=st.sampled_from(DTYPES),
    seed=st.integers(0, 2**16),
)
@SET
def test_trailing_update_close_to_oracle(m, nt, b, dt, seed):
    a = _arr(seed, (m, nt), dt)
    q = _arr(seed + 1, (m, b), dt)
    w = _arr(seed + 2, (b, nt), dt)
    next_width = min(4, nt)
    a_new, s = ops.trailing_update(a, q, w, next_width=next_width,
                                   use_pallas=True)
    a_ref, s_ref = ref.trailing_update(a, q, w, next_width=next_width)
    if dt == jnp.bfloat16:
        tol = dict(rtol=5e-2, atol=5e-1)
    else:
        tol = dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(a_new, np.float32), np.asarray(a_ref, np.float32), **tol
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), **tol)


@given(
    batch=st.integers(1, 4),
    m=st.integers(4, 60),
    nt=st.integers(2, 16),
    b=st.integers(1, 8),
    dt=st.sampled_from(DTYPES),
    seed=st.integers(0, 2**16),
)
@SET
def test_batch_dims_match_stacked_singles(batch, m, nt, b, dt, seed):
    """The ops wrapper's vmap over leading batch dims (the SimComm (P,)
    rank axis) equals per-slice kernel calls exactly."""
    a = _arr(seed, (batch, m, nt), dt)
    q = _arr(seed + 1, (batch, m, b), dt)
    w = _arr(seed + 2, (batch, b, nt), dt)
    nw = min(3, nt)
    a_new, s = ops.trailing_update(a, q, w, next_width=nw, use_pallas=True)
    for i in range(batch):
        ai, si = trailing_update(a[i], q[i], w[i], next_width=nw)
        assert np.array_equal(
            np.asarray(a_new[i], np.float32), np.asarray(ai, np.float32)
        )
        assert np.array_equal(np.asarray(s[i]), np.asarray(si))
    s0 = ops.panel_cross(a, split=nw, use_pallas=True)
    for i in range(batch):
        assert np.array_equal(
            np.asarray(s0[i]), np.asarray(panel_cross(a[i], split=nw))
        )


@given(
    m=st.integers(1, 300),
    n=st.integers(1, 24),
    split_frac=st.floats(0.01, 1.0),
    block_rows=st.sampled_from([8, 32, 1024]),
    dt=st.sampled_from(DTYPES),
    seed=st.integers(0, 2**16),
)
@SET
def test_panel_cross_close_to_oracle(m, n, split_frac, block_rows, dt, seed):
    split = max(1, round(split_frac * n))
    a = _arr(seed, (m, n), dt)
    s = panel_cross(a, split=split, block_rows=block_rows)
    s_ref = ref.panel_cross(a, split=split)
    assert s.shape == (split, n)
    tol = dict(rtol=5e-2, atol=5e-1) if dt == jnp.bfloat16 else \
        dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), **tol)


# ---------------------------------------------------------------------------
# blocked driver end-to-end over ragged m / n / panel widths
# ---------------------------------------------------------------------------

@given(
    log_p=st.integers(0, 3),
    m_local=st.integers(1, 6),       # × n keeps blocks tall enough
    n=st.integers(2, 20),
    pw=st.integers(1, 20),
    seed=st.integers(0, 2**16),
)
@SET
def test_blocked_qr_matches_dense_qr(log_p, m_local, n, pw, seed):
    from repro.qr import blocked_qr_sim

    p = 1 << log_p
    pw = min(pw, n)
    m_local = max(m_local * n, pw)   # each rank's block at least pw tall
    from repro.core import ref

    blocks = np.asarray(_arr(seed, (p, m_local, n), jnp.float32))
    res = blocked_qr_sim(jnp.asarray(blocks), panel_width=pw)
    rt = ref.qr_r(blocks.reshape(-1, n).astype(np.float64))
    assert np.asarray(res.valid).all()
    scale = max(1.0, np.abs(rt).max())
    assert np.abs(np.asarray(res.r)[0] - rt).max() / scale < 5e-4
