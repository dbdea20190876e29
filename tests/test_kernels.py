"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes
(interpret=True executes the Pallas kernel body on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(64, 4), (257, 7), (1024, 128), (500, 130), (2048, 64)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    # blocked accumulation reorders sums vs the single-matmul oracle
    if dt == jnp.bfloat16:
        return dict(rtol=3e-2, atol=3e-2)
    return dict(rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_gram_matches_ref(rng, m, n, dt):
    a = jnp.asarray(rng.standard_normal((m, n)), dtype=dt)
    got = ops.gram(a, use_pallas=True)
    want = ref.gram(a)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dt))


@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_apply_right_matches_ref(rng, m, n, dt):
    a = jnp.asarray(rng.standard_normal((m, n)), dtype=dt)
    w = jnp.asarray(rng.standard_normal((n, n)), dtype=dt)
    got = ops.apply_right(a, w, use_pallas=True)
    want = ref.apply_right(a, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dt)
    )


@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_fused_apply_gram_matches_ref(rng, m, n, dt):
    a = jnp.asarray(rng.standard_normal((m, n)), dtype=dt)
    w = jnp.asarray(rng.standard_normal((n, n)), dtype=dt)
    q, g = ops.fused_apply_gram(a, w, use_pallas=True)
    q_ref, g_ref = ref.fused_apply_gram(a, w)
    assert q.dtype == a.dtype and g.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(q, np.float32), np.asarray(q_ref, np.float32), **_tol(dt)
    )
    # blocked Gram accumulation reorders sums and bf16 squares grow large
    gt = dict(rtol=5e-2, atol=5e-1) if dt == jnp.bfloat16 else _tol(dt)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), **gt)
    # want_q=False consumes the panel in VMEM; the Gram must be identical
    g_only = ops.fused_apply_gram(a, w, use_pallas=True, want_q=False)
    assert np.array_equal(np.asarray(g_only), np.asarray(g))


def test_fused_apply_gram_bit_matches_unfused_kernels(rng):
    """The fused sweep takes the Gram of the *cast* panel with the same
    panel boundaries, so it must reproduce gram(apply_right(A, W)) exactly."""
    a = jnp.asarray(rng.standard_normal((1500, 40)), dtype=jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((40, 40)), dtype=jnp.bfloat16)
    q, g = ops.fused_apply_gram(a, w, use_pallas=True)
    q_u = ops.apply_right(a, w, use_pallas=True)
    g_u = ops.gram(q_u, use_pallas=True)
    assert np.array_equal(np.asarray(q, np.float32), np.asarray(q_u, np.float32))
    assert np.array_equal(np.asarray(g), np.asarray(g_u))


@pytest.mark.parametrize("n", [3, 16, 129, 256])
def test_combine_gram_matches_ref(rng, n):
    r1 = jnp.asarray(np.triu(rng.standard_normal((n, n))), dtype=jnp.float32)
    r2 = jnp.asarray(np.triu(rng.standard_normal((n, n))), dtype=jnp.float32)
    got = ops.combine_gram(r1, r2, use_pallas=True)
    want = ref.combine_gram(r1, r2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


@pytest.mark.parametrize("m,n", [(256, 16), (1000, 32), (4096, 64)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_cholesky_qr2_orthogonality_and_reconstruction(rng, m, n, use_pallas):
    a = jnp.asarray(rng.standard_normal((m, n)), dtype=jnp.float32)
    q, r = ops.cholesky_qr2(a, use_pallas=use_pallas)
    np.testing.assert_allclose(
        np.asarray(q.T @ q), np.eye(n), atol=2e-5
    )
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), rtol=1e-4, atol=1e-4)
    # R matches Householder ground truth (unique with positive diagonal)
    rt = np.linalg.qr(np.asarray(a, np.float64), mode="r")
    rt = rt * np.where(np.diagonal(rt) < 0, -1.0, 1.0)[:, None]
    np.testing.assert_allclose(np.asarray(r), rt, rtol=2e-3, atol=2e-3)


def test_cholesky_qr2_batched(rng):
    a = jnp.asarray(rng.standard_normal((5, 256, 16)), dtype=jnp.float32)
    q, r = ops.cholesky_qr2(a, use_pallas=True)
    assert q.shape == (5, 256, 16) and r.shape == (5, 16, 16)
    eye = np.broadcast_to(np.eye(16), (5, 16, 16))
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("bmi,bmj->bij", q, q)), eye, atol=2e-5
    )


def test_gram_block_rows_invariance(rng):
    """Result must not depend on the streaming block size."""
    a = jnp.asarray(rng.standard_normal((777, 50)), dtype=jnp.float32)
    outs = [
        np.asarray(ops.gram(a, use_pallas=True))
    ]
    from repro.kernels.gram import gram as raw_gram

    for br in (128, 256, 1024):
        outs.append(np.asarray(raw_gram(a, block_rows=br)))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=2e-3)  # accumulation order


def test_tri_inv(rng):
    r = jnp.asarray(
        np.triu(rng.standard_normal((24, 24))) + 8 * np.eye(24), jnp.float32
    )
    inv = ops.tri_inv(r)
    np.testing.assert_allclose(np.asarray(r @ inv), np.eye(24), atol=1e-5)


def test_tri_inv_batched_no_broadcast_identity(rng):
    """Batched factors solve against the single unbatched eye (vmapped)."""
    r = jnp.asarray(
        np.triu(rng.standard_normal((3, 2, 24, 24))) + 8 * np.eye(24),
        jnp.float32,
    )
    inv = ops.tri_inv(r)
    assert inv.shape == r.shape
    np.testing.assert_allclose(
        np.asarray(r @ inv),
        np.broadcast_to(np.eye(24), r.shape),
        atol=1e-5,
    )


# ---------------------------------------------------------------------------
# fused pipeline: sweep counts and R-only equivalence
# ---------------------------------------------------------------------------

def test_cholesky_qr2_r_matches_full_and_unfused(rng):
    a = jnp.asarray(rng.standard_normal((1000, 32)), dtype=jnp.float32)
    for pallas in (False, True):
        r_only = ops.cholesky_qr2_r(a, use_pallas=pallas)
        _, r_full = ops.cholesky_qr2(a, use_pallas=pallas)
        _, r_unfused = ops.cholesky_qr2(a, use_pallas=pallas, fused=False)
        assert np.array_equal(np.asarray(r_only), np.asarray(r_full)), pallas
        assert np.array_equal(np.asarray(r_only), np.asarray(r_unfused)), pallas


def test_traffic_model_sweep_counts(rng):
    from repro.kernels import traffic

    a = jnp.asarray(rng.standard_normal((2048, 32)), dtype=jnp.float32)
    with traffic.track_traffic() as t_fused:
        ops.cholesky_qr2_r(a, use_pallas=True)
    with traffic.track_traffic() as t_unfused:
        ops.cholesky_qr2(a, use_pallas=True, fused=False)
    assert t_fused.tall_sweeps == 2
    assert t_unfused.tall_sweeps == 4
    panel = 2048 * 32 * 4
    assert t_fused.read_bytes == 2 * panel + 32 * 32 * 4   # A twice + W once
    assert t_unfused.read_bytes > 4 * panel                # A, A, Q1, Q1 (+Ws)
    # R-only never writes a tall intermediate: only the two (n, n) Grams
    assert t_fused.write_bytes == 2 * 32 * 32 * 4
    assert t_unfused.write_bytes == 2 * panel + 2 * 32 * 32 * 4
    # nothing records outside a tracking block
    ops.gram(a, use_pallas=True)
    assert t_fused.tall_sweeps == 2


# ---------------------------------------------------------------------------
# backend auto-detection: the resolved flag must reach pallas_call
# ---------------------------------------------------------------------------

def test_interpret_flag_reaches_pallas_call(rng, monkeypatch):
    from jax.experimental import pallas as pl

    from repro.kernels import apply_right as apply_mod
    from repro.kernels import backend, fused_apply_gram as fused_mod
    from repro.kernels import gram as gram_mod

    captured = []
    real = pl.pallas_call

    def spy(*args, **kw):
        captured.append(kw.get("interpret"))
        kw["interpret"] = True          # CPU cannot compile Mosaic
        return real(*args, **kw)

    for mod in (gram_mod, apply_mod, fused_mod):
        monkeypatch.setattr(mod.pl, "pallas_call", spy, raising=True)

    # unique shapes so jit can't replay a cached trace from earlier tests
    a = jnp.asarray(rng.standard_normal((333, 11)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((11, 11)), dtype=jnp.float32)

    ops.gram(a, use_pallas=True, interpret=False)
    assert captured[-1] is False        # explicit override wins
    ops.apply_right(a, w, use_pallas=True, interpret=True)
    assert captured[-1] is True
    ops.fused_apply_gram(a, w, use_pallas=True)          # auto-detect
    assert captured[-1] is backend.default_interpret()
    assert backend.default_interpret() is True           # CPU container


@pytest.mark.parametrize("panel_width", [None, 8])
def test_qrconfig_interpret_reaches_cqr2_local_qr(rng, monkeypatch,
                                                   panel_width):
    """``QRConfig.interpret`` reaches every ``pl.pallas_call`` of the
    ``cqr2_pallas`` local QR, in the TSQR and the blocked driver alike."""
    from jax.experimental import pallas as pl

    from repro.kernels import fused_apply_gram as fused_mod
    from repro.kernels import gram as gram_mod
    from repro.qr import QRConfig, factorize

    captured = []
    real = pl.pallas_call

    def spy(*args, **kw):
        captured.append(kw.get("interpret"))
        kw["interpret"] = True          # CPU cannot compile Mosaic
        return real(*args, **kw)

    for mod in (gram_mod, fused_mod):
        monkeypatch.setattr(mod.pl, "pallas_call", spy, raising=True)

    # unique shapes so jit can't replay a cached trace from earlier tests
    a = jnp.asarray(rng.standard_normal((4, 37, 13)), dtype=jnp.float32)
    cfg = QRConfig(panel_width=panel_width, local_r="cqr2_pallas",
                   interpret=False, pipeline="off")
    factorize(a, cfg)
    assert captured and all(c is False for c in captured), captured


# ---------------------------------------------------------------------------
# GPU (Triton) lowerings: per-program partial accumulators vs the TPU
# kernels' revisited-block accumulators — same math, parallel-grid-safe
# ---------------------------------------------------------------------------

def test_gpu_lowerings_match_tpu_kernels(rng):
    from repro.kernels import gpu
    from repro.kernels import gram as gram_mod
    from repro.kernels import apply_right as apply_mod
    from repro.kernels import fused_apply_gram as fused_mod
    from repro.kernels import trailing_update as trail_mod

    tol = dict(rtol=1e-5, atol=1e-5)
    m, n, b = 333, 11, 8
    a = jnp.asarray(rng.standard_normal((m, n)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((n, n)) / n, dtype=jnp.float32)
    q = jnp.asarray(rng.standard_normal((m, b)), dtype=jnp.float32)
    wt = jnp.asarray(rng.standard_normal((b, n)) / n, dtype=jnp.float32)

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)

    close(gpu.gram(a), gram_mod.gram(a, interpret=True))
    close(gpu.apply_right(a, w), apply_mod.apply_right(a, w, interpret=True))

    q_g, s_g = gpu.fused_apply_gram(a, w)
    q_t, s_t = fused_mod.fused_apply_gram(a, w, interpret=True)
    close(q_g, q_t)
    close(s_g, s_t)
    close(
        gpu.fused_apply_gram(a, w, want_q=False),
        fused_mod.fused_apply_gram(a, w, interpret=True, want_q=False),
    )

    an_g, s2_g = gpu.trailing_update(a, q, wt, next_width=b)
    an_t, s2_t = trail_mod.trailing_update(
        a, q, wt, next_width=b, interpret=True
    )
    close(an_g, an_t)
    close(s2_g, s2_t)
    close(
        gpu.trailing_update(a, q, wt),
        trail_mod.trailing_update(a, q, wt, interpret=True),
    )

    close(
        gpu.panel_cross(a, split=4),
        trail_mod.panel_cross(a, split=4, interpret=True),
    )
    ap_g, sp_g = gpu.pad_cross(a, split=4, out_width=16)
    ap_t, sp_t = trail_mod.pad_cross(a, split=4, out_width=16,
                                     interpret=True)
    close(ap_g, ap_t)
    close(sp_g, sp_t)
    # the padded columns are exact zeros on both lowerings
    assert not np.asarray(ap_g)[:, n:].any()
    assert not np.asarray(sp_g)[:, n:].any()


def test_gpu_routing_reaches_compiled_pallas_call(rng, monkeypatch):
    """On a (mocked) GPU runtime the jitted kernel wrappers must route to
    the Triton lowerings in repro.kernels.gpu with interpret=False — the
    compiled path — while CPU CI swaps the interpreter in underneath."""
    import jax

    from repro.kernels import gpu
    from repro.kernels import trailing_update as trail_mod

    captured = []
    real = gpu.pl.pallas_call

    def spy(*args, **kw):
        captured.append(kw.get("interpret"))
        kw["interpret"] = True          # CPU cannot compile Triton
        return real(*args, **kw)

    monkeypatch.setattr(gpu.pl, "pallas_call", spy, raising=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")

    # unique shapes so jit can't replay a cached trace from earlier tests
    m, n, b = 451, 9, 4
    a = jnp.asarray(rng.standard_normal((m, n)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((n, n)) / n, dtype=jnp.float32)
    q = jnp.asarray(rng.standard_normal((m, b)), dtype=jnp.float32)
    wt = jnp.asarray(rng.standard_normal((b, n)) / n, dtype=jnp.float32)

    got = ops.gram(a, use_pallas=True)
    assert captured and captured[-1] is False
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(a).T @ np.asarray(a),
        rtol=1e-4, atol=1e-4,
    )
    n_calls = len(captured)
    out = trail_mod.trailing_update(a, q, wt, next_width=b)
    assert len(captured) > n_calls and captured[-1] is False
    np.testing.assert_allclose(
        np.asarray(out[0]),
        np.asarray(a) - np.asarray(q) @ np.asarray(wt),
        rtol=1e-4, atol=1e-4,
    )
