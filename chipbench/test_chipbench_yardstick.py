"""The yardstick: the work of a QR from its shape, the chip's peaks, and
the plain reference."""
import numpy as np
import pytest

from chipbench import peaks, reference, rehearsal, spec, work

ROOT = rehearsal.ROOT

V5E = "TPU v5 lite"


@pytest.mark.parametrize("m,n,chips,want_us,bound", [
    (1 << 20, 50, 1, 256.07, "memory"),   # tsqr_500Mx50.sim: 200 MiB read at 819 GB/s
    (1 << 20, 50, 4, 64.03, "memory"),    # tsqr_500Mx50.mesh4: a quarter on each chip
    (1 << 20, 100, 1, 512.17, "memory"),  # blocked_150Mx100: 400 MiB; compute 106 us
    (1 << 20, 32, 1, 164, "memory"),      # 128 MiB read at 819 GB/s
    (1 << 20, 32, 4, 41, "memory"),       # a quarter on each chip
    (4096, 512, 1, 11.5, "memory"),       # 8 MiB + 1 MiB, by a hair
])
def test_least_time_of_the_cells(m, n, chips, want_us, bound):
    lt = work.least_time(m, n, 4, chips, V5E)
    assert lt.seconds * 1e6 == pytest.approx(want_us, rel=0.01)
    assert lt.bound == bound


def test_blocked_compute_time_is_just_under_its_memory_time():
    lt = work.least_time(4096, 512, 4, 1, V5E)
    assert lt.compute_s * 1e6 == pytest.approx(10.45, rel=0.01)
    assert lt.compute_s < lt.memory_s


def test_qr_flops_and_bytes():
    assert work.qr_flops(4096, 512) == 2 * 4096 * 512 ** 2 - 2 * 512 ** 3 / 3
    assert work.qr_bytes(1 << 20, 32, 4, chips=4) == ((1 << 20) * 32 / 4 + 32 * 32) * 4


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        work.least_time(1024, 32, 4, 1, "cpu")


def test_v5e_peaks_have_a_source():
    pk = peaks.peaks(V5E)
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]


def test_reference_r_matches_a_triangular_factor():
    a = np.random.default_rng(0).standard_normal((256, 16))
    r = reference.reference_r(a)
    assert np.allclose(np.tril(r, -1), 0)
    assert (np.diagonal(r) >= 0).all()
    assert np.allclose(r.T @ r, a.T @ a)
    # R is unique up to the signs of its rows
    assert reference.rel_err(-r, r) == 0.0


def test_rel_err_is_normwise():
    r = np.diag([1000.0, 1.0])
    off = r.copy()
    off[1, 1] += 0.5
    assert reference.rel_err(off, r) == pytest.approx(0.5 / 1000)


@pytest.mark.parametrize("deaths,want", [
    ({}, [1, 1, 1, 1]),
    ({1: 1}, [1, 0, 1, 0]),   # rank 1 dies at exchange 1; its partner 3 loses R
    ({2: 1}, [0, 1, 0, 1]),
    ({0: 0}, [0, 0, 0, 0]),   # a death at exchange 0 spreads to every rank
    ({3: 1, 2: 1}, [0, 0, 0, 0]),
])
def test_expected_valid_of_the_redundant_butterfly(deaths, want):
    promise = spec.promise("redundant", ROOT)
    assert promise.valid(4, deaths).tolist() == [bool(w) for w in want]
