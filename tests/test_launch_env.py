"""Process set-up of the entry points: host devices forced on the CPU only,
the compile cache placed from outside or at one fixed checkout path, and
too few chips failing a bench case where too few CPU devices skip it."""
import os
import sys

import jax
import pytest

from repro.launch import env

_FLAG = "--xla_force_host_platform_device_count"


@pytest.mark.parametrize("platforms,cpu", [
    ("cpu", True), ("cpu,tpu", True), ("tpu", False), ("", False),
])
def test_host_devices_forced_only_on_cpu(monkeypatch, platforms, cpu):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    monkeypatch.delitem(sys.modules, "jax")      # as before jax starts
    assert env.cpu_platform() is cpu
    assert env.force_host_devices(8) is cpu
    want = f"--xla_foo=1 {_FLAG}=8" if cpu else "--xla_foo=1"
    assert os.environ["XLA_FLAGS"] == want


def test_force_host_devices_zero_leaves_flags(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    assert not env.force_host_devices(0)
    assert os.environ["XLA_FLAGS"] == ""


def test_compile_cache_from_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert env.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = env.enable_compile_cache()
        assert path == env.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(path)
    assert os.path.basename(path) == ".jax_cache"
    assert os.path.isfile(os.path.join(root, "chip_smoke.py"))


def test_require_devices_skips_on_cpu_fails_on_chip(monkeypatch):
    from repro.bench.registry import BenchFailure, SkipCase, require_devices

    require_devices(1)
    with pytest.raises(SkipCase, match="devices"):
        require_devices(jax.device_count() + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(BenchFailure, match="devices"):
        require_devices(jax.device_count() + 1)


def test_roofline_peaks_keyed_by_device_kind():
    from repro.bench.cases import roofline

    assert roofline.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    # on the CPU the model prices the chip the dry-run meshes describe
    assert roofline.peaks() == roofline.PEAKS[roofline.TARGET_KIND]
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v99")
