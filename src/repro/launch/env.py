"""Process set-up shared by the command-line entry points.

Two decisions live here so that every entry point makes them alike:

  * :func:`force_host_devices` — SPMD runs on a CPU host simulate their
    mesh with ``--xla_force_host_platform_device_count``.  That flag only
    multiplies the *CPU* backend's devices, so it is set only where the run
    is on the CPU (``JAX_PLATFORMS`` names ``cpu`` first).  On an
    accelerator the run uses the chips, and a mesh wider than the chips is
    an error of the run, not something to simulate.
  * :func:`enable_compile_cache` — JAX's persistent compilation cache.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``.jax_cache`` at the root
    of the checkout, a fixed path, so a later run of the same checkout
    finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import sys

__all__ = [
    "CACHE_DIR",
    "cpu_platform",
    "enable_compile_cache",
    "force_host_devices",
]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)

_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def cpu_platform() -> bool:
    """Is JAX held to the CPU (``JAX_PLATFORMS`` names ``cpu`` first)?
    Read from the environment, so it can be asked before JAX starts."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return first == "cpu"


def force_host_devices(n: int) -> bool:
    """Give the CPU backend ``n`` devices, where the run is on the CPU and
    JAX has not started yet.  Returns whether the flag is in effect."""
    if n <= 0 or not cpu_platform():
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_FLAG in flags:
        return True
    if "jax" in sys.modules:
        print(f"[env] jax already imported; cannot force {n} host devices",
              file=sys.stderr)
        return False
    os.environ["XLA_FLAGS"] = f"{flags} {_DEVICE_FLAG}={n}".strip()
    return True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
