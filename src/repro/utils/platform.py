"""The one answer to "which backend is this process on?".

Kernel wrappers pick Pallas interpret mode from it (interpret everywhere
but a TPU), the dispatcher picks its TPU-only kernels from it, and
``chip_smoke.py`` asserts it says ``tpu`` before anything compiles.
"""

from __future__ import annotations

import os

import jax

# The fixed in-checkout home of JAX's persistent compile cache when the
# environment names none (listed in .gitignore).
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_compilation_cache")


def platform() -> str:
    """Platform of the default device: 'tpu', 'cpu', 'gpu', ..."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return
    it: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, so
    nothing is set here), else :data:`DEFAULT_COMPILE_CACHE`.  Call
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
