"""Test config: run everything on an 8-device virtual CPU mesh.

This is the TPU-native analog of the reference's fake-device / Gloo tricks
(SURVEY.md §4): XLA's host platform is forced to expose 8 devices so all
sharding/collective paths execute for real without TPU hardware.

The driver runs the suite with JAX_PLATFORMS=cpu; the config update below
makes a bare `pytest tests/` do the same.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# float32 means float32 in numeric tests; TPU runs keep the fast MXU default.
jax.config.update("jax_default_matmul_precision", "highest")

# Persist XLA compilations across test runs AND across the workers and
# subprocesses of one run: every process of this repo finds the same
# directory by the one rule in _core/compile_cache.py
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
from paddle_tpu._core import compile_cache  # noqa: E402

compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)


import pytest  # noqa: E402


@pytest.fixture
def reference_source():
    """Reads a file of the reference checkout, or skips the test:
    /root/reference is mounted on some machines only."""
    def read(path):
        fp = f"/root/reference/python/paddle/{path}"
        if not os.path.exists(fp):
            pytest.skip(f"{fp} is not mounted on this machine")
        with open(fp) as f:
            return f.read()

    return read
