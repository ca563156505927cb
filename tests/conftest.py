"""Test configuration: the suite runs on a virtual 8-device CPU platform.

Sharding tests run on the virtual 8-device CPU mesh; the program itself
runs on the chip through ``python chip_smoke.py`` (``--chips 4`` for the
multi-chip paths), and tests/test_tpu_compile.py compiles the main-path
kernels for a described v5e chip.  Must run before any backend init.
"""
import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _map_count():
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux
        return 0


def _map_limit():
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 0


_MAP_LIMIT = _map_limit()


@pytest.fixture(autouse=True)
def _bounded_code_mappings():
    """Keep a test process under the kernel's memory-map limit.

    XLA's CPU backend maps code for every compiled kernel and keeps it as
    long as the executable is cached, so a worker that compiles a few
    hundred merge programs approaches ``vm.max_map_count``; past it the
    next compile crashes the process.  Dropping JAX's caches between tests
    unmaps them (later tests recompile what they need)."""
    yield
    if _MAP_LIMIT and _map_count() > _MAP_LIMIT // 2:
        jax.clear_caches()
