"""Test configuration: force JAX onto a virtual 8-device CPU platform so
sharding/mesh tests run anywhere, and make all randomness deterministic
(reference test strategy: OryxTest.java:38 + RandomManager.useTestSeed)."""

import os
import tempfile

# XLA_FLAGS must be in the env before the CPU backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Tier-1's CPU executables stay OUT of the checkout's compile cache
# (<repo>/.jax_cache, common/compile_cache.py): the chip tool copies the
# whole tree to its machine on every call, and CPU entries never hit
# there.  An exported JAX_COMPILATION_CACHE_DIR outranks the config key,
# here and in every child process a test spawns.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "oryx-tpu-tier1-jax-cache"))

# config.update wins over the environment regardless of import order;
# tests must never touch real hardware.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from oryx_tpu.common.rand import RandomManager  # noqa: E402


@pytest.fixture(autouse=True)
def _test_seed():
    RandomManager.use_test_seed()
    yield
