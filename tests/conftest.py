"""Test harness: force JAX onto an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; the sharding/collective paths are
validated on ``--xla_force_host_platform_device_count=8`` the way the
reference validates cluster behavior on a kind cluster (SURVEY §4).
"""

import os

# force CPU even when the host has a TPU — tests must not occupy the chip,
# and the sharding paths need 8 devices. Both are read when jax first
# initialises its backend, so setting them before any test imports jax is
# the whole recipe.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def event_loop_policy():
    return asyncio.DefaultEventLoopPolicy()


def run(coro):
    """Run a coroutine to completion on a fresh loop (test helper)."""
    return asyncio.run(coro)
