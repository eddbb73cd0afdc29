"""Test harness configuration.

The reference's distributed tests shrink world size onto one node
(SURVEY.md §4); ours go further and run every DP/TP/PP/SyncBN test with no
accelerator at all, on 8 virtual CPU devices. This must happen before the
first JAX backend initialization:

- ``XLA_FLAGS --xla_force_host_platform_device_count=8`` gives 8 CPU devices;
- ``jax.config.update("jax_platforms", "cpu")`` holds JAX to the CPU
  even where the machine has a TPU.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_sim():
    assert jax.default_backend() == "cpu"
    assert jax.device_count() == 8, "tests expect 8 simulated devices"
