"""Test harness: a virtual 8-device CPU mesh.

The reference's verification strategy is "multi-node without a cluster" —
everything runs on one host with 4 GPUs via ``mp.spawn`` / single-host
``torchrun`` (SURVEY.md section 4). The JAX-native analog: force 8 fake CPU
devices with ``--xla_force_host_platform_device_count`` so every sharding and
collective path compiles and executes without TPU hardware. Must run before
jax initializes its backends, hence the env mutation at import time.
"""

import os

# Force CPU regardless of any ambient JAX_PLATFORMS (the build env pins a TPU
# backend there); the test suite's whole point is hardware-free sharding.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# A plugin that imported jax before this file would have let the config
# capture the ambient JAX_PLATFORMS before the env mutation above; override
# it through the config API too (safe: backends aren't initialized yet).
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {len(devs)}"
    return devs
