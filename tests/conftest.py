"""Test harness: simulate an 8-device TPU mesh on CPU.

The analogue of the reference fixture pattern ``ray.init(num_cpus=N)`` +
Gloo backend for CPU integration tests (``tests/test_ddp.py:20-39``,
SURVEY §4): we force the JAX host platform and split it into 8 virtual
devices so every mesh/sharding/collective path runs in CI without TPU
hardware.  Must run before the first ``import jax`` anywhere in the test
process — conftest import time is the earliest reliable hook.

Worker actors spawned by the LocalBackend inherit this environment, so
they also see 8 CPU devices.
"""

import os

# Tests run on the virtual CPU mesh.  Set RLT_REAL_TPU=1 to opt in to
# real-hardware tests (the analogue of the reference's CLUSTER=1 gate,
# test_ddp_gpu.py:125-136).  The environment alone decides — nothing
# imports jax before this file — and spawned worker actors inherit it.
if not os.environ.get("RLT_REAL_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The persistent compilation cache (utils/compile_cache.py: on by
# default, at a fixed path in the checkout) stays OFF for test runs:
# several xdist workers and their actor children would all write CPU
# executables to one directory, and what tests/test_chip_compile.py
# compiles for a described chip could never be read back.  The tests of
# the cache contract turn it on for the process they start.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) == 8, "conftest env did not take effect"
    return devices
