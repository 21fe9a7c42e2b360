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

import faulthandler  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

# Every tier-1 test runs under these two limits and no other; nothing
# sets them from outside.  The soft one is an interval timer on the
# worker's main thread: it fails the test with every thread's stack.
# The hard one stands behind it for a main thread stuck below the
# interpreter (an XLA rendezvous, a lock held in C), where no handler
# runs: it dumps the stacks to the worker's own stderr and ends the
# process; xdist reports the test as its worker's crash and goes on on
# a new one.  A hang costs one failure and one limit, not the run's 1470
# s.  ``slow`` tests are outside tier-1 and run under neither.
SOFT_LIMIT_S = 180.0
HARD_LIMIT_S = SOFT_LIMIT_S + 30.0

_real_stderr = pytest.StashKey[int]()


def pytest_configure(config):
    # Capture is suspended here, so fd 2 is the process's own stderr;
    # while a test runs it is pytest's capture file, which a process
    # ended by the hard limit never gets to show.
    config.stash[_real_stderr] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_real_stderr])


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    if item.get_closest_marker("slow") is not None:
        return (yield)

    def over_the_limit(signum, frame):
        with tempfile.TemporaryFile("w+") as stacks:
            faulthandler.dump_traceback(stacks, all_threads=True)
            stacks.seek(0)
            pytest.fail(
                f"{item.nodeid} is over the limit of {SOFT_LIMIT_S:g} s "
                f"a test (tests/conftest.py). Every thread's stack:\n"
                f"{stacks.read()}", pytrace=False)

    faulthandler.dump_traceback_later(
        HARD_LIMIT_S, exit=True, file=item.config.stash[_real_stderr])
    old = signal.signal(signal.SIGALRM, over_the_limit)
    signal.setitimer(signal.ITIMER_REAL, SOFT_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) == 8, "conftest env did not take effect"
    return devices
