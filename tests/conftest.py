"""Test harness configuration.

Tests run against the CPU backend with a virtual 8-device mesh so that all
sharding / multi-chip codepaths (the analogue of the reference's in-process
multi-disk test layouts, test-utils_test.go:185-202) are exercised without
TPU hardware.  Must run before jax initializes.
"""

import os

# Hold JAX to the virtual 8-device CPU platform, through the environment
# and through the config API (the latter still works when something
# imported jax first, as long as no backend has been *initialized* yet).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The suite exercises the DEVICE backend over XLA:CPU.  ``auto`` would
# resolve to the host codec here (no accelerator), so ask for it by
# name; get_backend allows that because the platform is pinned above.
os.environ.setdefault("MINIO_ERASURE_BACKEND", "tpu")

import jax

jax.config.update("jax_platforms", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-process integration tests"
    )
    # the digest-only encode kernel donates its input; the CPU test
    # platform cannot always honor donation and says so per call
    # (pytest's capture reinstalls filters, bypassing the module-level
    # filter in ops/codec_step.py)
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable",
    )


# -- thread/FD leak detector (leak-detect_test.go:30-90) -----------------

import threading as _threading

import pytest as _pytest


def _open_fd_count() -> int:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return 0


# process-lifetime singletons that start lazily on first use and are
# shared across every server in the process (NOT per-test leaks);
# "iopool" is the global per-disk I/O fan-out plane (parallel/iopool.py)
_LEAK_ALLOW_PREFIXES = ("codec-batcher", "jax", "grpc", "iopool")


@_pytest.fixture()
def leakcheck():
    """Snapshot live threads + open fds before the test; after it,
    poll for convergence back to the baseline (threads need a grace
    period to drain) and fail on leftovers.  Server-spawning tests
    opt in by listing this fixture FIRST so its teardown runs last,
    after the server shutdown."""
    import time as _time

    before = set(_threading.enumerate())
    fds_before = _open_fd_count()
    yield
    deadline = _time.monotonic() + 10.0
    leaked: list = []
    fd_growth = 0
    while _time.monotonic() < deadline:
        leaked = [
            t
            for t in _threading.enumerate()
            if t not in before
            and t.is_alive()
            and not t.name.startswith(_LEAK_ALLOW_PREFIXES)
        ]
        # small tolerance: lazy singletons (logging handles, jax
        # runtime fds) may open on first use inside the test
        fd_growth = _open_fd_count() - fds_before
        if not leaked and fd_growth <= 4:
            return
        _time.sleep(0.1)
    raise AssertionError(
        "leak detected after test: "
        f"threads={[t.name for t in leaked]} fd_growth={fd_growth}"
    )
