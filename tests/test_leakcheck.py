"""Thread/FD leak discipline (the leak-detect_test.go:30-90 analogue).

The ``leakcheck`` fixture (conftest.py) snapshots live threads and
open fds around a test and fails when a server-spawning test leaves
either behind.  These tests prove both directions: a full server
lifecycle converges, and a deliberate leak trips the detector.
"""

import threading
import time

import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.server.http import S3Server
from minio_tpu.storage.xl import XLStorage

from s3client import S3Client


def test_server_lifecycle_leaks_nothing(leakcheck, tmp_path):
    """Start a full server, run traffic (worker threads, notifier,
    admission), shut down: every thread and fd must be released."""
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=4096, min_part_size=1)
    srv = S3Server(ol, address="127.0.0.1:0").start()
    try:
        c = S3Client(srv.endpoint)
        assert c.make_bucket("leakb").status == 200
        for i in range(3):
            assert c.put_object(
                "leakb", f"o{i}", b"x" * 5000
            ).status == 200
            assert c.get_object("leakb", f"o{i}").status == 200
    finally:
        srv.shutdown()


def test_resource_balances_converge_to_zero(leakcheck, tmp_path):
    """Runtime cross-check of the MTPU6xx static proof: after PUT/GET
    traffic plus a forced admission shed, every statically-proved
    balance is empirically zero — admission tokens (tenant and
    select), the plane inflight gauge, and the codec's device-byte
    parity-plane account."""
    from minio_tpu.cache.allocator import device_budget
    from minio_tpu.codec.backend import reset_backend
    from minio_tpu.server.admission import TokenCounter

    reset_backend()  # the account is the process's: start from its zero
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=4096, min_part_size=1)
    srv = S3Server(ol, address="127.0.0.1:0").start()
    try:
        c = S3Client(srv.endpoint)
        assert c.make_bucket("balb").status == 200
        for i in range(3):
            assert c.put_object(
                "balb", f"o{i}", b"y" * 9000
            ).status == 200
            assert c.get_object("balb", f"o{i}").status == 200
        # forced shed: the probe token the refused path takes must be
        # undone (the MTPU601 admission canary drops exactly that undo)
        ctr = TokenCounter()
        assert ctr.try_acquire(1) is True
        assert ctr.try_acquire(1) is False
        ctr.release()
        assert ctr.value() == 0
        assert len(ctr._res) == 0
        # the final release races the response write (route()'s
        # finally runs after the client sees the bytes): poll briefly
        adm = srv.admission
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and (
            adm.tenant_inflight() or srv.plane_stats.inflight
        ):
            time.sleep(0.01)
        assert adm.tenant_inflight() == {}
        assert adm.select_inflight() == 0
        assert srv.plane_stats.inflight == 0
    finally:
        srv.shutdown()
    assert device_budget().usage("parity_plane") == 0


def test_detector_catches_a_deliberate_leak():
    """The fixture machinery itself must trip on a leaked thread."""
    before = set(threading.enumerate())
    stop = threading.Event()
    t = threading.Thread(
        target=stop.wait, name="deliberate-leak", daemon=True
    )
    t.start()
    try:
        deadline = time.monotonic() + 1.0
        leaked = []
        while time.monotonic() < deadline:
            leaked = [
                x
                for x in threading.enumerate()
                if x not in before and x.is_alive()
            ]
            if not leaked:
                break
            time.sleep(0.1)
        assert leaked and leaked[0].name == "deliberate-leak"
    finally:
        stop.set()
        t.join(timeout=5)


def test_leakcheck_fixture_is_available(leakcheck):
    """Opt-in marker: the fixture resolves and tolerates a clean test."""


def test_lockorder_auditor_leaves_no_residue(leakcheck):
    """The lock-order auditor (minio_tpu.analysis.lockorder) patches
    module globals, class methods and blocking builtins on install;
    uninstall must restore every one of them and leave no threads
    behind — otherwise a single analysis run would contaminate the
    rest of the suite."""
    import threading as real_threading

    from minio_tpu.analysis.lockorder import (
        LockOrderAuditor,
        run_builtin_scenario,
    )
    from minio_tpu.dsync import local_locker, namespace

    real_sleep = time.sleep
    rw_methods = {
        name: getattr(namespace._RWLock, name)
        for name in (
            "acquire_read",
            "acquire_write",
            "release_read",
            "release_write",
        )
    }

    aud = LockOrderAuditor()
    with aud.installed():
        assert namespace.threading is not real_threading
        assert time.sleep is not real_sleep
        assert (
            namespace._RWLock.acquire_read
            is not rw_methods["acquire_read"]
        )
        # exercise the patched plane so restoration isn't vacuous
        ns = namespace.NamespaceLock()
        with ns.write("leakb", "obj", timeout=5):
            pass

    assert namespace.threading is real_threading
    assert local_locker.threading is real_threading
    assert time.sleep is real_sleep
    for name, original in rw_methods.items():
        assert getattr(namespace._RWLock, name) is original

    # the built-in CLI scenario spins 8 worker threads: all must be
    # joined and every patch restored by the time it returns (the
    # leakcheck fixture then verifies thread/fd convergence globally)
    assert run_builtin_scenario() == []
    assert time.sleep is real_sleep
    assert namespace.threading is real_threading
