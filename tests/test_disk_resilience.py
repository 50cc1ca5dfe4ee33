"""Disk resilience: per-op disk-ID validation, wiped-disk recovery
without restart, dynamic timeouts
(cmd/xl-storage-disk-id-check.go, erasure-sets.go:200-295,
dynamic-timeouts.go)."""

import io
import os
import shutil

import pytest

from minio_tpu.heal.background import FreshDiskMonitor, HealQueue
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.objectlayer.format import (
    FormatErasure,
    read_format,
    wait_for_format,
    write_format,
)
from minio_tpu.objectlayer.sets import ErasureSets
from minio_tpu.storage import errors as serrors
from minio_tpu.storage.diskcheck import DiskIDCheck
from minio_tpu.storage.xl import XLStorage
from minio_tpu.utils.dyntimeout import LOG_SIZE, DynamicTimeout


def _formatted_disks(root, n=4):
    disks = [XLStorage(str(root / f"d{i}")) for i in range(n)]
    ref, ordered = wait_for_format(disks, 1, n, timeout_s=5)
    return ref, ordered


def _guard(ordered, ref):
    return [
        DiskIDCheck(d, ref.sets[0][i], check_interval_s=0.0)
        for i, d in enumerate(ordered)
    ]


def test_ops_pass_through_when_id_matches(tmp_path):
    ref, ordered = _formatted_disks(tmp_path)
    guarded = _guard(ordered, ref)
    ol = ErasureObjects(guarded, block_size=4096, min_part_size=1)
    ol.make_bucket("bkt")
    ol.put_object("bkt", "k", io.BytesIO(b"data"), 4)
    buf = io.BytesIO()
    ol.get_object("bkt", "k", buf)
    assert buf.getvalue() == b"data"


def test_swapped_disk_rejected(tmp_path):
    """A drive holding a DIFFERENT format uuid fails per-op."""
    ref, ordered = _formatted_disks(tmp_path)
    guarded = _guard(ordered, ref)
    # swap: stamp disk 0 with some other identity
    write_format(
        ordered[0],
        FormatErasure(id=ref.id, this="intruder-uuid", sets=ref.sets),
    )
    with pytest.raises(serrors.DiskNotFound, match="mismatch"):
        guarded[0].read_all(".sys", "format.json")
    assert not guarded[0].is_online()
    # the other disks still work; quorum ops survive
    ol = ErasureObjects(guarded, block_size=4096, min_part_size=1)
    ol.make_bucket("bkt")
    ol.put_object("bkt", "k", io.BytesIO(b"data"), 4)


def test_wiped_disk_fails_ops_until_healed(tmp_path):
    ref, ordered = _formatted_disks(tmp_path)
    guarded = _guard(ordered, ref)
    ol = ErasureObjects(guarded, block_size=4096, min_part_size=1)
    ol.make_bucket("bkt")
    ol.put_object("bkt", "k", io.BytesIO(b"payload!"), 8)
    # wipe drive 1 (replaced with an empty one)
    root = ordered[1].root
    shutil.rmtree(root)
    import os

    os.makedirs(root)
    with pytest.raises(serrors.DiskNotFound):
        guarded[1].read_all(".sys", "format.json")
    # reads still serve from the healthy quorum
    buf = io.BytesIO()
    ol.get_object("bkt", "k", buf)
    assert buf.getvalue() == b"payload!"


def test_fresh_disk_monitor_restores_wiped_disk(tmp_path):
    """Remove+restore a disk dir: the monitor re-stamps identity and
    heals the namespace back - no restart (VERDICT r3 item 7)."""
    ref, ordered = _formatted_disks(tmp_path)
    guarded = _guard(ordered, ref)
    sets = ErasureSets(
        guarded, 1, 4, block_size=4096, format_ref=ref
    )
    eset = sets.sets[0]
    eset.min_part_size = 1
    sets.make_bucket("bkt")
    sets.put_object("bkt", "k", io.BytesIO(b"survive-me"), 10)
    # wipe drive 2
    root = ordered[2].root
    shutil.rmtree(root)
    import os

    os.makedirs(root)
    queue = HealQueue()
    monitor = FreshDiskMonitor(sets, queue, interval_s=9999)
    stamped = monitor.scan_once()
    assert stamped == 1
    # identity restored with the slot's original uuid
    fmt = read_format(ordered[2])
    assert fmt is not None and fmt.this == ref.sets[0][2]
    # heal queue got the namespace sweep; run it
    task = queue.pop(timeout=1)
    while task is not None:
        try:
            if task.object:
                eset.heal_object(task.bucket, task.object)
            else:
                sets.heal_bucket(task.bucket)
        except Exception:  # noqa: BLE001
            pass
        task = queue.pop(timeout=0.2)
    # the wiped disk carries the shard again
    assert ordered[2].stat_file("bkt", "k/xl.meta") is not None
    buf = io.BytesIO()
    sets.get_object("bkt", "k", buf)
    assert buf.getvalue() == b"survive-me"


# -- the liveness question (PR 34) ------------------------------------------
#
# Whether a local drive is there is what its DiskIDCheck last found: the
# look at format.json, once an interval, fails under a root that is gone,
# and between looks ``is_online()`` is a field read.  A failed call that
# blames the drive forgets the last look, so the next question looks again.


class _Clock:
    now = 1000.0

    def monotonic(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    from minio_tpu.storage import diskcheck

    c = _Clock()
    monkeypatch.setattr(diskcheck, "time", c)
    return c


def _liveness():
    from minio_tpu.codec.telemetry import KERNEL_STATS

    return KERNEL_STATS.snapshot()["liveness"]


def _moved(before):
    after = _liveness()
    return [after[k] - before[k] for k in ("asked", "looked", "reset")]


def _server_stack(ordered, ref, interval):
    from minio_tpu.storage import metered

    return [
        DiskIDCheck(metered.wrap(d), ref.sets[0][i], check_interval_s=interval)
        for i, d in enumerate(ordered)
    ]


def _lose_root(raw, tmp_path):
    kept = str(tmp_path / "kept")
    shutil.move(raw.root, kept)
    return kept


def _after_a_failed_call(drive, raw, tmp_path, clock):
    _lose_root(raw, tmp_path)
    before = _liveness()
    assert drive.is_online()  # what the drive last said; no look
    assert _moved(before) == [1, 0, 0]
    with pytest.raises(serrors.VolumeNotFound):
        drive.read_version("bkt", "k")
    assert _moved(before) == [1, 0, 1]
    assert not drive.is_online()  # the same instant: the call said so
    assert _moved(before) == [2, 1, 1]
    with pytest.raises(serrors.DiskNotFound):  # from memory, and no reset
        drive.read_version("bkt", "k")
    assert _moved(before) == [2, 1, 1]


def _after_the_interval(drive, raw, tmp_path, clock):
    _lose_root(raw, tmp_path)
    before = _liveness()
    clock.now += 0.9
    assert drive.is_online()
    clock.now += 0.1
    assert not drive.is_online()
    assert not drive.is_online()
    assert _moved(before) == [3, 1, 0]


def _a_root_that_returns(drive, raw, tmp_path, clock):
    kept = _lose_root(raw, tmp_path)
    clock.now += 1.0
    assert not drive.is_online()
    shutil.move(kept, raw.root)
    before = _liveness()
    assert not drive.is_online()  # what it last said, for the interval
    clock.now += 1.0
    assert drive.is_online()
    assert drive.read_all(".sys", "format.json")
    assert _moved(before) == [2, 1, 0]


def _a_missing_bucket_blames_no_drive(drive, raw, tmp_path, clock):
    before = _liveness()
    with pytest.raises(serrors.VolumeNotFound):
        drive.stat_vol("no-such-bucket")
    with pytest.raises(serrors.FileNotFound):
        drive.read_version("bkt", "no-such-key")
    assert drive.is_online()
    assert _moved(before) == [1, 0, 0]


def _a_swapped_format(drive, raw, tmp_path, clock):
    fmt = read_format(raw)
    write_format(
        raw, FormatErasure(id=fmt.id, this="intruder-uuid", sets=fmt.sets)
    )
    clock.now += 1.0
    assert not drive.is_online()
    with pytest.raises(serrors.DiskNotFound, match="mismatch"):
        drive.read_all(".sys", "format.json")
    write_format(raw, fmt)
    clock.now += 1.0
    assert drive.is_online()


LIVENESS_CASES = {
    "masked-after-one-failed-call": _after_a_failed_call,
    "masked-after-the-interval": _after_the_interval,
    "live-again-after-the-interval": _a_root_that_returns,
    "a-missing-bucket-blames-no-drive": _a_missing_bucket_blames_no_drive,
    "a-swapped-format-is-refused": _a_swapped_format,
}


@pytest.mark.parametrize("case", LIVENESS_CASES)
def test_liveness_is_what_the_drive_last_said(tmp_path, clock, case):
    ref, ordered = _formatted_disks(tmp_path / "drives")
    ordered[0].make_vol("bkt")
    drive = _server_stack(ordered, ref, 1.0)[0]
    before = _liveness()
    assert drive.is_online() and drive.is_online()
    assert _moved(before) == [2, 1, 0]
    LIVENESS_CASES[case](drive, ordered[0], tmp_path, clock)


def test_interval_zero_looks_every_call(tmp_path, clock):
    ref, ordered = _formatted_disks(tmp_path / "drives")
    drive = _server_stack(ordered, ref, 0.0)[0]
    before = _liveness()
    assert drive.is_online() and drive.is_online() and drive.is_online()
    assert _moved(before) == [3, 3, 0]
    _lose_root(ordered[0], tmp_path)
    assert not drive.is_online()
    assert _moved(before) == [4, 4, 0]


def test_a_bare_drive_and_a_remote_one_keep_their_own_answer(tmp_path, clock):
    """A bare XLStorage still stats its root (the heal's fresh-disk monitor
    probes ``raw.is_online()``); a wrapper over a drive that is not local
    asks its client's flag first, as before."""
    ref, ordered = _formatted_disks(tmp_path / "drives")
    raw = ordered[0]

    class Remote:
        online = True

        def is_local(self):
            return False

        def is_online(self):
            return self.online

        def __getattr__(self, name):
            return getattr(raw, name)

    remote = Remote()
    drive = DiskIDCheck(remote, ref.sets[0][0], check_interval_s=1.0)
    assert drive.is_online()
    remote.online = False
    assert not drive.is_online()  # no wait for the interval
    remote.online = True
    assert drive.is_online()
    _lose_root(raw, tmp_path)
    assert not raw.is_online()


@pytest.mark.parametrize("lost", [1, 2])
def test_put_and_get_in_the_second_a_root_goes(tmp_path, clock, lost):
    """Liveness is a hint for masking, never a vote: a drive that vanishes
    inside the interval is met on the error path, the write still counts
    its quorum from what each drive answered and the read decodes from
    the shards that verify."""
    ref, ordered = _formatted_disks(tmp_path / "drives", n=6)
    guarded = _server_stack(ordered, ref, 1.0)
    ol = ErasureObjects(guarded, parity_blocks=2, block_size=4096, min_part_size=1)
    ol.make_bucket("bkt")
    old, new = os.urandom(20000), os.urandom(30000)
    ol.put_object("bkt", "old", io.BytesIO(old), len(old))
    assert all(d is not None for d in ol._online_disks())
    before = _liveness()
    for i in range(lost):
        shutil.rmtree(ordered[2 + i].root)
    # the same instant: every drive still says it is there
    assert all(d.is_online() for d in guarded)
    ol.put_object("bkt", "new", io.BytesIO(new), len(new))
    for key, want in (("new", new), ("old", old)):
        buf = io.BytesIO()
        ol.get_object("bkt", key, buf)
        assert buf.getvalue() == want
    assert ol.get_object_info("bkt", "new").size == len(new)
    online = ol._online_disks()
    assert [d is None for d in online] == [2 <= i < 2 + lost for i in range(6)]
    assert _moved(before)[2] >= lost
    # at write quorum (4 of 6): the new object is on every drive that is left
    for i, raw in enumerate(ordered):
        assert os.path.exists(os.path.join(raw.root, "bkt", "new", "xl.meta")) == (
            not 2 <= i < 2 + lost)


# -- dynamic timeouts -----------------------------------------------------


def test_dynamic_timeout_increases_on_failures():
    dt = DynamicTimeout(10.0, 1.0)
    for _ in range(LOG_SIZE):
        dt.log_failure()
    assert dt.timeout == pytest.approx(12.5)


def test_dynamic_timeout_decreases_toward_average():
    dt = DynamicTimeout(10.0, 1.0)
    for _ in range(LOG_SIZE):
        dt.log_success(0.1)
    # (10 + 0.125) / 2
    assert dt.timeout == pytest.approx(5.0625)
    # never below the minimum
    for _ in range(20 * LOG_SIZE):
        dt.log_success(0.0001)
    assert dt.timeout >= 1.0


def test_dynamic_timeout_stable_in_between():
    dt = DynamicTimeout(10.0, 1.0)
    # 20% failures: between the 10% and 33% thresholds -> unchanged
    for i in range(LOG_SIZE):
        if i % 5 == 0:
            dt.log_failure()
        else:
            dt.log_success(1.0)
    assert dt.timeout == pytest.approx(10.0)


# ---- escalation matrix: bitrot x slow-disk x exhaustion ----------------
#
# These drive codec/erasure.py's hedged quorum loop directly over
# in-memory shards (tests/test_erasure.py doubles) so each cell of the
# matrix is deterministic: latency is injected per reader, bitrot by
# flipping stored bytes, and the hedge deadline is seeded through the
# health registry instead of waiting for organic warmup.


import threading
import time

import numpy as np

from minio_tpu.codec.erasure import Erasure, QuorumError
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.parallel import iopool
from minio_tpu.storage import health as disk_health

from tests.test_erasure import MemShard


class _SlowShard(MemShard):
    """read_at stalls; the straggler the hedge must route around."""

    def __init__(self, delay_s):
        super().__init__()
        self.delay_s = delay_s

    def read_at(self, off, length):
        time.sleep(self.delay_s)
        return super().read_at(off, length)


def _seed_pool_latency(reg, endpoint="warm", seconds=0.0005, n=30):
    """Warm the pool-wide read estimator so hedge_deadline() is live
    (clamped to MINIO_TPU_HEDGE_MIN_MS, 2ms by default)."""
    for _ in range(n):
        reg.record_shard_read(endpoint, seconds, ok=True)


def _encode(er, payload, n):
    shards = [MemShard() for _ in range(n)]
    er.encode(io.BytesIO(payload), list(shards), write_quorum=n - 1)
    return shards


def _rng_payload(size, seed=5):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8
    ).tobytes()


def _warm_decode(er, payload, n):
    """One healthy decode on clean shards: warms the verify kernel
    (first-call JIT would otherwise dwarf the injected delays) and
    feeds the pool read estimator real sub-ms samples."""
    clean = _encode(er, payload, n)
    for i, r in enumerate(clean):
        iopool.tag_io_key(r, f"warm-clean-{i}")
    out = io.BytesIO()
    er.decode(out, list(clean), 0, len(payload), len(payload))
    assert out.getvalue() == payload


def test_bitrot_plus_slow_disk_in_one_round(tmp_path):
    """One round faces BOTH failure modes at once: data shard 0 is
    slow AND corrupt, data shard 1 healthy, parity slower still.  The
    deadline hedges onto parity, the corrupt straggler lands mid-round
    and fails verify, parity completes the quorum — bytes come back
    bit-identical, heal_required fires (bitrot was OBSERVED, the hedge
    win must not mask it), and the hedge telemetry shows a win."""
    disk_health.reset_registry()
    k, m, bs = 2, 2, 2048
    n = k + m
    er = Erasure(k, m, bs)
    payload = _rng_payload(bs)  # single block: one group, one round
    shards = _encode(er, payload, n)
    _warm_decode(er, payload, n)

    # shard 0: corrupt one byte inside the stored frame's data region
    shards[0].buf[40] ^= 0xFF
    slow0 = _SlowShard(0.03)
    slow0.buf = shards[0].buf
    par2, par3 = _SlowShard(0.06), _SlowShard(0.06)
    par2.buf, par3.buf = shards[2].buf, shards[3].buf
    readers = [slow0, shards[1], par2, par3]
    for i, r in enumerate(readers):
        iopool.tag_io_key(r, f"matrix-a-{i}")

    reg = disk_health.registry()
    _seed_pool_latency(reg)
    assert reg.hedge_deadline() is not None
    hedge0 = KERNEL_STATS.snapshot()["hedge"]

    out = io.BytesIO()
    written, heal = er.decode(out, readers, 0, len(payload), len(payload))
    assert written == len(payload)
    assert out.getvalue() == payload
    assert heal, "observed bitrot must set heal even when a hedge won"
    hedge1 = KERNEL_STATS.snapshot()["hedge"]
    assert hedge1["launched"] > hedge0["launched"]
    assert hedge1["won"] > hedge0["won"]
    disk_health.reset_registry()


def test_hedge_win_masking_slow_but_clean_shard_sets_no_heal(tmp_path):
    """The complement: a shard that is merely SLOW (clean bytes) loses
    the hedge race — losing on time is not damage, so heal stays
    unset and the loser is reported as a censored slow sample."""
    disk_health.reset_registry()
    k, m, bs = 2, 2, 2048
    n = k + m
    er = Erasure(k, m, bs)
    payload = _rng_payload(bs, seed=6)
    shards = _encode(er, payload, n)
    _warm_decode(er, payload, n)
    slow0 = _SlowShard(0.25)
    slow0.buf = shards[0].buf
    readers = [slow0, shards[1], shards[2], shards[3]]
    for i, r in enumerate(readers):
        iopool.tag_io_key(r, f"matrix-b-{i}")
    reg = disk_health.registry()
    _seed_pool_latency(reg)

    out = io.BytesIO()
    t0 = time.monotonic()
    written, heal = er.decode(out, readers, 0, len(payload), len(payload))
    wall = time.monotonic() - t0
    assert out.getvalue() == payload
    assert not heal, "a slow-but-clean straggler is not damage"
    assert wall < 0.2, f"hedge should beat the 250ms straggler ({wall:.3f}s)"
    # the straggler's breaker saw the censored sample
    assert reg.get_disk("matrix-b-0").snapshot()["slow_strikes"] >= 1
    disk_health.reset_registry()


def test_escalation_exhaustion_raises_not_hangs(tmp_path):
    """Below read quorum the loop must fail FAST with the canonical
    QuorumError, never wait out deadlines on shards that do not
    exist."""
    disk_health.reset_registry()
    k, m, bs = 2, 2, 2048
    n = k + m
    er = Erasure(k, m, bs)
    payload = _rng_payload(bs, seed=7)
    shards = _encode(er, payload, n)
    # three dead disks: only one live shard < k
    readers = [None, shards[1], None, None]
    t0 = time.monotonic()
    with pytest.raises(QuorumError, match="read quorum lost"):
        er.decode(io.BytesIO(), readers, 0, len(payload), len(payload))
    assert time.monotonic() - t0 < 5.0
    disk_health.reset_registry()


def test_escalation_exhaustion_with_bitrot_everywhere(tmp_path):
    """k-1 intact shards + corrupt everywhere else: escalation reads
    every shard, verify rejects the rot, and the loop terminates in
    QuorumError instead of spinning on an empty preference list."""
    disk_health.reset_registry()
    k, m, bs = 2, 2, 2048
    n = k + m
    er = Erasure(k, m, bs)
    payload = _rng_payload(bs, seed=8)
    shards = _encode(er, payload, n)
    for s in (0, 2, 3):  # corrupt all but one shard
        shards[s].buf[50] ^= 0xFF
    readers = list(shards)
    t0 = time.monotonic()
    with pytest.raises(QuorumError, match="read quorum lost"):
        er.decode(io.BytesIO(), readers, 0, len(payload), len(payload))
    assert time.monotonic() - t0 < 5.0
    disk_health.reset_registry()
