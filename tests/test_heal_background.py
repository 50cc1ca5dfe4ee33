"""Background heal machinery: MRF queue, heal routine, fresh-disk
monitor, and verify-healing-style multi-process convergence.
"""

import io
import os
import shutil
import time

import numpy as np
import pytest

from minio_tpu.heal import background as heal_bg
from minio_tpu.heal.background import (
    FreshDiskMonitor,
    HealQueue,
    HealRoutine,
    HealTask,
)
from minio_tpu.objectlayer import format as fmt
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.objectlayer.sets import ErasureSets
from minio_tpu.objectlayer.zones import ErasureZones
from minio_tpu.storage import errors as serrors
from minio_tpu.storage.xl import XLStorage

BLOCK = 4096


def _pay(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8
    ).tobytes()


# -- queue -----------------------------------------------------------------


def test_heal_queue_dedup_and_order():
    q = HealQueue()
    q.push(HealTask("b", "o1"))
    q.push(HealTask("b", "o2"))
    q.push(HealTask("b", "o1"))  # dup dropped
    assert len(q) == 2
    assert q.pop() == HealTask("b", "o1")
    assert q.pop() == HealTask("b", "o2")
    assert q.pop(timeout=0.05) is None
    # re-push after pop is allowed (no longer pending)
    q.push(HealTask("b", "o1"))
    assert len(q) == 1


class _Drive:
    """A drive a set-aside task waits for: answers as told, counts asks."""

    def __init__(self, online: bool = False):
        self.online = online
        self.asked = 0

    def is_online(self) -> bool:
        self.asked += 1
        return self.online


def _parked_since(before: dict) -> dict:
    return {k: v - before[k] for k, v in heal_bg.PARKED.items()}


def test_heal_queue_dedups_across_the_fifo_and_the_set_aside():
    q = HealQueue()
    gone = _Drive()
    before = dict(heal_bg.PARKED)
    q.push(HealTask("b", "o1"), waits=(gone,))  # could write nothing: aside
    q.push(HealTask("b", "o1"), waits=(gone,))  # absorbed
    q.push(HealTask("b", "o2"))  # can write: queued
    q.push(HealTask("b", "o2"), waits=(gone,))  # already queued: dropped
    assert len(q) == 2 and q.parked_depth() == 1
    assert q.pop(timeout=0.05) == HealTask("b", "o2")
    q.task_done()
    assert q.pop(timeout=0.05) is None  # o1 waits for its drive
    assert q.idle()
    # a push that can write takes the object out of the set-aside, once
    q.push(HealTask("b", "o1"))
    q.push(HealTask("b", "o1"), waits=(gone,))
    assert q.parked_depth() == 0 and len(q) == 1
    assert q.pop(timeout=0.05) == HealTask("b", "o1")
    q.task_done()
    assert _parked_since(before) == {"set_aside": 1, "absorbed": 1, "requeued": 0}


def test_heal_queue_requeues_what_waits_on_a_drive_that_answers():
    q = HealQueue()
    d1, d2 = _Drive(), _Drive()
    before = dict(heal_bg.PARKED)
    q.push(HealTask("b", "o1"), waits=(d1,))
    q.push(HealTask("b", "o2"), waits=(d1, d2))
    q.push(HealTask("b", "o3"), waits=(d2,))
    q.push(HealTask("b", "o3"), waits=(d1,))  # absorbed, and waits on d1 too
    assert q.requeue_answered() == 0
    assert (d1.asked, d2.asked) == (1, 1)  # once a drive, not once a task
    d1.online = True
    assert not q.idle()  # d1 answers: its tasks go back before idle
    got = {q.pop(timeout=0.05) for _ in range(3)}
    assert got == {HealTask("b", f"o{i}") for i in (1, 2, 3)}
    assert q.parked_depth() == 0 and len(q) == 0
    assert q.requeue_answered() == 0 and d2.asked == 2  # nothing left to ask
    assert _parked_since(before) == {"set_aside": 3, "absorbed": 1, "requeued": 3}


# -- MRF: partial write -> hook -> routine heals ---------------------------


class _FlakyDisk:
    """StorageAPI wrapper failing writes while .failing (naughtyDisk)."""

    def __init__(self, inner):
        self._inner = inner
        self.failing = False

    def __getattr__(self, name):
        fn = getattr(self._inner, name)
        if name in ("create_file", "rename_data", "write_metadata"):
            def guarded(*a, **kw):
                if self.failing:
                    raise serrors.FaultyDisk("injected")
                return fn(*a, **kw)

            return guarded
        return fn


def test_mrf_partial_write_heals(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    flaky = _FlakyDisk(disks[3])
    layer = ErasureObjects(
        disks[:3] + [flaky], block_size=BLOCK, min_part_size=1
    )
    queue = HealQueue()
    layer.heal_hook = queue.push_object
    layer.make_bucket("mrf")

    flaky.failing = True
    data = _pay(2 * BLOCK + 5, seed=1)
    layer.put_object("mrf", "obj", io.BytesIO(data), len(data))
    # write met quorum (3/4) and the miss was queued
    assert len(queue) == 1
    flaky.failing = False

    routine = HealRoutine(layer, queue).start()
    try:
        assert routine.drain(10)
        assert routine.healed == 1
    finally:
        routine.stop()
    # the flaky disk now holds its shard
    assert "obj" in list(disks[3].walk("mrf"))
    out = io.BytesIO()
    layer.get_object("mrf", "obj", out)
    assert out.getvalue() == data


# -- fresh-disk monitor ----------------------------------------------------


def _zones_layer(tmp_path, n=4):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(n)]
    ref, ordered = fmt.load_or_init_format(disks, 1, n)
    sets = ErasureSets(
        ordered, 1, n, block_size=BLOCK, format_ref=ref
    )
    for es in sets.sets:
        es.min_part_size = 1
    return ErasureZones([sets]), ordered


def test_fresh_disk_monitor_stamps_and_sweeps(tmp_path):
    zones, disks = _zones_layer(tmp_path)
    zones.make_bucket("mon")
    objs = {f"obj{i}": _pay(BLOCK + i, seed=i) for i in range(3)}
    for name, data in objs.items():
        zones.put_object("mon", name, io.BytesIO(data), len(data))

    # simulate a drive swap: wipe the disk's contents (root stays, as a
    # freshly mounted empty filesystem would)
    victim = disks[2]
    for entry in os.listdir(victim.root):
        shutil.rmtree(os.path.join(victim.root, entry))
    assert fmt.read_format(victim) is None

    queue = HealQueue()
    monitor = FreshDiskMonitor(zones, queue, interval_s=3600)
    stamped = monitor.scan_once()
    assert stamped == 1
    # re-stamped with the SAME uuid its slot records
    refreshed = fmt.read_format(victim)
    assert refreshed is not None
    assert refreshed.this == zones.zones[0].format_ref.sets[0][2]
    # sweep enqueued the bucket + every object
    assert len(queue) == 1 + len(objs)

    routine = HealRoutine(zones, queue).start()
    try:
        assert routine.drain(30)
    finally:
        routine.stop()
    for name, data in objs.items():
        assert name in list(victim.walk("mon"))
        out = io.BytesIO()
        zones.get_object("mon", name, out)
        assert out.getvalue() == data
    # second scan: nothing fresh
    assert monitor.scan_once() == 0


def test_boot_stamped_disk_triggers_sweep(tmp_path):
    """A wiped drive present at BOOT is stamped by load_or_init_format
    and must still get its set swept by the monitor's first pass."""
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ref, ordered = fmt.load_or_init_format(disks, 1, 4)
    sets = ErasureSets(ordered, 1, 4, block_size=BLOCK, format_ref=ref)
    for es in sets.sets:
        es.min_part_size = 1
    zones = ErasureZones([sets])
    zones.make_bucket("boot")
    data = _pay(BLOCK * 2, seed=7)
    zones.put_object("boot", "obj", io.BytesIO(data), len(data))

    # node goes down; drive wiped; node boots again
    victim = ordered[1]
    for entry in os.listdir(victim.root):
        shutil.rmtree(os.path.join(victim.root, entry))
    ref2, ordered2 = fmt.load_or_init_format(ordered, 1, 4)
    assert ref2.id == ref.id
    sets2 = ErasureSets(
        ordered2, 1, 4, block_size=BLOCK, format_ref=ref2
    )
    for es in sets2.sets:
        es.min_part_size = 1
    zones2 = ErasureZones([sets2])

    queue = HealQueue()
    monitor = FreshDiskMonitor(zones2, queue, interval_s=3600)
    monitor.scan_once()
    assert len(queue) >= 2  # bucket + object
    routine = HealRoutine(zones2, queue).start()
    try:
        assert routine.drain(30)
    finally:
        routine.stop()
    assert "obj" in list(victim.walk("boot"))


def test_bitrot_read_queues_deep_heal(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, block_size=BLOCK, min_part_size=1)
    queue = HealQueue()
    layer.heal_hook = queue.push_object
    layer.make_bucket("rot")
    data = _pay(2 * BLOCK, seed=9)
    layer.put_object("rot", "obj", io.BytesIO(data), len(data))

    # corrupt a DATA shard's bytes on disk: the k-read GET never
    # touches parity shards (erasure-decode.go:63-88), so parity
    # bitrot is the crawler's job, not the read path's; the object's
    # rotation decides which disk holds data shard 0
    from minio_tpu.objectlayer.metadata import hash_order

    data_disk = disks[hash_order("rot/obj", 4)[0] - 1]
    part = next(
        os.path.join(dp, f)
        for dp, _, fs in os.walk(os.path.join(data_disk.root, "rot"))
        for f in fs
        if f.startswith("part.")
    )
    with open(part, "r+b") as f:
        f.seek(40)
        f.write(b"\xff\xff\xff\xff")

    out = io.BytesIO()
    info = layer.get_object("rot", "obj", out)
    assert out.getvalue() == data  # parity covered the damage
    assert info.user_defined.get("x-internal-heal-required") == "true"
    assert len(queue) == 1

    routine = HealRoutine(layer, queue).start()
    try:
        assert routine.drain(10)
    finally:
        routine.stop()
    out = io.BytesIO()
    info = layer.get_object("rot", "obj", out)
    assert out.getvalue() == data
    assert "x-internal-heal-required" not in info.user_defined
