"""An erasure set that serves with drives lost: PUT, GET, STAT and DELETE
through the object layer while some drives' paths are files, not
directories (what the benchmark does to a lost drive), at EC 8+4 with two
of 12 lost and at EC 12+4 with three of 16 lost.

The shards left on the drives are decoded by the benchmark's plain
reference (``benchmark/reference.py``: GF(2^8) tables of its own), a GET is
made with exactly k shards left, and the counters the heal path keeps
(``kernel-stats.heal``, ``reconstruct.stripes``, the ``heal`` thread role)
are read back.
"""

import io
import os
import random
import shutil
import sys
import time

import numpy as np
import pytest

from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.heal import background as heal_bg
from minio_tpu.objectlayer import api
from minio_tpu.objectlayer import format as fmt
from minio_tpu.objectlayer.sets import ErasureSets
from minio_tpu.objectlayer.zones import ErasureZones
from minio_tpu.storage import metered
from minio_tpu.storage.diskcheck import DiskIDCheck
from minio_tpu.storage.xl import XLStorage
from minio_tpu.utils import spans

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
)
import reference  # noqa: E402

# a block of 64 KiB: 5461.33 bytes a data row at k = 12, as a 10 MiB block
# is 873,813.3 there - every shard of EC 12+4 is ragged
BLOCK = 64 * 1024
SIZES = (BLOCK, 30001, 2 * BLOCK)  # one block, a ragged size, two blocks
BUCKET = "lost"
# (k, m, drives lost, 1-based as the benchmark's traffic files give them)
GEOMETRIES = [
    pytest.param(8, 4, (2, 7), id="ec8+4-2lost"),
    pytest.param(12, 4, (3, 8, 13), id="ec12+4-3lost"),
]


class Set:
    """One erasure set of k + m drives built as the server builds it: each
    local drive behind its identity check, which looks at every call."""

    def __init__(self, root, k: int, m: int):
        self.k, self.m, self.n = k, m, k + m
        self.paths = [str(root / f"d{i}") for i in range(self.n)]
        raw = [XLStorage(p) for p in self.paths]
        ref, ordered = fmt.load_or_init_format(raw, 1, self.n)
        guarded = [
            DiskIDCheck(metered.wrap(d), ref.sets[0][i], check_interval_s=0.0)
            for i, d in enumerate(ordered)
        ]
        sets = ErasureSets(guarded, 1, self.n, parity_blocks=m, block_size=BLOCK,
                           format_ref=ref)
        self.layer = ErasureZones([sets])
        self.eset = sets.sets[0]
        self.queue = heal_bg.HealQueue()
        self.eset.heal_hook = self.queue.push_object
        self.layer.make_bucket(BUCKET)

    def lose(self, drives, undoably: bool = False) -> None:
        """The drives' paths stop being directories: nothing can be read
        from them and nothing written back.  ``undoably`` moves each
        drive's directory aside, for ``bring_back``."""
        for d in drives:
            path = self.paths[d - 1]
            if undoably:
                os.rename(path, path + ".away")
            else:
                shutil.rmtree(path)
            open(path, "w").close()

    def bring_back(self, drives) -> None:
        """Drives lost ``undoably`` answer again, holding what they held."""
        for d in drives:
            path = self.paths[d - 1]
            os.remove(path)
            os.rename(path + ".away", path)

    def put(self, key: str, body: bytes):
        return self.layer.put_object(BUCKET, key, io.BytesIO(body), len(body))

    def get(self, key: str) -> bytes:
        out = io.BytesIO()
        self.layer.get_object(BUCKET, key, out)
        return out.getvalue()

    def shards(self, key: str) -> "dict[int, str]":
        live = [p for p in self.paths if os.path.isdir(p)]
        return reference.shards_on_drives(live, BUCKET, key)


def payload(seed: int, size: int) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, size, dtype=np.uint8).tobytes()


def objects(tag: str) -> "list[tuple[str, bytes]]":
    return [(f"{tag}-{i}", payload(1000 * len(tag) + i, size)) for i, size in enumerate(SIZES)]


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_every_verb_answers_right_with_drives_lost(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    before = objects("before")
    for key, body in before:
        s.put(key, body)
    s.lose(lost)
    after = objects("after")
    for key, body in after:
        info = s.put(key, body)
        assert info.size == len(body)
    for key, body in before + after:
        assert s.get(key) == body  # a read rebuilds what the lost drives held
        assert s.layer.get_object_info(BUCKET, key).size == len(body)
    for key, _ in before + after:
        s.layer.delete_object(BUCKET, key)
        with pytest.raises(api.ObjectNotFound):
            s.layer.get_object_info(BUCKET, key)


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_the_surviving_shards_decode_through_the_reference(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    rng = random.Random(f"{k}+{m}/reference")
    for key, body in objects("ref"):
        s.put(key, body)
        parts = s.shards(key)
        # an acknowledged PUT is on every drive that is there: n less the lost
        assert len(parts) == s.n - len(lost) >= k
        use = sorted(rng.sample(sorted(parts), k))
        assert reference.decode_object(parts, use, len(body), k, m, BLOCK) == body


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_a_get_with_exactly_k_shards_left_answers_right(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    rng = random.Random(f"{k}+{m}/bitrot")
    for key, body in objects("rot"):
        s.put(key, body)
        parts = s.shards(key)
        # alter a byte of as many more shards as parity has left: k remain whole
        for victim in rng.sample(sorted(parts), m - len(lost)):
            at = reference.FRAME_DIGEST + min(len(body), BLOCK) // k // 2
            with open(parts[victim], "r+b") as f:
                f.seek(at)
                b = f.read(1)
                f.seek(at)
                f.write(bytes([b[0] ^ 0xFF]))
        assert s.get(key) == body


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_one_drive_past_parity_fails_the_put_for_quorum(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    key, body = objects("q")[0]
    s.lose(range(1, m + 2))
    with pytest.raises(api.WriteQuorumError):
        s.put(key, body)


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_each_short_put_is_pushed_to_the_heal_queue_once(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    pushed = dict(heal_bg.PUSHES)
    batch = objects("mrf")
    for key, body in batch:
        s.put(key, body)
    assert heal_bg.PUSHES["partial_write"] - pushed["partial_write"] == len(batch)
    assert len(s.queue) == len(batch)
    assert KERNEL_STATS.snapshot()["heal"]["queue_depth"] >= len(batch)
    # a read that rebuilds pushes the object again, as a degraded read
    key, body = batch[0]
    assert s.get(key) == body
    assert heal_bg.PUSHES["degraded_read"] - pushed["degraded_read"] == 1
    assert heal_bg.PUSHES["partial_write"] - pushed["partial_write"] == len(batch)


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_degraded_reads_count_the_data_rows_each_stripe_rebuilt(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    want_rows = want_stripes = 0
    for key, body in objects("rows"):
        s.put(key, body)
        rows = k - sum(1 for i in s.shards(key) if i < k)  # data rows on lost drives
        if rows:
            stripes = -(-len(body) // BLOCK)
            want_rows += rows * stripes
            want_stripes += stripes
    a = KERNEL_STATS.snapshot()["reconstruct"]
    for key, body in objects("rows"):
        assert s.get(key) == body
    b = KERNEL_STATS.snapshot()["reconstruct"]
    assert b["rows_rebuilt"] - a["rows_rebuilt"] == want_rows
    assert b["stripes"] - a["stripes"] == want_stripes
    by_rows = {int(r): n - a["stripes_by_rows"].get(r, 0) for r, n in b["stripes_by_rows"].items()}
    assert sum(by_rows.values()) == want_stripes
    assert sum(r * n for r, n in by_rows.items()) == want_rows
    assert all(1 <= r <= len(lost) for r, n in by_rows.items() if n)


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_kernel_stats_report_heal_outcomes_and_the_heal_role(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    batch = objects("heal")
    for key, body in batch:
        s.put(key, body)
    # a short PUT's entry waits for a lost drive; a push with nothing to
    # wait for (a crawl's) runs the task now, against drives still gone
    for key, _ in batch:
        s.queue.push_object(BUCKET, key)
    a = KERNEL_STATS.snapshot()
    routine = heal_bg.HealRoutine(s.layer, s.queue).start()
    try:
        assert routine.drain(60)
        b = KERNEL_STATS.snapshot()  # the thread's CPU is read while it lives
    finally:
        routine.stop()
    moved = {o: b["heal"]["tasks"][o] - a["heal"]["tasks"][o] for o in heal_bg.OUTCOMES}
    # every shard missing is on a drive that is still gone: nothing to write
    assert moved == {"healed": 0, "unwritable": len(batch), "clean": 0, "failed": 0}
    assert b["heal"]["written_bytes"] == a["heal"]["written_bytes"]
    # each task verified every shard that is there, whole
    survivors = s.n - len(lost)
    shard_bytes = sum(os.path.getsize(p) for key, _ in batch for p in s.shards(key).values())
    assert b["heal"]["read_bytes"] - a["heal"]["read_bytes"] == shard_bytes
    assert shard_bytes % survivors == 0
    assert b["heal"]["seconds"] > a["heal"]["seconds"]
    assert "heal" in b["cpu"] and b["cpu"]["heal"] > 0
    heal_spans = [r for r in b["spans"] if r["name"] == spans.HEAL_OBJECT]
    assert {r["role"] for r in heal_spans} == {"heal"}
    assert sum(r["count"] for r in heal_spans) - sum(
        r["count"] for r in a["spans"] if r["name"] == spans.HEAL_OBJECT) == len(batch)
    # the objects still read back
    for key, body in batch:
        assert s.get(key) == body


class _FailsRename:
    """A drive that takes its shard and then fails the commit, after a while."""

    def __init__(self, inner, delay_s: float):
        self._inner, self._delay = inner, delay_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def rename_data(self, *a, **kw):
        time.sleep(self._delay)
        raise OSError(5, "injected")


class _FailsWrites(_FailsRename):
    """A drive that opens the shard and then fails every write to it."""

    def create_file(self, *a, **kw):
        return _BadWriter(self._inner.create_file(*a, **kw), self._delay)


class _BadWriter:
    def __init__(self, inner, delay_s: float):
        self._inner, self._delay = inner, delay_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def write(self, data):
        time.sleep(self._delay)
        raise OSError(5, "injected")


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
@pytest.mark.parametrize("phase,drive", [("put_rename", _FailsRename),
                                         ("put_flush", _FailsWrites)])
def test_a_puts_time_on_drives_that_fail_is_counted_by_phase(tmp_path, k, m, lost, phase, drive):
    s = Set(tmp_path, k, m)
    for slot in (0, 1):
        s.eset.disks[slot] = drive(s.eset.disks[slot], 0.05)
    a = KERNEL_STATS.snapshot()["fanout_failed"].get(phase, {"jobs": 0, "run_seconds": 0})
    key, body = objects("fail")[0]
    s.put(key, body)  # two of n fail: quorum still met
    assert s.get(key) == body
    b = KERNEL_STATS.snapshot()["fanout_failed"][phase]
    assert b["jobs"] - a["jobs"] == 2
    assert 0.1 <= b["run_seconds"] - a["run_seconds"] < 5


def _heal_counts() -> dict:
    return KERNEL_STATS.snapshot()["heal"]


def _moved(a: dict, b: dict, table: str) -> dict:
    return {k: v - a[table].get(k, 0) for k, v in b[table].items()}


def _decodes_with(s: Set, key: str, body: bytes, must: "list[int]") -> bool:
    """The object decodes through the reference from the shard indices
    ``must`` and as many others as k takes."""
    parts = s.shards(key)
    rest = [i for i in sorted(parts) if i not in must]
    use = sorted(list(must) + rest[: s.k - len(must)])
    return reference.decode_object(parts, use, len(body), s.k, s.m, BLOCK) == body


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_heals_that_can_write_nothing_are_set_aside(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost, undoably=True)
    batch = objects("park")
    a = _heal_counts()
    for key, body in batch:
        s.put(key, body)
        assert s.get(key) == body  # degraded: pushed again, absorbed
    routine = heal_bg.HealRoutine(s.layer, s.queue).start()
    try:
        assert routine.drain(30)  # nothing waits on a drive that answers
    finally:
        routine.stop()
    b = _heal_counts()
    assert _moved(a, b, "tasks") == dict.fromkeys(heal_bg.OUTCOMES, 0)
    assert _moved(a, b, "parked") == {
        "set_aside": len(batch), "absorbed": len(batch), "requeued": 0}
    pushed = _moved(a, b, "pushed")
    assert pushed["partial_write"] == pushed["degraded_read"] == len(batch)
    assert pushed["bitrot_read"] == 0
    assert len(s.queue) == s.queue.parked_depth() == len(batch)
    assert b["parked_depth"] >= len(batch) and b["queue_depth"] >= len(batch)


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_set_aside_heals_run_once_their_drives_answer(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost, undoably=True)
    batch = objects("back")
    for key, body in batch:
        s.put(key, body)
        assert s.get(key) == body
    a = _heal_counts()
    s.bring_back(lost)
    # the drives answer: what waited on them is owed a heal before idle
    assert not s.queue.idle()
    routine = heal_bg.HealRoutine(s.layer, s.queue).start()
    try:
        assert routine.drain(60)
    finally:
        routine.stop()
    b = _heal_counts()
    assert _moved(a, b, "tasks") == {
        "healed": len(batch), "unwritable": 0, "clean": 0, "failed": 0}
    assert _moved(a, b, "parked")["requeued"] == len(batch)
    assert len(s.queue) == 0
    returned = [s.paths[d - 1] for d in lost]
    for key, body in batch:
        back = reference.shards_on_drives(returned, BUCKET, key)
        assert len(back) == len(lost) and len(s.shards(key)) == s.n
        assert _decodes_with(s, key, body, sorted(back))


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_a_read_that_meets_bitrot_heals_at_once_with_drives_lost(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    batch = objects("rot-now")
    for key, body in batch:
        s.put(key, body)
    key, body = batch[-1]
    parts = s.shards(key)
    victim = min(i for i in parts if i < k)  # a data shard the GET reads
    with open(parts[victim], "r+b") as f:
        f.seek(reference.FRAME_DIGEST + 1)
        b0 = f.read(1)
        f.seek(reference.FRAME_DIGEST + 1)
        f.write(bytes([b0[0] ^ 0xFF]))
    a = _heal_counts()
    assert s.get(key) == body
    b = _heal_counts()
    assert _moved(a, b, "pushed")["bitrot_read"] == 1
    assert _moved(a, b, "pushed")["degraded_read"] == 0
    assert s.queue.parked_depth() == len(batch) - 1  # it left the set-aside
    routine = heal_bg.HealRoutine(s.layer, s.queue).start()
    try:
        assert routine.drain(30)
    finally:
        routine.stop()
    c = _heal_counts()
    assert _moved(b, c, "tasks") == {"healed": 1, "unwritable": 0, "clean": 0, "failed": 0}
    assert len(s.queue) == s.queue.parked_depth() == len(batch) - 1
    assert _decodes_with(s, key, body, [victim])


@pytest.mark.parametrize("k,m,lost", GEOMETRIES)
def test_a_put_refused_by_a_drive_that_is_there_heals_at_once(tmp_path, k, m, lost):
    s = Set(tmp_path, k, m)
    s.lose(lost)
    slot = next(i for i in range(s.n) if i + 1 not in lost)
    good = s.eset.disks[slot]
    s.eset.disks[slot] = _FailsWrites(good, 0.0)
    key, body = objects("refused")[0]
    a = _heal_counts()
    s.put(key, body)  # short on the lost drives and on the one that refused
    s.eset.disks[slot] = good
    b = _heal_counts()
    assert _moved(a, b, "pushed")["partial_write"] == 1
    assert _moved(a, b, "parked")["set_aside"] == 0
    assert len(s.queue) == 1 and s.queue.parked_depth() == 0
    assert len(s.shards(key)) == s.n - len(lost) - 1
    routine = heal_bg.HealRoutine(s.layer, s.queue).start()
    try:
        assert routine.drain(30)
    finally:
        routine.stop()
    c = _heal_counts()
    assert _moved(b, c, "tasks") == {"healed": 1, "unwritable": 0, "clean": 0, "failed": 0}
    assert len(s.shards(key)) == s.n - len(lost)
    assert s.get(key) == body
