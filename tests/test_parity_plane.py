"""Device-resident parity plane (ISSUE 7): the digest-only encode seam.

Covers the tentpole's moving parts in isolation and end to end:

* ParityPlaneCache - bounded occupancy under concurrent adds, FIFO
  write-back eviction order, forget accounting;
* digest-only encode (TpuBackend/CpuBackend/batcher) - bit-identical
  parity + digests vs the eager path;
* encode_end/encode_digest_end idempotency (the satellite fix: error-
  path cleanup can never double-consume a handle);
* quorum-early ParityBand - drain failures are heal-flagged, never
  silent; late-dead callbacks fire behind the ack;
* the batcher's cache-pressure backoff;
* D2H telemetry split by plane (data digests eager, parity lazy).
"""

import io
import threading
import time

import numpy as np
import pytest

from minio_tpu.codec import backend as backend_mod
from minio_tpu.codec.backend import (
    CpuBackend,
    ParityPlaneCache,
    TpuBackend,
    parity_plane_cache,
    reset_backend,
)
from minio_tpu.codec.batcher import BatchingBackend
from minio_tpu.codec.erasure import Erasure
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.parallel import iopool


@pytest.fixture(autouse=True)
def _fresh_parity_cache():
    """Every test gets its own parity cache singleton (and leaves no
    device planes parked for the next test)."""
    reset_backend()
    yield
    reset_backend()


@pytest.fixture
def single_device(monkeypatch):
    """Force the single-device digest path (the 8-device test mesh has
    no device-resident cache - planes live sharded)."""
    monkeypatch.setenv("MINIO_MESH", "0")


def _data(batch=3, k=4, length=256, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (batch, k, length), dtype=np.uint8
    )


# -- ParityPlaneCache ----------------------------------------------------


class _StubRef:
    """Cache-entry double: drain() write-back that forgets itself."""

    def __init__(self, cache, nbytes):
        self.cache = cache
        self.nbytes = nbytes
        self.drained = threading.Event()

    def drain(self):
        self.drained.set()
        self.cache.forget(self)
        return b""


def test_cache_add_evicts_fifo_beyond_budget():
    cache = ParityPlaneCache(capacity_bytes=100)
    refs = [_StubRef(cache, 40) for _ in range(4)]
    for r in refs[:2]:
        cache.add(r)
    assert cache.stats()["occupancy_bytes"] == 80
    assert not any(r.drained.is_set() for r in refs[:2])
    cache.add(refs[2])  # 120 > 100: oldest written back
    assert refs[0].drained.is_set()
    assert not refs[1].drained.is_set()
    cache.add(refs[3])
    assert refs[1].drained.is_set()
    assert not refs[2].drained.is_set()
    s = cache.stats()
    assert s["occupancy_bytes"] == 80
    assert s["evictions"] == 2 and s["added"] == 4


def test_cache_oversized_lone_plane_is_admitted():
    """A single plane larger than the budget must not deadlock or evict
    itself - it just loses laziness at the next add."""
    cache = ParityPlaneCache(capacity_bytes=10)
    big = _StubRef(cache, 100)
    cache.add(big)
    assert not big.drained.is_set()
    assert cache.pressure() == 10.0
    nxt = _StubRef(cache, 100)
    cache.add(nxt)
    assert big.drained.is_set()


def test_cache_forget_is_idempotent_and_rebalances():
    cache = ParityPlaneCache(capacity_bytes=100)
    r = _StubRef(cache, 60)
    cache.add(r)
    cache.forget(r)
    cache.forget(r)  # double-forget must not go negative
    s = cache.stats()
    assert s["occupancy_bytes"] == 0 and s["entries"] == 0


def test_cache_occupancy_bounded_under_concurrent_adds():
    """A burst of concurrent PUT-sized planes never pins more than
    budget + one in-flight plane of device memory."""
    cache = ParityPlaneCache(capacity_bytes=1000)
    peak = []
    peak_lk = threading.Lock()

    def put_many(seed):
        for _ in range(25):
            cache.add(_StubRef(cache, 100))
            occ = cache.stats()["occupancy_bytes"]
            with peak_lk:
                peak.append(occ)

    threads = [
        threading.Thread(target=put_many, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # transient overshoot is bounded by the planes concurrently inside
    # add() (one per thread), never unbounded
    assert max(peak) <= 1000 + 8 * 100
    assert cache.stats()["occupancy_bytes"] <= 1000


# -- digest-only encode: bit identity ------------------------------------


def test_cpu_backend_digest_seam_matches_eager():
    be = CpuBackend()
    data = _data()
    parity, digests = be.encode(data, 2)
    d2, ref = be.encode_digest_end(be.encode_digest_begin(data, 2))
    np.testing.assert_array_equal(d2, digests)
    np.testing.assert_array_equal(ref.drain(), parity)


def test_tpu_digest_path_bit_identical_and_lazy(single_device):
    be = TpuBackend()
    data = _data(batch=2, k=4, length=512, seed=3)
    parity, digests = CpuBackend().encode(data, 2)
    KERNEL_STATS.reset()
    dig, ref = be.encode_digest_end(be.encode_digest_begin(data, 2))
    np.testing.assert_array_equal(dig, digests)
    # parity has NOT crossed the bus yet: only digest bytes recorded
    planes = {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    }
    assert planes.get("data", 0) == dig.nbytes
    assert planes.get("parity", 0) == 0
    assert parity_plane_cache().stats()["entries"] == 1
    par = ref.drain()
    np.testing.assert_array_equal(par, parity)
    planes = {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    }
    assert planes["parity"] > 0
    assert parity_plane_cache().stats()["entries"] == 0
    # memoized: a second drain is the same array, no second transfer
    assert ref.drain() is par
    assert {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    } == planes


def test_tpu_digest_path_all_zero_plane(single_device):
    """An all-zero plane drains as zeros, the whole plane one transfer."""
    be = TpuBackend()
    data = np.zeros((1, 4, 16384), dtype=np.uint8)  # a rung: no padding
    KERNEL_STATS.reset()
    dig, ref = be.encode_digest_end(be.encode_digest_begin(data, 2))
    par = ref.drain()
    assert not par.any()
    planes = {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    }
    assert planes["parity"] == par.nbytes


def test_release_drops_plane_without_transfer(single_device):
    be = TpuBackend()
    data = _data(batch=1, k=2, length=128, seed=9)
    KERNEL_STATS.reset()
    _dig, ref = be.encode_digest_end(be.encode_digest_begin(data, 1))
    assert parity_plane_cache().stats()["entries"] == 1
    ref.release()
    assert parity_plane_cache().stats()["entries"] == 0
    planes = {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    }
    assert planes.get("parity", 0) == 0


# -- encode_end idempotency (the satellite fix) --------------------------


def test_tpu_encode_end_is_idempotent(single_device):
    be = TpuBackend()
    data = _data(seed=4)
    h = be.encode_begin(data, 2)
    r1 = be.encode_end(h)
    r2 = be.encode_end(h)  # error-path cleanup racing normal consume
    assert r1 is r2
    parity, digests = r1
    p_ref, d_ref = CpuBackend().encode(data, 2)
    np.testing.assert_array_equal(parity, p_ref)
    np.testing.assert_array_equal(digests, d_ref)


def test_tpu_encode_digest_end_is_idempotent(single_device):
    be = TpuBackend()
    h = be.encode_digest_begin(_data(seed=5), 2)
    r1 = be.encode_digest_end(h)
    r2 = be.encode_digest_end(h)
    assert r1 is r2
    # and the cache holds ONE plane, not two
    assert parity_plane_cache().stats()["added"] == 1


def test_batcher_encode_end_is_idempotent():
    b = BatchingBackend(CpuBackend(), deadline_s=0.02)
    try:
        h = b.encode_begin(_data(seed=6), 2)
        r1 = b.encode_end(h)
        r2 = b.encode_end(h)  # double-end must not corrupt _active
        assert r1 is r2
        # the distinct-client signal went back to zero exactly once:
        # a fresh encode still coalesces/flushes promptly
        parity, _ = b.encode(_data(seed=7), 2)
        assert parity.shape == (3, 2, 256)
    finally:
        b.shutdown()


# -- batcher digest seam + cache-pressure backoff ------------------------


def test_batcher_digest_seam_slices_match(single_device):
    """Concurrent digest-only encodes coalesce; every caller's slice of
    the shared plane drains bit-identical to its eager encode."""
    ref_be = CpuBackend()
    b = BatchingBackend(TpuBackend(), deadline_s=0.05)
    try:
        datas = [_data(seed=i) for i in range(6)]
        expected = [ref_be.encode(d, 2) for d in datas]
        results = [None] * 6
        barrier = threading.Barrier(6)

        def run(i):
            barrier.wait()
            h = b.encode_digest_begin(datas[i], 2)
            results[i] = b.encode_digest_end(h)

        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (dig, pref) in enumerate(results):
            np.testing.assert_array_equal(dig, expected[i][1])
            np.testing.assert_array_equal(pref.drain(), expected[i][0])
    finally:
        b.shutdown()


class _PressureBackend(CpuBackend):
    def __init__(self):
        self.pressure = 0.0

    def parity_cache_pressure(self):
        return self.pressure


def test_batcher_backs_off_under_cache_pressure():
    inner = _PressureBackend()
    b = BatchingBackend(inner, deadline_s=0.02)
    try:
        inner.pressure = 2.0
        t0 = time.monotonic()
        threading.Timer(0.06, lambda: setattr(inner, "pressure", 0.1)).start()
        h = b.encode_digest_begin(_data(seed=8), 2)
        waited = time.monotonic() - t0
        b.encode_digest_end(h)
        assert 0.04 <= waited < 0.3
        # no pressure: admission is immediate
        t0 = time.monotonic()
        b.encode_digest_end(b.encode_digest_begin(_data(seed=9), 2))
        assert time.monotonic() - t0 < 0.25
    finally:
        b.shutdown()


def test_batcher_backoff_is_bounded():
    """Pressure that never clears must not wedge admission."""
    inner = _PressureBackend()
    inner.pressure = 99.0
    b = BatchingBackend(inner, deadline_s=0.02)
    try:
        t0 = time.monotonic()
        b.encode_digest_end(b.encode_digest_begin(_data(seed=10), 2))
        assert time.monotonic() - t0 < 2.0
    finally:
        b.shutdown()


# -- ParityBand: nothing fails silently behind the ack -------------------


def test_parity_band_flags_heal_on_failed_submitted_job():
    band = iopool.ParityBand()
    band.submit(5, "disk-5", lambda: (_ for _ in ()).throw(OSError("boom")))
    band.submit(4, "disk-4", lambda: None)
    assert band.settle() is False
    assert band.heal_required and band.dead_slots == {5}


def test_parity_band_flag_heal_is_idempotent_per_slot():
    band = iopool.ParityBand()
    band.flag_heal(3, OSError("x"))
    band.flag_heal(3, OSError("y"))
    band.flag_heal(4, OSError("z"))
    assert band.dead_slots == {3, 4}


def test_parity_band_adopts_flusher_stragglers():
    pool = iopool.get_pool()
    flusher = iopool.ShardFlusher(pool)
    band = iopool.ParityBand(pool)
    gate = threading.Event()

    def slow_fail():
        gate.wait(5.0)
        raise OSError("parity disk died behind the ack")

    jobs = [(s, f"ik-{s}", lambda: None, 0) for s in range(4)]
    jobs.append((4, "ik-4", slow_fail, 0))
    dead = flusher.flush(jobs, quorum=4)
    assert dead == set()  # acked at data quorum, straggler in flight
    band.adopt(flusher)
    assert band.adopted
    gate.set()
    assert band.settle() is False
    assert band.dead_slots == {4}


def test_parity_band_late_dead_callback_fires_behind_ack():
    pool = iopool.get_pool()
    flusher = iopool.ShardFlusher(pool)
    seen = []
    fired = threading.Event()

    def on_late(slot, err):
        seen.append((slot, str(err)))
        fired.set()

    flusher.on_late_dead = on_late
    gate = threading.Event()

    def slow_fail():
        gate.wait(5.0)
        raise OSError("late")

    jobs = [(s, f"lk-{s}", lambda: None, 0) for s in range(3)]
    jobs.append((3, "lk-3", slow_fail, 0))
    flusher.flush(jobs, quorum=3)
    gate.set()
    assert fired.wait(5.0)
    assert seen == [(3, "late")]
    flusher.drain()


def test_parity_band_finish_settles_in_background():
    band = iopool.ParityBand()
    band.submit(2, "fin-2", lambda: None)
    verdicts = []
    fut = band.finish(on_done=lambda b: verdicts.append(b.heal_required))
    assert fut.wait(5.0)
    assert verdicts == [False]


# -- end to end: quorum-early encode writes identical shards -------------


class MemShard:
    def __init__(self):
        self.buf = bytearray()

    def write(self, b):
        self.buf += b


def _encode_to_shards(payload, k, m, block_size, band=None, env=None):
    er = Erasure(k, m, block_size)
    shards = [MemShard() for _ in range(k + m)]
    total = er.encode(
        io.BytesIO(payload),
        list(shards),
        write_quorum=k + 1,
        parity_band=band,
    )
    return total, shards


def test_quorum_early_shards_bit_identical_to_legacy(
    single_device, monkeypatch
):
    k, m, bs = 4, 2, 2048
    payload = np.random.default_rng(21).integers(
        0, 256, 3 * bs + 123, dtype=np.uint8
    ).tobytes()
    monkeypatch.setenv("MINIO_TPU_PARITY_PLANE", "off")
    total_legacy, legacy_shards = _encode_to_shards(payload, k, m, bs)
    legacy = [bytes(s.buf) for s in legacy_shards]
    monkeypatch.setenv("MINIO_TPU_PARITY_PLANE", "on")
    band = iopool.ParityBand()
    total_early, early_shards = _encode_to_shards(
        payload, k, m, bs, band=band
    )
    assert band.adopted
    # parity shards are still draining in the background band until
    # settle() — snapshotting them before this point would race
    assert band.settle() is True
    early = [bytes(s.buf) for s in early_shards]
    assert total_early == total_legacy == len(payload)
    assert early == legacy


def test_digest_mode_without_band_settles_inline(single_device):
    """Default commit (MINIO_TPU_PARITY_ACK=settle): digest-only encode
    with no band still waits for parity writers before returning."""
    k, m, bs = 4, 2, 2048
    payload = b"q" * (2 * bs + 77)
    total, shards = _encode_to_shards(payload, k, m, bs)
    assert total == len(payload)
    er = Erasure(k, m, bs)
    for s in shards:
        assert len(s.buf) == er.shard_file_size(len(payload))
