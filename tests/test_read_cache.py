"""Tiered read cache (minio_tpu/cache/): admission, eviction,
invalidation (local + cross-node), device-budget coexistence, and the
digest-verified hit path over a real ErasureObjects layer.
"""

import io
import os
import shutil
import threading

import numpy as np
import pytest

from minio_tpu import cache as rcache
from minio_tpu.cache.admission import AdmissionFilter, FrequencySketch
from minio_tpu.cache.allocator import DeviceBudget
from minio_tpu.cache.tiered import (
    TIER_DEVICE,
    TIER_HOST,
    TieredReadCache,
)
from minio_tpu.cluster import peer as peer_mod
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.storage.xl import XLStorage

BLOCK = 4096


# -- harness -------------------------------------------------------------


@pytest.fixture
def cache_env():
    """Enable the host-tier cache for the test, restore + reset after."""

    def enable(mode="host", **extra):
        os.environ["MINIO_TPU_READ_CACHE"] = mode
        for k, v in extra.items():
            os.environ[k] = v
        rcache.reset_read_cache()

    saved = {
        k: os.environ.get(k)
        for k in (
            "MINIO_TPU_READ_CACHE",
            "MINIO_TPU_READ_CACHE_MB",
            "MINIO_TPU_READ_CACHE_DEVICE_MB",
        )
    }
    yield enable
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    rcache.set_broadcast(None)
    rcache.reset_read_cache()


@pytest.fixture
def layer(tmp_path):
    disks = [XLStorage(str(tmp_path / f"disk{i}")) for i in range(6)]
    ol = ErasureObjects(disks, block_size=BLOCK)
    ol.make_bucket("bucket")
    return ol, disks


def _payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8
    ).tobytes()


def _get(ol, name, **kw):
    buf = io.BytesIO()
    ol.get_object("bucket", name, buf, **kw)
    return buf.getvalue()


class _FakeBackend:
    """verify() stub: a constant verdict, so tier mechanics can be
    tested without real bitrot frames."""

    def __init__(self, ok=True):
        self.ok = ok
        self.calls = 0

    def verify(self, data, digests):
        self.calls += 1
        g, k = data.shape[0], data.shape[1]
        return np.full((g, k), self.ok, dtype=bool)


def _group(seed=0, g=2, k=3, n=64):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (g, k, n), dtype=np.uint8)
    digests = rng.integers(0, 2**31, (g, k, 8), dtype=np.uint32)
    return data, digests


def _key(obj, first_block=0, g=2, n=64, data_dir="dd0"):
    return ("bucket", obj, data_dir, 1, first_block, g, n)


# -- admission unit tests ------------------------------------------------


def test_frequency_sketch_counts_saturate_and_age():
    sk = FrequencySketch(width=64, depth=4, sample_factor=1)
    assert sk.estimate("cold") == 0
    for _ in range(4):
        sk.touch("warm")
    assert 1 <= sk.estimate("warm") <= 15
    before = sk.estimate("warm")
    for _ in range(1000):
        sk.touch(f"noise-{_}")
    # the aging sweeps halved counts at least once along the way
    assert sk.ages >= 1
    assert sk.estimate("warm") <= before


def test_admission_contest_hot_beats_cold():
    adm = AdmissionFilter()
    for _ in range(8):
        adm.record("hot")
    adm.record("cold")
    assert adm.contest("hot", "cold")
    assert not adm.contest("cold", "hot")
    # no victim: always admitted
    assert adm.contest("anything", None)
    st = adm.stats()
    assert st["admitted"] >= 2 and st["rejected"] >= 1


def test_admission_seed_prefers_crawled_heat():
    adm = AdmissionFilter()
    adm.seed("crawled", hits=4)
    adm.record("fresh")
    assert adm.contest("crawled", "fresh")
    assert adm.stats()["seeded"] == 1


# -- device budget -------------------------------------------------------


def test_device_budget_ledger():
    b = DeviceBudget(100)
    assert b.headroom() == 100
    b.set_usage("parity_plane", 60)
    b.set_usage("read_cache", 25)
    assert b.usage() == 85
    assert b.usage("parity_plane") == 60
    assert b.headroom() == 15
    snap = b.snapshot()
    assert snap["capacity_bytes"] == 100
    assert snap["accounts"]["read_cache"] == 25
    b.set_usage("parity_plane", 0)
    assert b.headroom() == 75


# -- tier mechanics ------------------------------------------------------


def test_put_lookup_roundtrip_host_tier():
    c = TieredReadCache(TIER_HOST, host_capacity=1 << 20, device_capacity=0)
    be = _FakeBackend()
    data, digests = _group()
    assert c.put(_key("o"), "bucket/o", data, digests, source="put")
    out = c.lookup(be, _key("o"), "bucket/o")
    assert out is not None and np.array_equal(out, data)
    st = c.stats()
    assert st["tiers"][TIER_HOST]["hits"] == 1
    assert c.lookup(be, _key("absent"), "bucket/absent") is None
    assert c.stats()["tiers"][TIER_HOST]["misses"] == 1


def test_eviction_respects_capacity_and_admission():
    data, digests = _group()
    per_entry = data.nbytes + digests.nbytes
    c = TieredReadCache(
        TIER_HOST, host_capacity=3 * per_entry, device_capacity=0
    )
    # make one object hot enough to win any contest
    for _ in range(10):
        c.admission.record("bucket/hot")
    assert c.put(_key("hot"), "bucket/hot", data, digests)
    for i in range(8):
        c.put(_key(f"cold{i}"), f"bucket/cold{i}", data, digests)
    st = c.stats()["tiers"][TIER_HOST]
    assert st["occupancy_bytes"] <= 3 * per_entry
    # the hot entry survived the cold flood (TinyLFU admission)
    assert c.lookup(_FakeBackend(), _key("hot"), "bucket/hot") is not None
    assert st["rejects"] + st["evictions"] > 0


def test_oversized_entry_rejected():
    data, digests = _group()
    c = TieredReadCache(
        TIER_HOST, host_capacity=data.nbytes // 2, device_capacity=0
    )
    assert not c.put(_key("big"), "bucket/big", data, digests)
    assert c.stats()["tiers"][TIER_HOST]["rejects"] == 1


def test_invalidate_drops_all_groups_of_object():
    c = TieredReadCache(TIER_HOST, host_capacity=1 << 20, device_capacity=0)
    data, digests = _group()
    for fb in (0, 4, 8):
        c.put(_key("o", first_block=fb), "bucket/o", data, digests)
    c.put(_key("other"), "bucket/other", data, digests)
    assert c.invalidate("bucket", "o") == 3
    assert c.lookup(_FakeBackend(), _key("o"), "bucket/o") is None
    assert (
        c.lookup(_FakeBackend(), _key("other"), "bucket/other") is not None
    )
    assert c.stats()["invalidations"] == 1
    assert c.invalidate("bucket", "gone") == 0


def test_verify_failure_drops_entry_and_counts():
    c = TieredReadCache(TIER_HOST, host_capacity=1 << 20, device_capacity=0)
    data, digests = _group()
    c.put(_key("o"), "bucket/o", data, digests)
    bad = _FakeBackend(ok=False)
    assert c.lookup(bad, _key("o"), "bucket/o") is None
    st = c.stats()
    assert st["verify_drops"] == 1
    assert st["tiers"][TIER_HOST]["entries"] == 0
    # a later lookup is a plain miss, not another drop
    assert c.lookup(bad, _key("o"), "bucket/o") is None
    assert c.stats()["verify_drops"] == 1


def test_concurrent_put_lookup_stays_bounded():
    data, digests = _group(g=1, k=2, n=256)
    per_entry = data.nbytes + digests.nbytes
    cap = 8 * per_entry
    c = TieredReadCache(TIER_HOST, host_capacity=cap, device_capacity=0)
    be = _FakeBackend()
    errors = []

    def worker(tid):
        try:
            for i in range(50):
                name = f"o{tid}-{i % 12}"
                c.put(_key(name), f"bucket/{name}", data, digests)
                c.lookup(be, _key(name), f"bucket/{name}")
        except Exception as exc:  # noqa: BLE001 - surfaced below
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = c.stats()["tiers"][TIER_HOST]
    assert st["occupancy_bytes"] <= cap
    assert st["entries"] * per_entry == st["occupancy_bytes"]


@pytest.mark.parametrize("short_by", ["everything", "one-byte"])
def test_device_tier_respects_shared_budget(short_by):
    """With the parity plane holding the device budget - all of it, or
    all but one byte less than an entry needs - device admissions
    overflow to the host tier instead of double-booking, and the
    parity plane's bytes are never an eviction victim."""
    data, digests = _group()
    per_entry = data.nbytes + digests.nbytes
    budget = DeviceBudget(per_entry * 2)
    held = per_entry * 2 if short_by == "everything" else per_entry + 1
    budget.set_usage("parity_plane", held)
    c = TieredReadCache(
        TIER_DEVICE,
        host_capacity=1 << 20,
        device_capacity=1 << 20,
        budget=budget,
    )
    assert c.put(_key("o"), "bucket/o", data, digests)
    st = c.stats()["tiers"]
    assert st[TIER_DEVICE]["entries"] == 0
    assert st[TIER_HOST]["entries"] == 1
    assert budget.usage("parity_plane") == held
    assert budget.usage("read_cache") == 0
    # the parity plane drains: device tier opens up and reports usage
    budget.set_usage("parity_plane", 0)
    assert c.put(_key("o2"), "bucket/o2", data, digests)
    assert c.stats()["tiers"][TIER_DEVICE]["entries"] == 1
    assert budget.usage("read_cache") == per_entry


def test_device_eviction_demotes_to_host():
    data, digests = _group()
    per_entry = data.nbytes + digests.nbytes
    c = TieredReadCache(
        TIER_DEVICE,
        host_capacity=1 << 20,
        device_capacity=per_entry,  # one device slot
        budget=DeviceBudget(1 << 30),
    )
    heat = "bucket/o0"
    c.admission.record(heat)
    for _ in range(8):  # strict >: the newcomer must be hotter to evict
        c.admission.record("bucket/o1")
    assert c.put(_key("o0"), heat, data, digests)
    assert c.put(_key("o1"), "bucket/o1", data, digests)
    st = c.stats()
    assert st["demotions"] == 1
    assert st["tiers"][TIER_DEVICE]["entries"] == 1
    assert st["tiers"][TIER_HOST]["entries"] == 1
    # the demoted group still serves (now from host)
    out = c.lookup(_FakeBackend(), _key("o0"), heat)
    assert out is not None and np.array_equal(out, data)


# -- object-layer integration --------------------------------------------


def test_get_serves_from_cache_bit_identical(cache_env, layer):
    ol, _ = layer
    payload = _payload(5 * BLOCK + 123, seed=1)
    # baseline: cache off — today's read path
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    assert rcache.read_cache() is None
    baseline = _get(ol, "obj")
    assert baseline == payload

    cache_env("host")
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    first = _get(ol, "obj")
    hot = _get(ol, "obj")
    assert first == payload and hot == baseline
    st = rcache.read_cache_stats()
    assert st["mode"] == "host"
    assert st["tiers"][TIER_HOST]["hits"] > 0


def test_ranged_get_bit_identical_with_cache(cache_env, layer):
    ol, _ = layer
    payload = _payload(4 * BLOCK + 77, seed=2)
    cache_env("host")
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    _get(ol, "obj")  # warm
    for off, ln in ((0, 10), (BLOCK - 3, 7), (BLOCK, 2 * BLOCK), (17, None)):
        kw = {"offset": off}
        if ln is not None:
            kw["length"] = ln
        got = _get(ol, "obj", **kw)
        want = payload[off:] if ln is None else payload[off:off + ln]
        assert got == want, (off, ln)


def test_off_mode_is_inert(cache_env, layer):
    ol, _ = layer
    cache_env("off")
    payload = _payload(2 * BLOCK, seed=3)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    assert _get(ol, "obj") == payload
    assert rcache.read_cache() is None
    st = rcache.read_cache_stats()
    assert st["mode"] == "off"
    assert st["tiers"][TIER_HOST]["hits"] == 0


def test_overwrite_invalidates_and_serves_new_bytes(cache_env, layer):
    ol, _ = layer
    cache_env("host")
    old = _payload(3 * BLOCK, seed=4)
    new = _payload(3 * BLOCK, seed=5)
    ol.put_object("bucket", "obj", io.BytesIO(old), len(old))
    assert _get(ol, "obj") == old
    ol.put_object("bucket", "obj", io.BytesIO(new), len(new))
    assert _get(ol, "obj") == new
    assert _get(ol, "obj") == new  # hot path too
    assert rcache.read_cache_stats()["invalidations"] >= 1


def test_delete_invalidates(cache_env, layer):
    ol, _ = layer
    cache_env("host")
    payload = _payload(2 * BLOCK, seed=6)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    _get(ol, "obj")
    before = rcache.read_cache_stats()["invalidations"]
    ol.delete_object("bucket", "obj")
    st = rcache.read_cache_stats()
    assert st["invalidations"] > before
    assert st["tiers"][TIER_HOST]["entries"] == 0


def test_heal_invalidates(cache_env, layer, tmp_path):
    ol, disks = layer
    cache_env("host")
    payload = _payload(2 * BLOCK + 9, seed=7)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    _get(ol, "obj")
    shutil.rmtree(disks[2].root)
    os.makedirs(os.path.join(disks[2].root, ".sys", "tmp"))
    disks[2].make_vol("bucket")
    before = rcache.read_cache_stats()["invalidations"]
    res = ol.heal_object("bucket", "obj")
    assert res["healed"], res
    assert rcache.read_cache_stats()["invalidations"] > before
    assert _get(ol, "obj") == payload


def test_corrupted_cached_group_falls_back_to_quorum(cache_env, layer):
    ol, _ = layer
    cache_env("host")
    payload = _payload(3 * BLOCK + 41, seed=8)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    _get(ol, "obj")
    c = rcache.read_cache()
    tier = c._tiers[TIER_HOST]
    assert tier, "PUT should have populated the cache"
    for ent in tier.values():
        ent.data = np.array(ent.data, copy=True)
        ent.data[..., 0] ^= 0xFF  # rot every cached group
    got = _get(ol, "obj")
    assert got == payload  # served from the quorum read, not the rot
    st = rcache.read_cache_stats()
    assert st["verify_drops"] >= 1


def test_invalidate_object_broadcasts_once(cache_env):
    cache_env("host")
    calls = []
    rcache.set_broadcast(lambda b, o: calls.append((b, o)))
    data, digests = _group()
    c = rcache.read_cache()
    c.put(_key("o"), "bucket/o", data, digests)
    dropped = rcache.invalidate_object("bucket", "o")
    assert dropped == 1
    assert calls == [("bucket", "o")]
    # the peer-RPC twin never re-broadcasts (no ping-pong)
    c.put(_key("o"), "bucket/o", data, digests)
    assert rcache.invalidate_local("bucket", "o") == 1
    assert calls == [("bucket", "o")]


def test_peer_handler_invalidates_local(cache_env):
    cache_env("host")
    data, digests = _group()
    c = rcache.read_cache()
    c.put(_key("o"), "bucket/o", data, digests)
    handler = peer_mod.PeerRESTServer._METHODS["invalidatereadcache"]
    res = handler(None, {"bucket": ["bucket"], "object": ["o"]}, None)
    assert res == {"ok": True, "dropped": 1}
    assert c.lookup(_FakeBackend(), _key("o"), "bucket/o") is None
    bad = handler(None, {"bucket": ["bucket"]}, None)
    assert bad["ok"] is False


def test_seed_heat_reaches_admission(cache_env):
    cache_env("host")
    rcache.seed_heat("bucket", "crawled", hits=4)
    st = rcache.read_cache_stats()["admission"]
    assert st["seeded"] == 1


def test_clear_read_cache(cache_env):
    cache_env("host")
    data, digests = _group()
    c = rcache.read_cache()
    c.put(_key("a"), "bucket/a", data, digests)
    c.put(_key("b"), "bucket/b", data, digests)
    assert rcache.clear_read_cache() == 2
    assert rcache.read_cache_stats()["tiers"][TIER_HOST]["entries"] == 0


def test_auto_mode_resolves_to_a_real_tier(cache_env):
    cache_env("auto")
    assert rcache.cache_mode() in ("host", "device")
    cache_env("bogus-value")
    assert rcache.cache_mode() == "off"


# -- reconstructed-row admission (parity-preferred readers) ---------------


class _LocalityShard:
    """In-memory shard file whose locality the test controls: a cluster
    node whose LOCAL drives hold parity shards prefers them over remote
    data shards, so a healthy GET reconstructs on every read."""

    def __init__(self, is_local):
        self.is_local = is_local
        self.buf = bytearray()
        self.reads = 0

    def write(self, b):
        self.buf += b

    def read_at(self, off, length):
        self.reads += 1
        return bytes(self.buf[off : off + length])


def test_admits_from_reconstructed_rows_when_parity_preferred(
    cache_env, monkeypatch
):
    """The preference order is local-before-data: a node whose local
    drives hold parity never reads the data slots directly, and the
    cache must still populate from the reconstructed rows (with
    freshly computed digest words) — otherwise such a node misses
    forever and the hot-key chaos cell sees disk calls on every GET."""
    from minio_tpu.codec.erasure import Erasure
    from minio_tpu.storage import health

    # the order is under test, not hedging (MINIO_TPU_HEDGE=0, on the
    # registry this process already has): once earlier reads have armed
    # the hedge deadline, 2 ms of scheduling delay duplicates a read
    # onto the next shard in order, a data shard
    monkeypatch.setattr(health.registry().cfg, "hedge_enabled", False)
    cache_env("host")
    k, m, size = 3, 3, 40_000
    er = Erasure(k, m, 4096)
    payload = _payload(size, seed=21)
    shards = [
        _LocalityShard(is_local=(i >= k)) for i in range(k + m)
    ]
    er.encode(io.BytesIO(payload), list(shards), write_quorum=k + 1)

    ctx = rcache.context_for("bucket", "obj", "dd-rec", 1)
    assert ctx is not None
    out = io.BytesIO()
    written, heal = er.decode(
        out, [s for s in shards], 0, size, size, cache_ctx=ctx
    )
    assert written == size and out.getvalue() == payload
    assert not heal  # unread data slots are not damage
    # only the preferred (local parity) shards were opened
    assert all(s.reads == 0 for s in shards[:k])
    stats = rcache.read_cache_stats()
    assert stats["tiers"][TIER_HOST]["entries"] >= 1

    def no_readers():
        raise AssertionError("cache hit must not open shard readers")

    out2 = io.BytesIO()
    written2, heal2 = er.decode(
        out2, no_readers, 0, size, size, cache_ctx=ctx
    )
    assert written2 == size and out2.getvalue() == payload
    assert not heal2


# -- FileInfo side-car ----------------------------------------------------


def test_meta_sidecar_serves_get_without_quorum_read(
    cache_env, layer, monkeypatch
):
    ol, _disks = layer
    cache_env("host")
    payload = _payload(24_000, seed=31)
    ol.put_object("bucket", "meta-obj", io.BytesIO(payload), len(payload))
    assert _get(ol, "meta-obj") == payload  # warm: stores the FileInfo

    from minio_tpu.objectlayer import erasure_object as eo

    def boom(*a, **kw):
        raise AssertionError("sidecar hit must not fan out xl.meta reads")

    monkeypatch.setattr(eo, "read_all_fileinfo", boom)
    assert _get(ol, "meta-obj") == payload  # fully cached: meta + groups
    # version-pinned reads never use the side-car
    with pytest.raises(AssertionError):
        _get(ol, "meta-obj", version_id="null")
    # invalidation drops the side-car entry too: the next GET needs the
    # (now broken) quorum read again
    rcache.invalidate_local("bucket", "meta-obj")
    with pytest.raises(AssertionError):
        _get(ol, "meta-obj")


def test_update_object_meta_invalidates_sidecar(cache_env, layer):
    ol, _disks = layer
    cache_env("host")
    payload = _payload(16_000, seed=32)
    ol.put_object("bucket", "tagged", io.BytesIO(payload), len(payload))
    assert _get(ol, "tagged") == payload
    ol.update_object_meta(
        "bucket", "tagged", {"x-amz-tagging": "team=storage"}
    )
    buf = io.BytesIO()
    info = ol.get_object("bucket", "tagged", buf)
    assert buf.getvalue() == payload
    assert info.user_defined.get("x-amz-tagging") == "team=storage"


def test_meta_sidecar_off_mode_untouched(cache_env, layer):
    ol, _disks = layer
    cache_env("off")
    payload = _payload(16_000, seed=33)
    ol.put_object("bucket", "plain", io.BytesIO(payload), len(payload))
    assert _get(ol, "plain") == payload
    assert rcache.read_cache() is None
