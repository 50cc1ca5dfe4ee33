"""Disk cache layer (cmd/disk-cache.go): read-through caching,
etag invalidation, LRU GC at watermarks."""

import io
import os

import pytest

from minio_tpu.objectlayer.cache import CacheObjectLayer
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.storage.xl import XLStorage


@pytest.fixture()
def layers(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    backend = ErasureObjects(disks, block_size=4096, min_part_size=1)
    cache = CacheObjectLayer(
        backend,
        [str(tmp_path / "cache0"), str(tmp_path / "cache1")],
        quota_bytes=1 << 20,
    )
    cache.make_bucket("bkt")
    return backend, cache


def _get(layer, key, **kw):
    buf = io.BytesIO()
    layer.get_object("bkt", key, buf, **kw)
    return buf.getvalue()


def _get_n_info(layer, key, **kw):
    """The served GET's way in: it must not slip past the cache to the
    backend's own reader."""
    buf = io.BytesIO()
    with layer.get_object_n_info("bkt", key) as reader:
        reader.stream(buf, **kw)
    return buf.getvalue()


@pytest.mark.parametrize("get", [_get, _get_n_info])
def test_read_through_and_hit(layers, get):
    backend, cache = layers
    data = os.urandom(9000)
    cache.put_object("bkt", "obj", io.BytesIO(data), len(data))
    assert get(cache, "obj") == data  # miss: populates
    assert cache.misses == 1 and cache.hits == 0
    assert get(cache, "obj") == data  # hit
    assert cache.hits == 1
    # range served from the cached whole object
    assert get(cache, "obj", offset=100, length=50) == data[100:150]
    assert cache.hits == 2


@pytest.mark.parametrize("get", [_get, _get_n_info])
def test_overwrite_invalidates(layers, get):
    backend, cache = layers
    cache.put_object("bkt", "obj", io.BytesIO(b"v1-data!"), 8)
    assert get(cache, "obj") == b"v1-data!"
    assert get(cache, "obj") == b"v1-data!"
    cache.put_object("bkt", "obj", io.BytesIO(b"v2-data!"), 8)
    assert get(cache, "obj") == b"v2-data!"  # not the stale v1


def test_stale_etag_detected_even_without_invalidate(layers):
    """Backend changed behind the cache's back (another node wrote):
    the etag check refuses the stale entry."""
    backend, cache = layers
    cache.put_object("bkt", "obj", io.BytesIO(b"first!!!"), 8)
    _get(cache, "obj")
    hits_before = cache.hits
    # write through the BACKEND directly - cache unaware
    backend.put_object("bkt", "obj", io.BytesIO(b"second!!"), 8)
    assert _get(cache, "obj") == b"second!!"
    assert cache.hits == hits_before  # stale entry did not serve


def test_delete_invalidates(layers):
    backend, cache = layers
    cache.put_object("bkt", "obj", io.BytesIO(b"bye"), 3)
    _get(cache, "obj")
    cache.delete_object("bkt", "obj")
    from minio_tpu.objectlayer.api import ObjectNotFound

    with pytest.raises(ObjectNotFound):
        _get(cache, "obj")


def test_lru_gc_evicts_oldest(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    backend = ErasureObjects(disks, block_size=4096, min_part_size=1)
    quota = 100_000
    cache = CacheObjectLayer(
        backend, [str(tmp_path / "c0")], quota_bytes=quota
    )
    cache.make_bucket("bkt")
    # each object ~20k stored; high watermark 80k
    import time

    for i in range(6):
        data = os.urandom(20_000)
        cache.put_object("bkt", f"o{i}", io.BytesIO(data), len(data))
        _get(cache, f"o{i}")
        time.sleep(0.01)  # distinct atimes
    drive = cache.drives[0]
    assert drive.used <= quota * 0.80 + 20_000
    # oldest entries evicted, newest survive
    assert drive.get("bkt", "o5") is not None
    assert drive.get("bkt", "o0") is None


def test_huge_objects_not_cached(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    backend = ErasureObjects(disks, block_size=4096, min_part_size=1)
    cache = CacheObjectLayer(
        backend, [str(tmp_path / "c0")], quota_bytes=50_000
    )
    cache.make_bucket("bkt")
    big = os.urandom(30_000)  # > 25% of quota
    cache.put_object("bkt", "big", io.BytesIO(big), len(big))
    assert _get(cache, "big") == big
    assert cache.drives[0].get("bkt", "big") is None  # skipped
    assert _get(cache, "big") == big  # still correct, direct


def test_cached_range_validation_matches_backend(layers):
    """Out-of-range reads on a CACHED object raise InvalidRange like
    the backend does (code-review r4: short-body divergence)."""
    backend, cache = layers
    cache.put_object("bkt", "small", io.BytesIO(b"0123456789"), 10)
    _get(cache, "small")  # populate
    from minio_tpu.objectlayer.api import InvalidRange

    with pytest.raises(InvalidRange):
        _get(cache, "small", offset=5, length=20)
    with pytest.raises(InvalidRange):
        _get(cache, "small", offset=11)


def test_passthrough_methods(layers):
    backend, cache = layers
    # unknown attributes delegate (listing, info, storage)
    cache.put_object("bkt", "listed", io.BytesIO(b"x"), 1)
    res = cache.list_objects("bkt")
    assert "listed" in [o.name for o in res.objects]
    assert cache.storage_info()["disks"] == 4


def test_repopulate_does_not_double_count(layers):
    """Refreshing a stale entry in place must swap its bytes in the
    accounting, not add them again (review r4)."""
    backend, cache = layers
    drive_used = lambda: sum(d.used for d in cache.drives)
    data = os.urandom(4000)
    cache.put_object("bkt", "obj", io.BytesIO(data), len(data))
    _get(cache, "obj")  # populate
    base = drive_used()
    # mutate the backend BEHIND the cache (as another node would)
    backend.put_object("bkt", "obj", io.BytesIO(data[::-1]), len(data))
    for _ in range(5):
        _get(cache, "obj")  # etag mismatch -> repopulate each time?
    # only one copy of the object may ever be accounted
    assert drive_used() == base


def test_concurrent_hits_no_meta_race(layers):
    """The read path must not rewrite meta.json (a truncate+write
    races other readers into spurious misses)."""
    import threading

    backend, cache = layers
    data = os.urandom(6000)
    cache.put_object("bkt", "obj", io.BytesIO(data), len(data))
    _get(cache, "obj")  # populate
    errs = []

    def reader():
        try:
            for _ in range(30):
                assert _get(cache, "obj") == data
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=reader) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert cache.misses == 1  # every later read was a clean hit
