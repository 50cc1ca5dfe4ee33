"""ErasureSets/Zones routing, format.json bootstrap, ellipses expansion.

Mirrors prepareErasureSets32-style layouts (test-utils_test.go:185-202)
scaled down to temp dirs.
"""

import io

import numpy as np
import pytest

from minio_tpu.objectlayer import api, format as fmt
from minio_tpu.objectlayer.sets import ErasureSets, crc_hash_mod
from minio_tpu.objectlayer.zones import ErasureZones
from minio_tpu.storage import errors as serrors
from minio_tpu.storage.xl import XLStorage
from minio_tpu.utils import ellipses

BLOCK = 2048


def _disks(tmp_path, n, prefix="d"):
    return [XLStorage(str(tmp_path / f"{prefix}{i}")) for i in range(n)]


# ---------------------------------------------------------------------------
# ellipses
# ---------------------------------------------------------------------------


def test_ellipses_expand():
    assert ellipses.expand("/tmp/disk{1...4}") == [
        "/tmp/disk1", "/tmp/disk2", "/tmp/disk3", "/tmp/disk4",
    ]
    got = ellipses.expand("http://h{1...2}/d{1...2}")
    assert got == [
        "http://h1/d1", "http://h1/d2", "http://h2/d1", "http://h2/d2",
    ]
    assert ellipses.expand("/plain") == ["/plain"]
    # zero-padded
    assert ellipses.expand("d{01...03}") == ["d01", "d02", "d03"]
    with pytest.raises(ValueError):
        ellipses.expand("d{5...2}")


def test_set_layout_math():
    assert ellipses.layout(4) == (1, 4)
    assert ellipses.layout(16) == (1, 16)
    assert ellipses.layout(32) == (2, 16)
    assert ellipses.layout(20) == (2, 10)
    assert ellipses.layout(18) == (2, 9)
    with pytest.raises(ValueError):
        ellipses.layout(17)


# ---------------------------------------------------------------------------
# format.json
# ---------------------------------------------------------------------------


def test_format_fresh_and_reload(tmp_path):
    disks = _disks(tmp_path, 8)
    ref, ordered = fmt.load_or_init_format(disks, 2, 4)
    assert len(ref.sets) == 2 and len(ref.sets[0]) == 4
    assert all(d is not None for d in ordered)
    # reload keeps identity and ordering even when args are shuffled
    shuffled = list(reversed(disks))
    ref2, ordered2 = fmt.load_or_init_format(shuffled, 2, 4)
    assert ref2.id == ref.id
    assert [d.root for d in ordered2] == [d.root for d in ordered]


def test_format_detects_foreign_disk(tmp_path):
    disks = _disks(tmp_path, 4)
    fmt.load_or_init_format(disks, 1, 4)
    other = _disks(tmp_path, 4, prefix="x")
    fmt.load_or_init_format(other, 1, 4)
    mixed = disks[:3] + [other[0]]
    with pytest.raises(serrors.InconsistentDisk):
        fmt.load_or_init_format(mixed, 1, 4)


def test_format_heals_fresh_disk_into_hole(tmp_path):
    disks = _disks(tmp_path, 4)
    ref, ordered = fmt.load_or_init_format(disks, 1, 4)
    # wipe disk 2's format (fresh replacement drive)
    import os, shutil

    shutil.rmtree(disks[2].root)
    os.makedirs(os.path.join(disks[2].root, ".sys", "tmp"))
    ref2, ordered2 = fmt.load_or_init_format(disks, 1, 4)
    assert ref2.id == ref.id
    assert all(d is not None for d in ordered2)
    # replacement got the hole's uuid
    assert fmt.read_format(disks[2]).this in ref.sets[0]


def test_format_layout_mismatch(tmp_path):
    disks = _disks(tmp_path, 4)
    fmt.load_or_init_format(disks, 1, 4)
    with pytest.raises(serrors.CorruptedFormat):
        fmt.load_or_init_format(disks, 2, 2)


# ---------------------------------------------------------------------------
# sets
# ---------------------------------------------------------------------------


@pytest.fixture
def sets(tmp_path):
    disks = _disks(tmp_path, 8)
    s = ErasureSets(disks, 2, 4, block_size=BLOCK)
    s.make_bucket("bucket")
    return s


def test_sets_routing_spreads(sets):
    keys = [f"obj-{i}" for i in range(40)]
    assert {crc_hash_mod(k, 2) for k in keys} == {0, 1}
    for k in keys:
        sets.put_object("bucket", k, io.BytesIO(b"v" + k.encode()), -1)
    # each object lives only in its routed set
    for k in keys:
        routed = sets.set_for(k)
        other = sets.sets[1 - sets.sets.index(routed)]
        assert routed.get_object_info("bucket", k).name == k
        with pytest.raises(api.ObjectNotFound):
            other.get_object_info("bucket", k)
    # full listing merges both sets in order
    res = sets.list_objects("bucket", max_keys=1000)
    assert [o.name for o in res.objects] == sorted(keys)


def test_sets_roundtrip_and_delete(sets):
    payload = np.random.default_rng(1).integers(
        0, 256, 3 * BLOCK, dtype=np.uint8
    ).tobytes()
    sets.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    buf = io.BytesIO()
    sets.get_object("bucket", "obj", buf)
    assert buf.getvalue() == payload
    sets.delete_object("bucket", "obj")
    with pytest.raises(api.ObjectNotFound):
        sets.get_object_info("bucket", "obj")


def test_sets_cross_set_copy(sets):
    # find two keys landing in different sets
    k1 = "obj-a"
    k2 = next(
        f"x{i}"
        for i in range(100)
        if crc_hash_mod(f"x{i}", 2) != crc_hash_mod(k1, 2)
    )
    sets.put_object("bucket", k1, io.BytesIO(b"payload"), 7)
    sets.copy_object("bucket", k1, "bucket", k2)
    buf = io.BytesIO()
    sets.get_object("bucket", k2, buf)
    assert buf.getvalue() == b"payload"


def test_sets_multipart_routes(sets):
    uid = sets.new_multipart_upload("bucket", "mp-obj", {})
    from minio_tpu.objectlayer.api import CompletePart

    pi = sets.put_object_part(
        "bucket", "mp-obj", uid, 1, io.BytesIO(b"part"), 4
    )
    sets.complete_multipart_upload(
        "bucket", "mp-obj", uid, [CompletePart(1, pi.etag)]
    )
    buf = io.BytesIO()
    sets.get_object("bucket", "mp-obj", buf)
    assert buf.getvalue() == b"part"


# ---------------------------------------------------------------------------
# zones
# ---------------------------------------------------------------------------


@pytest.fixture
def zones(tmp_path):
    z1 = ErasureSets(_disks(tmp_path, 4, "z1d"), 1, 4, block_size=BLOCK)
    z2 = ErasureSets(_disks(tmp_path, 4, "z2d"), 1, 4, block_size=BLOCK)
    z = ErasureZones([z1, z2])
    z.make_bucket("bucket")
    return z


def test_zones_put_get_overwrite_stays(zones):
    zones.put_object("bucket", "obj", io.BytesIO(b"v1"), 2)
    home = next(
        i
        for i, zz in enumerate(zones.zones)
        if _has(zz, "bucket", "obj")
    )
    # overwrite must stay in the same zone
    zones.put_object("bucket", "obj", io.BytesIO(b"v2-longer"), 9)
    assert _has(zones.zones[home], "bucket", "obj")
    assert not _has(zones.zones[1 - home], "bucket", "obj")
    buf = io.BytesIO()
    zones.get_object("bucket", "obj", buf)
    assert buf.getvalue() == b"v2-longer"
    zones.delete_object("bucket", "obj")
    with pytest.raises(api.ObjectNotFound):
        zones.get_object_info("bucket", "obj")


def _has(zone, bucket, obj) -> bool:
    try:
        zone.get_object_info(bucket, obj)
        return True
    except Exception:  # noqa: BLE001
        return False


def test_zones_listing_merges(zones):
    for i in range(10):
        zones.put_object("bucket", f"k{i}", io.BytesIO(b"x"), 1)
    res = zones.list_objects("bucket")
    assert [o.name for o in res.objects] == sorted(f"k{i}" for i in range(10))


def test_zones_multipart_pinning(zones):
    from minio_tpu.objectlayer.api import CompletePart

    uid = zones.new_multipart_upload("bucket", "mp", {})
    assert "." in uid
    pi = zones.put_object_part("bucket", "mp", uid, 1, io.BytesIO(b"dd"), 2)
    zones.complete_multipart_upload(
        "bucket", "mp", uid, [CompletePart(1, pi.etag)]
    )
    buf = io.BytesIO()
    zones.get_object("bucket", "mp", buf)
    assert buf.getvalue() == b"dd"
    with pytest.raises(api.InvalidUploadID):
        zones.put_object_part("bucket", "mp", "9.bogus", 1, io.BytesIO(b""), 0)


# ---------------------------------------------------------------------------
# placement (erasure-zones.go:113-184 semantics)
# ---------------------------------------------------------------------------


def test_zones_placement_deterministic(zones):
    idx = [zones._put_zone_index("bucket", f"new-{i}", 100)
           for i in range(20)]
    # same keys -> same zones, every time (no randomness)
    assert idx == [zones._put_zone_index("bucket", f"new-{i}", 100)
                   for i in range(20)]
    # and with roughly equal free space both zones receive keys
    assert set(idx) == {0, 1}


def test_zones_placement_skips_full_zone(zones, monkeypatch):
    # zone 0 reports no headroom: everything must land in zone 1
    snap = [(10, 1000), (10**9, 2 * 10**9)]
    monkeypatch.setattr(zones, "_usage_snapshot", lambda: snap)
    for i in range(10):
        assert zones._put_zone_index("bucket", f"full-{i}", 100) == 1
    # too-big object for every zone: falls back to most-free zone
    assert zones._put_zone_index("bucket", "huge", 10**12) == 1


def _stream_n_info(z, bucket, key):
    with z.get_object_n_info(bucket, key) as reader:
        buf = io.BytesIO()
        reader.stream(buf)
        return reader.info, buf.getvalue()


# what a single zone must do without asking who owns the key: each call,
# and how many times it may reach the zone's get_object_info
_SINGLE_ZONE_CALLS = {
    "place": (lambda z: z._put_zone_index("bucket", "obj", 5), 0),
    "get_object_info": (lambda z: z.get_object_info("bucket", "obj"), 1),
    "get_object": (
        lambda z: z.get_object("bucket", "obj", io.BytesIO()), 0,
    ),
    "get_object_n_info": (lambda z: _stream_n_info(z, "bucket", "obj"), 0),
    "update_object_meta": (
        lambda z: z.update_object_meta("bucket", "obj", {"k": "v"}), 0,
    ),
    "device_scan_source": (
        lambda z: z.device_scan_source("bucket", "obj"), 0,
    ),
    "delete_object": (lambda z: z.delete_object("bucket", "obj"), 0),
}


@pytest.mark.parametrize("call", sorted(_SINGLE_ZONE_CALLS))
def test_zones_single_zone_no_probe(tmp_path, call):
    z1 = ErasureSets(_disks(tmp_path, 4, "sz"), 1, 4, block_size=BLOCK)
    z = ErasureZones([z1])
    z.make_bucket("bucket")
    z.put_object("bucket", "obj", io.BytesIO(b"12345"), 5)
    calls = []
    orig = z1.get_object_info
    z1.get_object_info = lambda *a, **k: (calls.append(a), orig(*a, **k))[1]
    fn, probes = _SINGLE_ZONE_CALLS[call]
    fn(z)
    # single-zone placement never stats, and no read or delete probes
    # for an owner there is no choice of
    assert len(calls) == probes


class _CountingDisk(XLStorage):
    """A drive that counts the xl.meta reads it serves."""

    reads = 0

    def read_version(self, *a, **kw):
        self.reads += 1
        return super().read_version(*a, **kw)


def _counting_zone(tmp_path, prefix):
    disks = [
        _CountingDisk(str(tmp_path / f"{prefix}{i}")) for i in range(4)
    ]
    return ErasureSets(disks, 1, 4, block_size=BLOCK), disks


def _rounds(disks) -> "list[int]":
    return [d.reads for d in disks]


_BODY = b"x" * (3 * BLOCK + 17)

# one served operation each; every one must read xl.meta from each drive
# of the set exactly once
_ONE_ROUND_CALLS = {
    "get_object_info": lambda z: z.get_object_info("bucket", "obj"),
    "get_object_n_info": lambda z: _stream_n_info(z, "bucket", "obj"),
    "get_object": lambda z: z.get_object("bucket", "obj", io.BytesIO()),
    "get_object_range": lambda z: z.get_object(
        "bucket", "obj", io.BytesIO(), 5, BLOCK
    ),
    "delete_object": lambda z: z.delete_object("bucket", "obj"),
    "put_object_over": lambda z: z.put_object(
        "bucket", "obj", io.BytesIO(b"new"), 3
    ),
    "update_object_meta": lambda z: z.update_object_meta(
        "bucket", "obj", {"x-amz-tagging": "a=b"}
    ),
}


@pytest.mark.parametrize("call", sorted(_ONE_ROUND_CALLS))
def test_single_zone_one_metadata_round_per_call(tmp_path, call):
    z1, disks = _counting_zone(tmp_path, "cz")
    z = ErasureZones([z1])
    z.make_bucket("bucket")
    z.put_object("bucket", "obj", io.BytesIO(_BODY), len(_BODY))
    before = _rounds(disks)
    _ONE_ROUND_CALLS[call](z)
    assert [a - b for a, b in zip(_rounds(disks), before)] == [1] * 4


def test_n_info_streams_what_it_read(tmp_path):
    z1, _disks_ = _counting_zone(tmp_path, "nz")
    z = ErasureZones([z1])
    z.make_bucket("bucket")
    put = z.put_object("bucket", "obj", io.BytesIO(_BODY), len(_BODY))
    info, body = _stream_n_info(z, "bucket", "obj")
    assert (info.etag, info.size) == (put.etag, len(_BODY))
    assert body == _BODY
    with z.get_object_n_info("bucket", "obj") as reader:
        buf = io.BytesIO()
        reader.stream(buf, 7, BLOCK + 1)
        assert buf.getvalue() == _BODY[7 : 7 + BLOCK + 1]
    with pytest.raises(api.ObjectNotFound):
        z.get_object_n_info("bucket", "absent")


# with two zones and the object in the second, the first zone misses once
# and the owner is read once: never a third round
_TWO_ZONE_CALLS = {
    k: _ONE_ROUND_CALLS[k]
    for k in ("get_object_info", "get_object_n_info", "get_object")
}


@pytest.mark.parametrize("call", sorted(_TWO_ZONE_CALLS))
def test_two_zones_one_round_in_each(tmp_path, call):
    z1, d1 = _counting_zone(tmp_path, "ta")
    z2, d2 = _counting_zone(tmp_path, "tb")
    z = ErasureZones([z1, z2])
    z.make_bucket("bucket")
    z2.put_object("bucket", "obj", io.BytesIO(_BODY), len(_BODY))
    before = _rounds(d1 + d2)
    _TWO_ZONE_CALLS[call](z)
    assert [a - b for a, b in zip(_rounds(d1 + d2), before)] == [1] * 8


def test_zones_usage_snapshot_cached(zones):
    zones._put_zone_index("bucket", "warm", 1)
    stamped = zones._usage_ts
    for i in range(5):
        zones._put_zone_index("bucket", f"c{i}", 1)
    assert zones._usage_ts == stamped  # no re-stat within the TTL
