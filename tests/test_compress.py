"""Transparent compression (the S2 seam: object-api-utils.go:434
isCompressible, :686 decompress+skip range reads).
"""

import io
import os

import numpy as np
import pytest

from minio_tpu.codec import compress as compmod
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.storage.xl import XLStorage

BLOCK = 64 << 10


def _compressible(size, seed=0):
    """Low-entropy payload that deflate actually shrinks."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 16, size // 8 + 1, dtype=np.uint8)
    return bytes(words.repeat(8))[:size]


@pytest.fixture()
def layer(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=BLOCK)
    ol.make_bucket("zip")
    return ol


def test_is_compressible_rules():
    ok = compmod.is_compressible
    assert ok("logs/app.log", "text/plain", 1 << 20)
    assert ok("data.csv", "", 1 << 20)
    # excluded extension / content types
    assert not ok("movie.mp4", "", 1 << 30)
    assert not ok("photo.JPG", "", 1 << 20)
    assert not ok("x.bin", "video/mp4", 1 << 20)
    assert not ok("x.bin", "application/zip", 1 << 20)
    # too small to bother
    assert not ok("tiny.txt", "text/plain", 100)
    # unknown size (streaming) is assumed compressible
    assert ok("stream.txt", "text/plain", -1)


def test_roundtrip_and_stored_smaller(layer):
    size = 2 << 20
    data = _compressible(size, seed=1)
    info = layer.put_object(
        "zip", "doc", io.BytesIO(data), size, compress=True
    )
    assert info.size == size  # client-visible size is the original
    import hashlib

    assert info.etag == hashlib.md5(data).hexdigest()
    # stored representation is the deflate stream (smaller on disk)
    fi, _ = layer._read_quorum_fileinfo("zip", "doc")
    assert fi.metadata[compmod.META_COMPRESSION] == compmod.ALGORITHM
    assert fi.size < size // 2
    assert fi.parts[0].actual_size == size
    # reads decompress transparently
    out = io.BytesIO()
    ginfo = layer.get_object("zip", "doc", out)
    assert out.getvalue() == data
    assert ginfo.size == size
    # info path reports the original size too
    assert layer.get_object_info("zip", "doc").size == size


def test_range_reads_decompress_skip(layer):
    size = 1 << 20
    data = _compressible(size, seed=2)
    layer.put_object("zip", "rng", io.BytesIO(data), size, compress=True)
    for off, ln in [(0, 100), (12345, 54321), (size - 7, 7), (500000, 1)]:
        out = io.BytesIO()
        layer.get_object("zip", "rng", out, off, ln)
        assert out.getvalue() == data[off : off + ln], (off, ln)
    # invalid range is judged against the LOGICAL size
    from minio_tpu.objectlayer import api

    with pytest.raises(api.InvalidRange):
        layer.get_object("zip", "rng", io.BytesIO(), size - 1, 10)


def test_listing_reports_actual_size(layer):
    size = 1 << 20
    data = _compressible(size, seed=3)
    layer.put_object("zip", "ls/obj", io.BytesIO(data), size, compress=True)
    res = layer.list_objects("zip", "ls/")
    assert res.objects[0].size == size


def test_copy_of_compressed_object(layer):
    """Copy reads plaintext; the new object must not carry stale
    compression markers over uncompressed stored data."""
    size = 1 << 20
    data = _compressible(size, seed=4)
    layer.put_object("zip", "c-src", io.BytesIO(data), size, compress=True)
    layer.copy_object("zip", "c-src", "zip", "c-dst")
    fi, _ = layer._read_quorum_fileinfo("zip", "c-dst")
    assert compmod.META_COMPRESSION not in fi.metadata
    out = io.BytesIO()
    layer.get_object("zip", "c-dst", out)
    assert out.getvalue() == data


def test_heal_compressed_object(layer, tmp_path):
    """Heal operates on stored bytes: rebuild a wiped shard and read
    back the decompressed payload."""
    import shutil

    size = 1 << 20
    data = _compressible(size, seed=5)
    layer.put_object("zip", "heal-me", io.BytesIO(data), size, compress=True)
    victim = layer.disks[1]
    shutil.rmtree(os.path.join(victim.root, "zip", "heal-me"))
    res = layer.heal_object("zip", "heal-me")
    assert res["healed"]
    out = io.BytesIO()
    layer.get_object("zip", "heal-me", out)
    assert out.getvalue() == data


def test_server_end_to_end_compression(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_COMPRESS", "on")
    import sys

    sys.path.insert(0, "tests")
    from minio_tpu.server.http import S3Server
    from s3client import S3Client

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=BLOCK)
    srv = S3Server(ol, address="127.0.0.1:0").start()
    try:
        c = S3Client(srv.endpoint)
        c.make_bucket("zipe2e")
        data = _compressible(512 << 10, seed=6)
        r = c.put_object(
            "zipe2e", "report.txt", data,
            headers={"content-type": "text/plain"},
        )
        assert r.status == 200
        g = c.get_object("zipe2e", "report.txt")
        assert g.body == data
        assert g.headers["content-length"] == str(len(data))
        # range request
        g = c.get_object(
            "zipe2e", "report.txt", headers={"Range": "bytes=100-299"}
        )
        assert g.status == 206 and g.body == data[100:300]
        # stored bytes on disk are compressed
        fi, _ = ol._read_quorum_fileinfo("zipe2e", "report.txt")
        assert fi.size < len(data)
        # excluded type stays raw
        r = c.put_object("zipe2e", "img.png", data)
        fi, _ = ol._read_quorum_fileinfo("zipe2e", "img.png")
        assert compmod.META_COMPRESSION not in fi.metadata
    finally:
        srv.shutdown()

def test_multipart_compression(layer, monkeypatch):
    """Parts are independent deflate streams; ranges that cross part
    boundaries splice the per-part decompressors seamlessly."""
    import hashlib

    monkeypatch.setenv("MINIO_TPU_COMPRESS", "on")
    layer.min_part_size = 64 << 10  # keep the test payload small
    psize = 128 << 10
    p1 = _compressible(psize, seed=10)
    p2 = _compressible(psize, seed=11)
    p3 = _compressible(32 << 10, seed=12)  # short last part
    data = p1 + p2 + p3
    uid = layer.new_multipart_upload(
        "zip", "mp/doc.txt", {"content-type": "text/plain"}
    )
    from minio_tpu.objectlayer.api import CompletePart

    cps = []
    for n, part in enumerate([p1, p2, p3], start=1):
        pi = layer.put_object_part(
            "zip", "mp/doc.txt", uid, n, io.BytesIO(part), len(part)
        )
        # ListParts/PartInfo report the plaintext size
        assert pi.size == len(part)
        cps.append(CompletePart(n, pi.etag))
    listed = layer.list_object_parts("zip", "mp/doc.txt", uid)
    assert [p.size for p in listed] == [len(p1), len(p2), len(p3)]
    info = layer.complete_multipart_upload("zip", "mp/doc.txt", uid, cps)
    assert info.size == len(data)
    # stored form is compressed
    fi, _ = layer._read_quorum_fileinfo("zip", "mp/doc.txt")
    assert fi.metadata[compmod.META_COMPRESSION] == compmod.ALGORITHM
    assert fi.size < len(data) // 2
    assert [p.actual_size for p in fi.parts] == [len(p1), len(p2), len(p3)]
    # full read
    out = io.BytesIO()
    layer.get_object("zip", "mp/doc.txt", out)
    assert out.getvalue() == data
    # ranges: inside part 2, crossing the p1/p2 boundary, suffix
    for off, ln in [
        (psize + 100, 5000),
        (psize - 50, 100),
        (len(data) - 17, 17),
    ]:
        out = io.BytesIO()
        layer.get_object("zip", "mp/doc.txt", out, off, ln)
        assert out.getvalue() == data[off : off + ln], (off, ln)
    # multipart ETag is md5-of-plaintext-part-md5s
    md5s = hashlib.md5(
        b"".join(bytes.fromhex(hashlib.md5(p).hexdigest()) for p in [p1, p2, p3])
    ).hexdigest()
    assert info.etag == f"{md5s}-3"


def test_zero_bomb_range_is_bounded(layer):
    """A tiny range read of a highly-inflating object must not
    materialize the decompressed tail (DecompressWriter.finish is a
    no-op once the range is satisfied)."""
    size = 8 << 20
    data = bytes(size)  # zeros: ~1000x deflate inflation ratio
    layer.put_object("zip", "bomb", io.BytesIO(data), size, compress=True)

    class MaxTracker:
        largest = 0
        total = 0

        def write(self, b):
            MaxTracker.largest = max(MaxTracker.largest, len(b))
            MaxTracker.total += len(b)

    layer.get_object("zip", "bomb", MaxTracker(), 100, 1000)
    assert MaxTracker.total == 1000
    # nothing close to the 8 MiB plaintext was ever materialized
    assert MaxTracker.largest <= 1 << 20


def test_range_read_still_flags_heal(layer):
    """Bitrot seen while serving a compressed range read must still
    raise the heal flag (the early RangeSatisfied exit may not lose
    the decode's verdict)."""
    import shutil

    size = 1 << 20
    data = _compressible(size, seed=13)
    layer.put_object("zip", "rot", io.BytesIO(data), size, compress=True)
    healed_keys = []
    layer.heal_hook = lambda b, o: healed_keys.append((b, o))
    victim = layer.disks[2]
    shutil.rmtree(os.path.join(victim.root, "zip", "rot"))
    out = io.BytesIO()
    info = layer.get_object("zip", "rot", out, 10, 100)
    assert out.getvalue() == data[10:110]
    assert info.user_defined.get("x-internal-heal-required") == "true"
    assert healed_keys == [("zip", "rot")]
