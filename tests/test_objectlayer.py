"""ErasureObjects tests over real temp-dir disks.

The reference's ObjectLayer suite style (object-api-*_test.go,
object_api_suite_test.go): put/get/delete/list across sizes, overwrite,
offline disks, healing, quorum failures.
"""

import io
import os
import shutil

import numpy as np
import pytest

from minio_tpu.objectlayer import api
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.storage.xl import XLStorage

BLOCK = 4096  # small block size keeps tests fast


@pytest.fixture
def setup(tmp_path):
    disks = [XLStorage(str(tmp_path / f"disk{i}")) for i in range(6)]
    ol = ErasureObjects(disks, block_size=BLOCK)
    ol.make_bucket("bucket")
    return ol, disks


def _payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8
    ).tobytes()


def _get(ol, bucket, name, **kw):
    buf = io.BytesIO()
    info = ol.get_object(bucket, name, buf, **kw)
    return buf.getvalue(), info


def test_bucket_lifecycle(setup):
    ol, _ = setup
    ol.make_bucket("second")
    assert {b.name for b in ol.list_buckets()} >= {"bucket", "second"}
    with pytest.raises(api.BucketExists):
        ol.make_bucket("bucket")
    with pytest.raises(api.InvalidBucketName):
        ol.make_bucket("X")
    ol.delete_bucket("second")
    with pytest.raises(api.BucketNotFound):
        ol.get_bucket_info("second")


@pytest.mark.parametrize(
    "size", [0, 1, 100, BLOCK, BLOCK + 1, 3 * BLOCK + 17, 10 * BLOCK]
)
def test_put_get_roundtrip(setup, size):
    ol, _ = setup
    payload = _payload(size, seed=size)
    info = ol.put_object("bucket", f"obj-{size}", io.BytesIO(payload), size)
    assert info.size == size
    import hashlib

    assert info.etag == hashlib.md5(payload).hexdigest()
    got, ginfo = _get(ol, "bucket", f"obj-{size}")
    assert got == payload
    assert ginfo.size == size
    assert ginfo.etag == info.etag


def test_range_get(setup):
    ol, _ = setup
    payload = _payload(3 * BLOCK + 100, seed=1)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    for off, ln in [(0, 10), (BLOCK - 1, 2), (BLOCK, BLOCK), (100, 3 * BLOCK)]:
        got, _ = _get(ol, "bucket", "obj", offset=off, length=ln)
        assert got == payload[off : off + ln], (off, ln)
    with pytest.raises(api.InvalidRange):
        _get(ol, "bucket", "obj", offset=len(payload), length=10)


def test_overwrite_replaces_and_cleans(setup):
    ol, disks = setup
    ol.put_object("bucket", "obj", io.BytesIO(b"first"), 5)
    old = ol.get_object_info("bucket", "obj")
    ol.put_object("bucket", "obj", io.BytesIO(b"second!"), 7)
    got, info = _get(ol, "bucket", "obj")
    assert got == b"second!"
    # old data dirs removed on every disk (single data_dir remains)
    for d in disks:
        entries = [
            e for e in d.list_dir("bucket", "obj") if e.endswith("/")
        ]
        assert len(entries) == 1


def test_delete_object(setup):
    ol, _ = setup
    ol.put_object("bucket", "obj", io.BytesIO(b"x"), 1)
    ol.delete_object("bucket", "obj")
    with pytest.raises(api.ObjectNotFound):
        ol.get_object_info("bucket", "obj")
    with pytest.raises(api.ObjectNotFound):
        ol.delete_object("bucket", "obj")


def test_get_missing_object(setup):
    ol, _ = setup
    with pytest.raises(api.ObjectNotFound):
        _get(ol, "bucket", "nope")
    with pytest.raises(api.BucketNotFound):
        ol.get_object_info("nobucket", "x")


def test_read_with_offline_disks(setup):
    ol, disks = setup
    payload = _payload(2 * BLOCK + 5, seed=2)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    # take 2 disks offline (parity = 3 for 6 disks)
    ol.disks[0] = None
    ol.disks[3] = None
    got, _ = _get(ol, "bucket", "obj")
    assert got == payload


def test_write_with_offline_disk(setup):
    ol, disks = setup
    ol.disks[5] = None
    payload = _payload(BLOCK, seed=3)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    ol.disks[5] = disks[5]  # back online; read works regardless
    got, _ = _get(ol, "bucket", "obj")
    assert got == payload


def test_write_quorum_failure(setup):
    ol, _ = setup
    for i in range(4):
        ol.disks[i] = None
    with pytest.raises(api.WriteQuorumError):
        ol.put_object("bucket", "obj", io.BytesIO(b"data"), 4)


def test_read_quorum_failure(setup):
    ol, disks = setup
    payload = _payload(100, seed=4)
    ol.put_object("bucket", "obj", io.BytesIO(payload), 100)
    for i in range(4):
        ol.disks[i] = None
    with pytest.raises((api.ReadQuorumError, api.ObjectNotFound)):
        _get(ol, "bucket", "obj")


def test_copy_object(setup):
    ol, _ = setup
    payload = _payload(BLOCK + 7, seed=5)
    ol.put_object(
        "bucket", "src", io.BytesIO(payload), len(payload),
        {"content-type": "app/x"},
    )
    info = ol.copy_object("bucket", "src", "bucket", "dst")
    got, ginfo = _get(ol, "bucket", "dst")
    assert got == payload
    assert ginfo.content_type == "app/x"


def test_list_objects(setup):
    ol, _ = setup
    for name in ["a/1.txt", "a/2.txt", "b/3.txt", "top.txt"]:
        ol.put_object("bucket", name, io.BytesIO(b"x"), 1)
    res = ol.list_objects("bucket")
    assert [o.name for o in res.objects] == [
        "a/1.txt", "a/2.txt", "b/3.txt", "top.txt",
    ]
    # delimiter groups prefixes
    res = ol.list_objects("bucket", delimiter="/")
    assert res.prefixes == ["a/", "b/"]
    assert [o.name for o in res.objects] == ["top.txt"]
    # prefix + delimiter
    res = ol.list_objects("bucket", prefix="a/", delimiter="/")
    assert [o.name for o in res.objects] == ["a/1.txt", "a/2.txt"]
    # pagination: next_marker is the LAST key of the page (S3 semantics)
    res = ol.list_objects("bucket", max_keys=2)
    assert res.is_truncated and len(res.objects) == 2
    assert res.next_marker == res.objects[-1].name
    res2 = ol.list_objects("bucket", marker=res.next_marker, max_keys=10)
    assert not res2.is_truncated
    assert [o.name for o in res.objects] + [o.name for o in res2.objects] == [
        "a/1.txt", "a/2.txt", "b/3.txt", "top.txt",
    ]


def test_heal_object_missing_disk(setup, tmp_path):
    ol, disks = setup
    payload = _payload(2 * BLOCK + 9, seed=6)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    # wipe disk 2 entirely (fresh-disk scenario)
    shutil.rmtree(disks[2].root)
    os.makedirs(os.path.join(disks[2].root, ".sys", "tmp"))
    disks[2].make_vol("bucket")
    res = ol.heal_object("bucket", "obj")
    assert res["healed"], res
    # now read with all other copies of that shard offline to prove the
    # healed shard is real: take 3 other disks offline (parity=3)
    others = [i for i in range(6) if i != 2][:3]
    for i in others:
        ol.disks[i] = None
    got, _ = _get(ol, "bucket", "obj")
    assert got == payload


def test_heal_object_bitrot(setup):
    ol, disks = setup
    payload = _payload(BLOCK * 2, seed=7)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    fi = disks[1].read_version("bucket", "obj")
    shard_path = os.path.join(
        disks[1].root, "bucket", "obj", fi.data_dir, "part.1"
    )
    with open(shard_path, "r+b") as f:
        f.seek(40)
        f.write(b"\xde\xad")
    res = ol.heal_object("bucket", "obj")
    assert res["healed"] == res["outdated"] and res["healed"]
    # verify the healed file passes a deep scan
    disks[1].verify_file("bucket", "obj", fi)


def test_storage_info(setup):
    ol, _ = setup
    si = ol.storage_info()
    assert si["disks"] == 6 and si["online"] == 6
    assert si["data"] == 3 and si["parity"] == 3


# ---------------------------------------------------------------------------
# get_object_n_info: one read for the headers and the body
# ---------------------------------------------------------------------------


def _erasure_layer(tmp_path):
    disks = [XLStorage(str(tmp_path / f"n{i}")) for i in range(4)]
    return ErasureObjects(disks, block_size=BLOCK)


def _fs_layer(tmp_path):
    from minio_tpu.objectlayer.fs import FSObjects

    # overrides nothing: ObjectLayer's default, info then get_object
    return FSObjects(str(tmp_path / "drive"))


@pytest.mark.parametrize("make", [_erasure_layer, _fs_layer])
def test_get_object_n_info_is_info_plus_get(tmp_path, make):
    ol = make(tmp_path)
    ol.make_bucket("bucket")
    payload = _payload(3 * BLOCK + 17, seed=5)
    ol.put_object("bucket", "obj", io.BytesIO(payload), len(payload))
    info = ol.get_object_info("bucket", "obj")
    with ol.get_object_n_info("bucket", "obj") as reader:
        assert reader.info == info
        whole, part = io.BytesIO(), io.BytesIO()
        assert reader.stream(whole).etag == info.etag
        reader.stream(part, BLOCK - 3, BLOCK + 9)
        with pytest.raises(api.InvalidRange):
            reader.stream(io.BytesIO(), len(payload), 1)
    assert whole.getvalue() == payload
    assert part.getvalue() == payload[BLOCK - 3 : 2 * BLOCK + 6]
    with pytest.raises(api.ObjectNotFound):
        ol.get_object_n_info("bucket", "absent")
    with pytest.raises(api.BucketNotFound):
        ol.get_object_n_info("nobucket", "obj")


def test_n_info_holds_the_read_lock_until_closed(setup):
    """An overwrite waits for an open reader and goes through once it is
    closed; a failed open leaves nothing held."""
    import threading

    ol, _ = setup
    ol.put_object("bucket", "obj", io.BytesIO(b"old"), 3)
    reader = ol.get_object_n_info("bucket", "obj")
    done = threading.Event()

    def overwrite():
        ol.put_object("bucket", "obj", io.BytesIO(b"newer"), 5)
        done.set()

    t = threading.Thread(target=overwrite, daemon=True)
    t.start()
    assert not done.wait(0.3)  # the writer is behind the reader's lock
    buf = io.BytesIO()
    reader.stream(buf)  # no second lock behind the waiting writer
    assert buf.getvalue() == b"old" and reader.info.size == 3
    reader.close()
    reader.close()  # idempotent
    assert done.wait(10)
    with pytest.raises(api.ObjectNotFound):
        ol.get_object_n_info("bucket", "absent")
    assert not ol.nslock._locks  # every lock given back
