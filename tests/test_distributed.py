"""Distributed storage plane tests.

In-process: a StorageRESTClient against a live server's storage plane
must be indistinguishable from a local XLStorage (the reference relies
on this to make a cluster look like one big JBOD), and an erasure set
mixing local + remote disks must serve the full object API.

Multi-process: two real server processes on localhost sharing one
endpoint list (verify-healing.sh style), writes crossing the wire.
"""

import io
import os
import time

import numpy as np
import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.server.http import S3Server
from minio_tpu.storage import errors as serrors
from minio_tpu.storage.meta import FileInfo
from minio_tpu.storage.rest_client import StorageRESTClient
from minio_tpu.storage.rest_common import PREFIX as STORAGE_PREFIX
from minio_tpu.storage.rest_server import StorageRESTServer
from minio_tpu.storage.xl import XLStorage

from s3client import S3Client

SECRET = "minioadmin"
BLOCK = 4096


@pytest.fixture()
def remote_pair(tmp_path):
    """(local XLStorage, StorageRESTClient for the same dir over HTTP)."""
    root = str(tmp_path / "rdisk")
    local = XLStorage(root)
    srv = S3Server(None, address="127.0.0.1:0", secret_key=SECRET)
    srv.register_internode(
        STORAGE_PREFIX, StorageRESTServer([local], SECRET).handle
    )
    srv.start()
    client = StorageRESTClient("127.0.0.1", srv.port, root, SECRET)
    yield local, client
    srv.shutdown()


def test_remote_disk_parity(remote_pair):
    """Every StorageAPI op over the wire matches local semantics."""
    local, rc = remote_pair
    assert rc.is_online()
    assert not rc.is_local()

    rc.make_vol("vol")
    assert "vol" in [v.name for v in rc.list_vols()]
    rc.stat_vol("vol")
    with pytest.raises(serrors.VolumeNotFound):
        rc.stat_vol("nope")

    rc.write_all("vol", "cfg/x.bin", b"hello")
    assert rc.read_all("vol", "cfg/x.bin") == b"hello"
    assert local.read_all("vol", "cfg/x.bin") == b"hello"
    st = rc.stat_file("vol", "cfg/x.bin")
    assert st.size == 5
    with pytest.raises(serrors.FileNotFound):
        rc.read_all("vol", "cfg/nope")

    # shard stream: chunked append writes, random-access reads
    w = rc.create_file("vol", "obj/part.1")
    w.write(b"a" * 7000)
    w.write(b"b" * 5000)
    w.close()
    r = rc.read_file_stream("vol", "obj/part.1")
    assert r.read_at(0, 4) == b"aaaa"
    assert r.read_at(6999, 2) == b"ab"
    assert r.read_at(11998, 2) == b"bb"
    r.close()
    assert local.read_all("vol", "obj/part.1") == b"a" * 7000 + b"b" * 5000

    rc.rename_file("vol", "cfg/x.bin", "vol", "cfg/y.bin")
    assert rc.read_all("vol", "cfg/y.bin") == b"hello"
    rc.delete_file("vol", "cfg/y.bin")
    with pytest.raises(serrors.FileNotFound):
        rc.stat_file("vol", "cfg/y.bin")

    # xl.meta journal over the wire
    fi = FileInfo(
        volume="vol", name="meta-obj", version_id="", size=12,
        mod_time_ns=123456789, data_dir="dd1",
    )
    rc.write_metadata("vol", "meta-obj", fi)
    got = rc.read_version("vol", "meta-obj")
    assert got.size == 12 and got.data_dir == "dd1"
    assert list(rc.walk("vol")) == ["meta-obj"]

    rc.set_disk_id("disk-uuid-1")
    assert rc.get_disk_id() == "disk-uuid-1"

    info = rc.disk_info()
    assert info.total > 0

    rc.delete_vol("vol", force=True)
    with pytest.raises(serrors.VolumeNotFound):
        rc.stat_vol("vol")


def test_remote_disk_rejects_bad_jwt(remote_pair, tmp_path):
    local, rc = remote_pair
    bad = StorageRESTClient(
        "127.0.0.1", rc.port, local.root, "wrong-secret"
    )
    with pytest.raises(serrors.FaultyDisk):
        bad.make_vol("x")


def test_remote_disk_offline_detection(tmp_path):
    rc = StorageRESTClient("127.0.0.1", 1, str(tmp_path), SECRET)
    with pytest.raises(serrors.DiskNotFound):
        rc.read_all("v", "p")
    assert not rc.is_online()


@pytest.fixture()
def mixed_layer(tmp_path):
    """Erasure set of 4 disks: 2 local, 2 served over the REST plane."""
    locals_ = [XLStorage(str(tmp_path / f"l{i}")) for i in range(2)]
    remotes_backing = [
        XLStorage(str(tmp_path / f"r{i}")) for i in range(2)
    ]
    srv = S3Server(None, address="127.0.0.1:0", secret_key=SECRET)
    srv.register_internode(
        STORAGE_PREFIX, StorageRESTServer(remotes_backing, SECRET).handle
    )
    srv.start()
    remote_clients = [
        StorageRESTClient("127.0.0.1", srv.port, d.root, SECRET)
        for d in remotes_backing
    ]
    layer = ErasureObjects(
        locals_ + remote_clients, block_size=BLOCK, min_part_size=1,
    )
    yield layer, remotes_backing
    srv.shutdown()


def _pay(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8
    ).tobytes()


def test_mixed_local_remote_object_ops(mixed_layer):
    layer, remote_disks = mixed_layer
    layer.make_bucket("bkt")
    data = _pay(3 * BLOCK + 500, seed=1)
    info = layer.put_object("bkt", "obj", io.BytesIO(data), len(data))
    assert info.size == len(data)

    # shards really crossed the wire: remote disks hold part files
    found = [list(d.walk("bkt")) for d in remote_disks]
    assert all("obj" in f for f in found)

    out = io.BytesIO()
    layer.get_object("bkt", "obj", out)
    assert out.getvalue() == data

    # ranged read
    out = io.BytesIO()
    layer.get_object("bkt", "obj", out, offset=BLOCK, length=777)
    assert out.getvalue() == data[BLOCK : BLOCK + 777]

    # multipart across the wire
    uid = layer.new_multipart_upload("bkt", "mp", {})
    from minio_tpu.objectlayer.api import CompletePart

    p1 = layer.put_object_part(
        "bkt", "mp", uid, 1, io.BytesIO(data[:BLOCK]), BLOCK
    )
    p2 = layer.put_object_part(
        "bkt", "mp", uid, 2, io.BytesIO(data[BLOCK:]), len(data) - BLOCK
    )
    layer.complete_multipart_upload(
        "bkt", "mp", uid,
        [CompletePart(1, p1.etag), CompletePart(2, p2.etag)],
    )
    out = io.BytesIO()
    layer.get_object("bkt", "mp", out)
    assert out.getvalue() == data

    layer.delete_object("bkt", "obj")
    from minio_tpu.objectlayer import api as olapi

    with pytest.raises(olapi.ObjectNotFound):
        layer.get_object_info("bkt", "obj")


def test_mixed_layer_degraded_and_heal(mixed_layer, tmp_path):
    """Wipe a remote disk's data; reads survive, heal restores it."""
    layer, remote_disks = mixed_layer
    layer.make_bucket("hbk")
    data = _pay(2 * BLOCK + 99, seed=2)
    layer.put_object("hbk", "obj", io.BytesIO(data), len(data))

    # wipe one remote disk's copy entirely (simulates drive swap)
    import shutil

    victim = remote_disks[0]
    shutil.rmtree(os.path.join(victim.root, "hbk"))

    out = io.BytesIO()
    layer.get_object("hbk", "obj", out)
    assert out.getvalue() == data

    healed = layer.heal_object("hbk", "obj")
    assert healed
    # the remote disk has its shard again, readable through the layer
    assert "obj" in list(victim.walk("hbk"))
    out = io.BytesIO()
    layer.get_object("hbk", "obj", out)
    assert out.getvalue() == data


def test_local_volume_wipe_and_heal(tmp_path):
    """Wipe a bucket volume on a *local* disk (drive swap); heal_object
    must recreate the volume (heal_bucket / MakeVol semantics,
    erasure-healing.go:105) before rebuilding shards."""
    import shutil

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, block_size=BLOCK, min_part_size=1)
    layer.make_bucket("wbk")
    data = _pay(2 * BLOCK + 7, seed=5)
    layer.put_object("wbk", "obj", io.BytesIO(data), len(data))

    victim = disks[2]
    shutil.rmtree(os.path.join(victim.root, "wbk"))

    healed = layer.heal_object("wbk", "obj")
    assert healed["healed"]
    assert "obj" in list(victim.walk("wbk"))
    out = io.BytesIO()
    layer.get_object("wbk", "obj", out)
    assert out.getvalue() == data


def test_full_disk_wipe_and_heal(tmp_path):
    """Wipe an entire local disk (bucket volume AND .sys staging area);
    heal must restore both."""
    import shutil

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    layer = ErasureObjects(disks, block_size=BLOCK, min_part_size=1)
    layer.make_bucket("fbk")
    data = _pay(BLOCK + 31, seed=6)
    layer.put_object("fbk", "obj", io.BytesIO(data), len(data))

    victim = disks[1]
    for entry in os.listdir(victim.root):
        shutil.rmtree(os.path.join(victim.root, entry))

    healed = layer.heal_object("fbk", "obj")
    assert healed["healed"]
    assert "obj" in list(victim.walk("fbk"))
    out = io.BytesIO()
    layer.get_object("fbk", "obj", out)
    assert out.getvalue() == data


# -- multi-process cluster (spawned via the cluster harness) ---------------


def _thread(fn, *args):
    import threading

    t = threading.Thread(target=fn, args=args)
    t.start()
    return t


@pytest.mark.slow
def test_cross_node_put_race_serializes(tmp_path):
    """Two processes race PUTs to ONE object; dsync quorum locks must
    serialize them so every GET returns one writer's payload intact
    (never an interleaving or a quorum-broken object)."""
    from minio_tpu.cluster.harness import ClusterHarness

    with ClusterHarness(tmp_path, nodes=2, drives_per_node=2) as h:
        c1 = S3Client(h.nodes[0].endpoint)
        c2 = S3Client(h.nodes[1].endpoint)
        assert c1.make_bucket("race").status == 200

        pay_a = _pay(150_000, seed=10)
        pay_b = _pay(150_000, seed=11)
        for _ in range(4):
            results = {}

            def put(client, body, tag):
                results[tag] = client.put_object("race", "obj", body)

            ta = _thread(put, c1, pay_a, "a")
            tb = _thread(put, c2, pay_b, "b")
            ta.join(timeout=60)
            tb.join(timeout=60)
            assert results["a"].status == 200
            assert results["b"].status == 200
            r = c1.get_object("race", "obj")
            assert r.status == 200
            assert r.body in (pay_a, pay_b), "interleaved write!"


@pytest.mark.slow
def test_verify_healing_node_restart(tmp_path):
    """verify-healing.sh: write objects, kill a node, wipe one of its
    drives, restart it - the cluster must converge to fully healed with
    NO manual heal call (fresh-disk monitor + heal routine)."""
    import shutil

    from minio_tpu.cluster.harness import ClusterHarness

    with ClusterHarness(tmp_path, nodes=2, drives_per_node=2) as h:
        c1 = S3Client(h.nodes[0].endpoint)
        assert c1.make_bucket("vhb").status == 200
        objs = {f"obj{i}": _pay(50_000 + i, seed=20 + i) for i in range(3)}
        for name, data in objs.items():
            assert c1.put_object("vhb", name, data).status == 200

        # kill node2, wipe one of its drives (drive swap while down)
        h.kill(1)
        victim_root = h.nodes[1].drive_dirs[0]
        for entry in os.listdir(victim_root):
            shutil.rmtree(victim_root / entry)

        # restart node2 with the same endpoint list
        h.restart(1)

        # convergence: every object's shard reappears on the wiped
        # drive without any heal API call
        deadline = time.monotonic() + 60
        want = set(objs)
        while time.monotonic() < deadline:
            healed = {
                p.parent.parent.name
                for p in victim_root.glob("vhb/*/*/part.1")
            }
            if want <= healed:
                break
            time.sleep(0.5)
        else:
            raise AssertionError(
                f"never converged; healed={healed} want={want}"
            )
        # data still correct end-to-end from the restarted node
        c2 = S3Client(h.nodes[1].endpoint)
        for name, data in objs.items():
            r = c2.get_object("vhb", name)
            assert r.status == 200 and r.body == data


@pytest.mark.slow
def test_two_node_cluster(tmp_path):
    """verify-healing.sh style: 2 real server processes, one endpoint
    list, writes from one node readable from the other, degraded reads
    after a node dies."""
    from minio_tpu.cluster.harness import ClusterHarness

    with ClusterHarness(tmp_path, nodes=2, drives_per_node=2) as h:
        c1 = S3Client(h.nodes[0].endpoint)
        c2 = S3Client(h.nodes[1].endpoint)
        assert c1.make_bucket("dist").status == 200
        data = _pay(300_000, seed=3)
        assert c1.put_object("dist", "obj", data).status == 200

        # cross-node read: node2 must fetch node1's shards over the wire
        r = c2.get_object("dist", "obj")
        assert r.status == 200 and r.body == data

        # both nodes' drives hold shards
        for n in h.nodes:
            parts = [
                p
                for d in n.drive_dirs
                for p in d.glob("dist/obj/*/part.1")
            ]
            assert parts, f"no shards on node {n.index + 1}"

        # kill node2: node1 still serves reads (2/4 drives, k=2 met)
        h.kill(1)
        r = c1.get_object("dist", "obj")
        assert r.status == 200 and r.body == data

        # and writes fail cleanly without write quorum (2 < 3)
        r = c1.put_object("dist", "obj2", b"x" * 1000)
        assert r.status == 503


def test_remote_writer_retry_has_offsets(remote_pair, tmp_path):
    """RemoteShardWriter flushes carry explicit offsets so a blind
    transport retry cannot duplicate shard data."""
    local, rc = remote_pair
    local.make_vol("off")
    w = rc.create_file("off", "shard")
    w.write(b"x" * 10)
    w.close()
    assert local.read_all("off", "shard") == b"x" * 10
    # replaying the exact first flush (off=0, truncate) is idempotent
    rc._call(
        "appendfile",
        {"vol": "off", "path": "shard", "off": "0", "truncate": "1"},
        b"x" * 10,
    )
    assert local.read_all("off", "shard") == b"x" * 10


def test_internode_preauth_rejects_before_body(tmp_path):
    """An unauthenticated internode request is rejected from its headers
    alone - the server must not read (buffer) the declared body."""
    import http.client

    local = XLStorage(str(tmp_path / "pd"))
    srv = S3Server(
        None, address="127.0.0.1:0", secret_key=SECRET,
        internode_secret=SECRET,
    )
    srv.register_internode(
        STORAGE_PREFIX, StorageRESTServer([local], SECRET).handle
    )
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        # declare a 10 MiB body but send none: only a server that
        # answers WITHOUT reading the body can respond in time
        conn.putrequest("POST", f"{STORAGE_PREFIX}/diskinfo")
        conn.putheader("Content-Length", str(10 << 20))
        conn.putheader("Authorization", "Bearer bogus")
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 401
        conn.close()
        # an oversized body is rejected outright, authenticated or not
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        conn.putrequest("POST", f"{STORAGE_PREFIX}/diskinfo")
        conn.putheader("Content-Length", str(1 << 30))
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 413
        conn.close()
    finally:
        srv.shutdown()


# how the object lies on the remote drive -> (REMOVE's [named, walked] on
# the far side, error class on the near side)
REMOTE_REMOVALS = {
    "as-named": (None, True, [1, 0], None),
    "stray-file": ("leftover", True, [0, 1], None),
    "object-missing": (False, True, [0, 1], serrors.FileNotFound),
    "no-names-sent": (None, False, [0, 0], None),
}


@pytest.mark.parametrize("case", REMOTE_REMOVALS)
def test_remote_delete_file_carries_the_names(remote_pair, case):
    """``fi`` rides the one RPC of a removal, so the drive on the far side
    removes by name too; what it leaves and what the caller is told are
    the walk's."""
    from minio_tpu.storage import xl as xl_mod
    from minio_tpu.storage.meta import FileInfo, ObjectPartInfo

    lay, send, (named, walked), error = REMOTE_REMOVALS[case]
    local, rc = remote_pair
    rc.make_vol("vol")
    fi = FileInfo(
        volume="vol", name="a/obj", data_dir="0123abcd" * 4, size=8,
        parts=[ObjectPartInfo(1, 8, 8), ObjectPartInfo(2, 8, 8)],
    )
    if lay is not False:
        for part in fi.parts:
            rc.write_all("vol", f"a/obj/{fi.data_dir}/part.{part.number}", b"12345678")
        rc.write_metadata("vol", "a/obj", fi)
    if lay:
        rc.write_all("vol", f"a/obj/{lay}", b"stray")
    before = xl_mod.remove_counts()
    try:
        rc.delete_file("vol", "a/obj", recursive=True, fi=fi if send else None)
        got = None
    except Exception as e:  # noqa: BLE001 - the class is what is compared
        got = type(e)
    after = xl_mod.remove_counts()
    assert got is error
    assert [after[k] - before[k] for k in ("named", "walked")] == [named, walked]
    # the object, and its parent "a" with it; the volume stays
    assert os.listdir(os.path.join(local.root, "vol")) == []
