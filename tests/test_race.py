"""Systematic concurrency stress harness (SURVEY §5 race discipline).

The reference leans on Go's race detector in CI; Python has no
equivalent, so this harness drives MIXED concurrent operations
against shared layers and asserts the invariants a linearizable
object store must keep:

- a GET returns SOME complete version's bytes, never a torn mix;
- concurrent overwrites of one key leave exactly one winner whose
  GET, info and ETag agree;
- concurrent multipart uploads to one key interleave without
  corrupting either upload's parts;
- the final namespace equals the set of keys whose deletes lost.
"""

import hashlib
import io
import os
import threading
import time

import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.server.http import S3Server
from minio_tpu.storage.xl import XLStorage

from s3client import S3Client

BLOCK = 4096
THREADS = 8
ROUNDS = 12


@pytest.fixture()
def layer(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=BLOCK, min_part_size=1)
    ol.make_bucket("raceb")
    return ol


def _run_all(workers):
    errs = []

    def wrap(fn):
        def inner():
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                import traceback

                errs.append(
                    f"{type(e).__name__}: {e}\n"
                    + traceback.format_exc(limit=4)
                )

        return inner

    threads = [
        threading.Thread(target=wrap(fn), daemon=True)
        for fn in workers
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "worker hung"
    assert not errs, errs[0]


def test_concurrent_overwrites_one_winner(layer):
    """N writers hammer ONE key; every concurrent read returns some
    complete payload and the final state is one winner."""
    payloads = {
        i: bytes([i]) * (3000 + i) for i in range(THREADS)
    }
    valid = {hashlib.md5(p).hexdigest() for p in payloads.values()}
    stop = threading.Event()

    def writer(i):
        def go():
            for _ in range(ROUNDS):
                layer.put_object(
                    "raceb", "hot", io.BytesIO(payloads[i]),
                    len(payloads[i]),
                )

        return go

    reads = [0]
    read_errs = []

    def reader():
        while not stop.is_set():
            buf = io.BytesIO()
            try:
                layer.get_object("raceb", "hot", buf)
            except Exception:  # noqa: BLE001
                continue  # key may not exist yet
            got = buf.getvalue()
            reads[0] += 1
            if hashlib.md5(got).hexdigest() not in valid:
                read_errs.append(f"torn read: {len(got)} bytes")
                return

    # readers run CONCURRENTLY with the writers, stopping after them
    reader_threads = [
        threading.Thread(target=reader, daemon=True) for _ in range(2)
    ]
    for t in reader_threads:
        t.start()
    try:
        _run_all([writer(i) for i in range(THREADS)])
    finally:
        stop.set()
    for t in reader_threads:
        t.join(timeout=60)
    assert not read_errs, read_errs[0]
    assert reads[0] > 0, "readers never observed the key"
    info = layer.get_object_info("raceb", "hot")
    buf = io.BytesIO()
    layer.get_object("raceb", "hot", buf)
    final = buf.getvalue()
    assert hashlib.md5(final).hexdigest() == info.etag
    assert info.etag in valid


def test_concurrent_distinct_keys_all_land(layer):
    def writer(i):
        def go():
            for r in range(ROUNDS):
                data = f"{i}:{r}".encode() * 100
                layer.put_object(
                    "raceb", f"k-{i}-{r}", io.BytesIO(data), len(data)
                )

        return go

    _run_all([writer(i) for i in range(THREADS)])
    names = [
        o.name
        for o in layer.list_objects("raceb", max_keys=1000).objects
    ]
    assert len(names) == THREADS * ROUNDS
    # spot-check integrity across the namespace
    for i in (0, THREADS - 1):
        buf = io.BytesIO()
        layer.get_object("raceb", f"k-{i}-0", buf)
        assert buf.getvalue() == f"{i}:0".encode() * 100


def test_concurrent_put_delete_converges(layer):
    """PUT and DELETE race per key; afterwards every key is either
    fully present (readable, correct bytes) or fully absent."""
    from minio_tpu.objectlayer.api import ObjectNotFound

    keys = [f"pd-{i}" for i in range(THREADS)]

    def putter(k, data):
        def go():
            for _ in range(ROUNDS):
                layer.put_object(
                    "raceb", k, io.BytesIO(data), len(data)
                )

        return go

    def deleter(k):
        def go():
            for _ in range(ROUNDS):
                try:
                    layer.delete_object("raceb", k)
                except ObjectNotFound:
                    pass

        return go

    datas = {k: k.encode() * 500 for k in keys}
    _run_all(
        [putter(k, datas[k]) for k in keys]
        + [deleter(k) for k in keys]
    )
    for k in keys:
        buf = io.BytesIO()
        try:
            layer.get_object("raceb", k, buf)
        except ObjectNotFound:
            continue  # fully absent: fine
        assert buf.getvalue() == datas[k]


def test_concurrent_multipart_uploads_one_key(layer):
    from minio_tpu.objectlayer.api import CompletePart

    def uploader(i):
        def go():
            data1 = bytes([i]) * (6 << 20)
            data2 = bytes([i]) * 1000
            uid = layer.new_multipart_upload("raceb", "mpkey", {})
            p1 = layer.put_object_part(
                "raceb", "mpkey", uid, 1, io.BytesIO(data1), len(data1)
            )
            p2 = layer.put_object_part(
                "raceb", "mpkey", uid, 2, io.BytesIO(data2), len(data2)
            )
            layer.complete_multipart_upload(
                "raceb", "mpkey", uid,
                [CompletePart(1, p1.etag), CompletePart(2, p2.etag)],
            )

        return go

    _run_all([uploader(i) for i in range(4)])
    buf = io.BytesIO()
    info = layer.get_object_info("raceb", "mpkey")
    layer.get_object("raceb", "mpkey", buf)
    got = buf.getvalue()
    # one uploader won wholesale: uniform bytes, full length
    assert len(got) == (6 << 20) + 1000
    assert len(set(got)) == 1
    assert info.size == len(got)
    # no multipart staging leaked
    assert layer.list_multipart_uploads("raceb") == []


def test_concurrent_bucket_create_delete(layer):
    from minio_tpu.objectlayer.api import (
        BucketExists,
        BucketNotFound,
    )

    def cycler(i):
        def go():
            for _ in range(ROUNDS):
                try:
                    layer.make_bucket("churn")
                except BucketExists:
                    pass
                try:
                    layer.delete_bucket("churn", force=True)
                except BucketNotFound:
                    pass

        return go

    _run_all([cycler(i) for i in range(4)])
    # converged: either present or absent, never half-created
    try:
        layer.get_bucket_info("churn")
        present = True
    except BucketNotFound:
        present = False
    if present:
        layer.delete_bucket("churn", force=True)


def test_bucket_delete_vs_create_interleaving(tmp_path, monkeypatch):
    """Regression pin for the r4 full-suite failure: a DeleteVol whose
    directory vanishes underneath it (a racing deleter/creator) must
    surface a bucket-level outcome (VolumeNotFound -> treated as
    success by the layer), never a raw ENOENT that quorum accounting
    counts as a disk fault (WriteQuorumError)."""
    import shutil as _sh

    from minio_tpu.storage import errors as serrors

    d = XLStorage(str(tmp_path / "one"))
    d.make_vol("pinned")

    real_rmtree = _sh.rmtree

    def racing_rmtree(path, *a, **kw):
        # the racing deleter wins between _require_vol and rmtree
        real_rmtree(path, ignore_errors=True)
        return real_rmtree(path, *a, **kw)

    monkeypatch.setattr(_sh, "rmtree", racing_rmtree)
    with pytest.raises(serrors.VolumeNotFound):
        d.delete_vol("pinned", force=True)
    monkeypatch.undo()

    # at the erasure layer a disk reporting FileNotFoundError during
    # DeleteBucket is folded into the bucket-level outcome
    disks = [XLStorage(str(tmp_path / f"p{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=BLOCK, min_part_size=1)
    ol.make_bucket("pinb")
    orig = disks[0].delete_vol

    def flaky(volume, force=False):
        orig(volume, force=force)
        raise FileNotFoundError(2, "No such file or directory")

    disks[0].delete_vol = flaky
    ol.delete_bucket("pinb", force=True)  # must not raise quorum error


def test_bucket_churn_contended(layer):
    """CPU-contended create/delete churn: the r4 failure appeared only
    under full-suite load, so burn background CPU while churning."""
    from minio_tpu.objectlayer.api import BucketExists, BucketNotFound

    stop = threading.Event()

    def burner():
        while not stop.is_set():
            hashlib.sha256(b"x" * 8192).digest()

    burners = [
        threading.Thread(target=burner, daemon=True) for _ in range(4)
    ]
    for b in burners:
        b.start()
    try:

        def cycler():
            for _ in range(ROUNDS * 2):
                try:
                    layer.make_bucket("churn2")
                except BucketExists:
                    pass
                try:
                    layer.delete_bucket("churn2", force=True)
                except BucketNotFound:
                    pass

        _run_all([cycler for _ in range(6)])
    finally:
        stop.set()
        for b in burners:
            b.join(timeout=5)


def test_lock_order_acyclic_under_dsync_stress():
    """The lock-order auditor (minio_tpu.analysis.lockorder) installed
    over a dsync/namespace stress: DRWMutex write/read churn plus
    per-object namespace locks across THREADS workers must leave the
    observed acquisition graph acyclic and sleep-clean (no MTPU301/302
    on the lock plane's hot path)."""
    from minio_tpu.analysis.lockorder import LockOrderAuditor
    from minio_tpu.dsync.drwmutex import DRWMutex, Dsync
    from minio_tpu.dsync.local_locker import LocalLocker
    from minio_tpu.dsync.namespace import NamespaceLock

    aud = LockOrderAuditor()
    with aud.installed():
        lockers = [LocalLocker(endpoint=f"n{i}") for i in range(3)]
        ds = Dsync(lockers, refresh_interval_s=60.0)
        ns = NamespaceLock()
        try:

            def worker(i):
                def go():
                    for r in range(ROUNDS):
                        key = f"obj-{(i + r) % 4}"
                        # the object layer's real nesting order: the
                        # per-key namespace lock wraps the distributed
                        # mutex — hold it across the dsync round so the
                        # auditor sees the nested acquisitions.
                        m = DRWMutex(ds, f"raceb/{key}")
                        if (i + r) % 2 == 0:
                            with ns.write("raceb", key, timeout=30):
                                assert m.get_lock(f"w{i}", timeout=30)
                                m.unlock()
                        else:
                            with ns.read("raceb", key, timeout=30):
                                assert m.get_rlock(timeout=30)
                                m.runlock()

                return go

            _run_all([worker(i) for i in range(THREADS)])
        finally:
            ds.close()
    findings = aud.report()
    cycles = [f for f in findings if f.rule == "MTPU301"]
    assert cycles == [], "\n".join(f.render() for f in cycles)
    assert findings == [], "\n".join(f.render() for f in findings)
    # the stress actually exercised the audited lock plane
    assert aud.edge_labels(), "auditor observed no nested acquisitions"


def test_concurrent_server_requests(tmp_path):
    """The same invariants through the REAL server: SigV4, routing,
    admission, events all in the hot path."""
    disks = [XLStorage(str(tmp_path / f"sd{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=BLOCK, min_part_size=1)
    srv = S3Server(ol, address="127.0.0.1:0").start()
    try:
        boot = S3Client(srv.endpoint)
        assert boot.make_bucket("srvrace").status == 200
        payloads = {
            i: os.urandom(2000 + i) for i in range(THREADS)
        }

        def worker(i):
            def go():
                c = S3Client(srv.endpoint)  # own connection per thread
                for r in range(ROUNDS):
                    key = f"w{i}-{r % 3}"
                    assert c.put_object(
                        "srvrace", key, payloads[i]
                    ).status == 200
                    got = c.get_object("srvrace", key)
                    if got.status == 200:
                        assert got.body in payloads.values()
                    c.request("DELETE", f"/srvrace/w{i}-2")

            return go

        _run_all([worker(i) for i in range(THREADS)])
        r = boot.list_objects("srvrace")
        assert r.status == 200
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# a GET reads xl.meta once, under the lock it streams under
# ---------------------------------------------------------------------------

BIG_BLOCK = 1 << 20


@pytest.fixture()
def zoned_server(tmp_path):
    """The served stack as `python -m minio_tpu.server` builds it: one
    zone of one set behind ErasureZones."""
    from minio_tpu.objectlayer.sets import ErasureSets
    from minio_tpu.objectlayer.zones import ErasureZones

    disks = [XLStorage(str(tmp_path / f"zd{i}")) for i in range(4)]
    ol = ErasureZones([ErasureSets(disks, 1, 4, block_size=BIG_BLOCK)])
    srv = S3Server(ol, address="127.0.0.1:0").start()
    try:
        assert S3Client(srv.endpoint).make_bucket("lockb").status == 200
        yield srv
    finally:
        srv.shutdown()


def test_get_headers_and_body_are_one_version(zoned_server):
    """Overwrites with another size race GETs of the key: every answer's
    body has the length and the MD5 its own headers state."""
    payloads = [os.urandom(n) for n in (700, 300_005, 65_536, 1_500_000)]
    boot = S3Client(zoned_server.endpoint, timeout=60)
    assert boot.put_object("lockb", "hot", payloads[0]).status == 200
    stop = threading.Event()
    seen = [0]

    def writer():
        c = S3Client(zoned_server.endpoint, timeout=60)
        try:
            for r in range(30):
                # readers get in between two writes: a writer that never
                # lets go starves them, and the race with them
                time.sleep(0.03)
                p = payloads[r % len(payloads)]
                assert c.put_object("lockb", "hot", p).status == 200
        finally:
            stop.set()

    def reader():
        c = S3Client(zoned_server.endpoint, timeout=60)
        while not stop.is_set():
            # headers of one version before a shorter one's bytes end in
            # a cut connection: http.client raises, and that fails too
            got = c.get_object("lockb", "hot")
            assert got.status == 200
            assert len(got.body) == int(got.headers["content-length"])
            etag = got.headers["etag"].strip('"')
            assert hashlib.md5(got.body).hexdigest() == etag
            seen[0] += 1

    _run_all([writer, reader, reader, reader])
    assert seen[0] > 0


CKEY = bytes(range(32))


def _hang_up_mid_body(client):
    """GET the big object, read the first bytes, and go away."""
    url, headers = client.signed("GET", "/lockb/big")
    conn = client._connect()
    try:
        conn.request("GET", url, headers=headers)
        resp = conn.getresponse()
        assert resp.status == 200
        assert len(resp.read(4096)) == 4096
        resp.close()
    finally:
        conn.close()


# (object, request headers, status) of a GET that ends before or inside
# its body; each must leave the key's read lock released
_EARLY_EXITS = {
    "not_modified_304": ("plain", {"If-None-Match": "*"}, 304),
    "precondition_412": ("plain", {"If-Match": '"nope"'}, 412),
    "invalid_range_416": ("plain", {"Range": "bytes=999999-"}, 416),
    "ssec_key_missing": ("sealed", {}, 400),
    "client_closes_mid_body": ("big", None, 200),
}


@pytest.mark.parametrize("case", sorted(_EARLY_EXITS))
def test_get_early_exit_releases_lock(zoned_server, case):
    from minio_tpu.codec import sse as ssemod

    key, headers, status = _EARLY_EXITS[case]
    # a leaked read lock holds a PUT for the lock's 30 s and then fails
    # it: the client's own timeout turns that into this test's failure
    client = S3Client(zoned_server.endpoint, timeout=15)
    ol = zoned_server.object_layer
    if key == "sealed":
        ol.put_object(
            "lockb", key, io.BytesIO(b"s" * 5000), 5000,
            sse=ssemod.SSESpec("C", CKEY),
        )
    elif key == "big":
        big = os.urandom(32 * BIG_BLOCK)
        ol.put_object("lockb", key, io.BytesIO(big), len(big))
    else:
        assert client.put_object("lockb", key, b"p" * 5000).status == 200
    if headers is None:
        _hang_up_mid_body(client)
    else:
        got = client.get_object("lockb", key, headers=headers)
        assert got.status == status, got.body
    assert client.put_object("lockb", key, b"after").status == 200
    got = client.get_object("lockb", key)
    assert (got.status, got.body) == (200, b"after")
