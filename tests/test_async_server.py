"""Async request plane: parity, pipelining, 100-continue, timeouts,
backpressure.

Boots the full server in both MINIO_TPU_SERVER modes and asserts they
are black-box interchangeable (bit-identical objects, same shed
semantics) plus the asyncio-only behaviours (slow-loris 408, bounded
handler queue, per-tenant admission).  Raw-socket helpers are used
where http.client would hide the wire behaviour under test
(pipelining, deferred 100-continue, partial heads).
"""

import datetime
import hashlib
import os
import socket
import threading
import time

import numpy as np
import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.server import auth
from minio_tpu.server.http import S3Server
from minio_tpu.storage.xl import XLStorage

from s3client import S3Client

BLOCK = 4096
MODES = ("async", "threaded")


class _Srv:
    """A booted server plus the env keys to restore on teardown."""

    def __init__(self, srv, saved_env):
        self.srv = srv
        self.saved_env = saved_env


def _boot(root, mode, block_size=BLOCK, **env):
    env = {"MINIO_TPU_SERVER": mode, **env}
    # pin the loop count unless a test opts into multi-loop: the
    # single-pool tests (exact shed counts, backlog=1 semantics)
    # must not depend on the host's core count
    env.setdefault("MINIO_TPU_SERVER_LOOPS", "1")
    saved = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        os.environ[k] = str(v)
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=block_size, min_part_size=1)
    srv = S3Server(ol, address="127.0.0.1:0").start()
    return _Srv(srv, saved)


def _teardown(booted, drain_s=5.0):
    booted.srv.shutdown(drain_s=drain_s)
    for k, v in booted.saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _pay(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8
    ).tobytes()


# -- raw-socket helpers ---------------------------------------------------


def _signed_head(
    client, method, path, body=b"", extra=None, secret=None,
):
    """Build the raw request head (status line + headers) for a SigV4
    request, without sending it - so tests control wire framing."""
    amz_date = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ"
    )
    phash = hashlib.sha256(body).hexdigest()
    headers = {k.lower(): v for k, v in (extra or {}).items()}
    headers.setdefault("host", f"{client.host}:{client.port}")
    headers["x-amz-date"] = amz_date
    headers["x-amz-content-sha256"] = phash
    signed = sorted(headers)
    sig = auth.sign_v4(
        method, path, {}, headers, signed, phash,
        client.access_key, secret or client.secret_key, amz_date,
        client.region,
    )
    scope = f"{amz_date[:8]}/{client.region}/s3/aws4_request"
    headers["authorization"] = (
        f"{auth.SIGN_V4_ALGORITHM} "
        f"Credential={client.access_key}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}"
    )
    if body:
        headers["content-length"] = str(len(body))
    lines = [f"{method} {path} HTTP/1.1"]
    lines += [f"{k}: {v}" for k, v in headers.items()]
    return ("\r\n".join(lines) + "\r\n\r\n").encode()


def _read_response(f):
    """Read one HTTP response (status, headers, body) off a socket
    file; returns (status, headers, body)."""
    status_line = f.readline()
    if not status_line:
        return None, {}, b""
    status = int(status_line.split()[1])
    headers = {}
    while True:
        line = f.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    body = b""
    if status != 100 and "content-length" in headers:
        body = f.read(int(headers["content-length"]))
    return status, headers, body


def _connect(srv):
    host, port = srv.endpoint.split("//")[1].rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=10)
    return s


# -- mode parity ----------------------------------------------------------


def test_put_get_bit_identity_across_modes(leakcheck, tmp_path):
    """The same payload stored through each plane round-trips to the
    same bytes and the same ETag - the threaded plane is the bisection
    oracle for the async one."""
    payload = _pay(1 << 20, seed=7)
    got = {}
    for mode in MODES:
        booted = _boot(tmp_path / mode, mode)
        try:
            c = S3Client(booted.srv.endpoint)
            assert c.make_bucket("parity").status == 200
            r = c.put_object("parity", "obj", payload)
            assert r.status == 200
            g = c.get_object("parity", "obj")
            assert g.status == 200
            got[mode] = (r.headers["etag"], g.body)
        finally:
            _teardown(booted)
    assert got["async"][1] == payload
    assert got["async"] == got["threaded"]


@pytest.mark.parametrize("mode", MODES)
def test_keepalive_pipelined_ordering(leakcheck, tmp_path, mode):
    """Two requests written back-to-back on one connection come back
    in order on that same connection."""
    booted = _boot(tmp_path, mode)
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("pipe").status == 200
        bodies = {f"o{i}": _pay(2048, seed=i) for i in (1, 2)}
        for k, v in bodies.items():
            assert c.put_object("pipe", k, v).status == 200

        s = _connect(booted.srv)
        try:
            head = _signed_head(c, "GET", "/pipe/o1") + _signed_head(
                c, "GET", "/pipe/o2"
            )
            s.sendall(head)
            f = s.makefile("rb")
            for key in ("o1", "o2"):
                status, hdrs, body = _read_response(f)
                assert status == 200
                assert body == bodies[key]
        finally:
            s.close()
    finally:
        _teardown(booted)


# -- Expect: 100-continue -------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_expect_100_continue_with_waiting_client(leakcheck, tmp_path, mode):
    """A client that genuinely withholds the body until 100 Continue
    arrives must still complete the PUT - i.e. the server sends the
    interim response when it decides to read the body, not never."""
    booted = _boot(tmp_path, mode)
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("expect").status == 200
        body = _pay(8192, seed=3)
        head = _signed_head(
            c, "PUT", "/expect/waits", body=body,
            extra={"expect": "100-continue"},
        )
        s = _connect(booted.srv)
        try:
            s.sendall(head)
            f = s.makefile("rb")
            # body is NOT on the wire yet - the server must talk first
            status, _, _ = _read_response(f)
            assert status == 100
            s.sendall(body)
            status, hdrs, _ = _read_response(f)
            assert status == 200
        finally:
            s.close()
        g = c.get_object("expect", "waits")
        assert g.status == 200 and g.body == body
    finally:
        _teardown(booted)


@pytest.mark.parametrize("mode", MODES)
def test_expect_100_rejected_headers_skip_continue(leakcheck, tmp_path, mode):
    """When the request is rejected on its headers the server must NOT
    invite the body: final status comes first and the connection
    closes (the unread body would otherwise desync the framing)."""
    booted = _boot(tmp_path, mode)
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("expect2").status == 200
        body = _pay(4096, seed=4)
        head = _signed_head(
            c, "PUT", "/expect2/denied", body=body,
            extra={"expect": "100-continue"}, secret="wrong-secret",
        )
        s = _connect(booted.srv)
        try:
            s.sendall(head)
            f = s.makefile("rb")
            status, hdrs, _ = _read_response(f)
            assert status == 403
            # the unread body means the server MUST sever the
            # connection rather than resync on garbage
            assert f.read(1) == b""  # EOF - no 100 ever arrives
        finally:
            s.close()
    finally:
        _teardown(booted)


# -- timeouts -------------------------------------------------------------


def test_slow_loris_header_timeout_async(leakcheck, tmp_path):
    """A connection that dribbles a partial head gets 408 + close once
    MINIO_TPU_HEADER_TIMEOUT_S expires, freeing the parse stage."""
    booted = _boot(tmp_path, "async", MINIO_TPU_HEADER_TIMEOUT_S="0.5")
    try:
        s = _connect(booted.srv)
        try:
            s.sendall(b"GET /loris HTTP/1.1\r\nHost: x")  # never finishes
            f = s.makefile("rb")
            t0 = time.monotonic()
            status, _, _ = _read_response(f)
            assert status == 408
            assert time.monotonic() - t0 < 8.0
            assert f.read(1) == b""
        finally:
            s.close()
    finally:
        _teardown(booted)


def test_slow_loris_timeout_threaded(leakcheck, tmp_path):
    """The threaded oracle sheds the same attack via the per-socket
    idle timeout - the connection just dies."""
    booted = _boot(tmp_path, "threaded", MINIO_TPU_IDLE_TIMEOUT_S="0.5")
    try:
        s = _connect(booted.srv)
        try:
            s.sendall(b"GET /loris HTTP/1.1\r\nHost: x")
            s.settimeout(8.0)
            deadline = time.monotonic() + 8.0
            data = b"x"
            while data and time.monotonic() < deadline:
                data = s.recv(4096)
            assert data == b""  # server closed on us
        finally:
            s.close()
    finally:
        _teardown(booted)


@pytest.mark.parametrize("mode", ["async", "threaded"])
def test_idle_keepalive_closes_without_a_reply(leakcheck, tmp_path, mode):
    """A kept-alive connection that sits idle past MINIO_TPU_IDLE_TIMEOUT_S
    is closed with no bytes on the wire: a 408 written there would be read
    by the client as the answer to its NEXT request (the benchmark's admin
    client met exactly that after a set-up of over a minute)."""
    booted = _boot(tmp_path, mode, MINIO_TPU_IDLE_TIMEOUT_S="0.5")
    try:
        c = S3Client(booted.srv.endpoint)
        s = _connect(booted.srv)
        try:
            s.sendall(_signed_head(c, "GET", "/"))
            f = s.makefile("rb")
            status, _, _ = _read_response(f)
            assert status == 200
            s.settimeout(8.0)
            assert f.read(1) == b""  # EOF, and nothing before it
        finally:
            s.close()
    finally:
        _teardown(booted)


# -- backpressure + admission ---------------------------------------------


def _retry_503(call, *args, **kw):
    """503 SlowDown is the shed signal and is retryable; poll through
    transient sheds (e.g. the tiny window between a response flushing
    and its tenant slot releasing)."""
    r = call(*args, **kw)
    deadline = time.monotonic() + 10.0
    while r.status == 503 and time.monotonic() < deadline:
        time.sleep(0.05)
        r = call(*args, **kw)
    return r


class _BlockingLayer:
    """Wraps get_object so reads of one key park on an Event, holding
    a worker slot for as long as the test needs."""

    def __init__(self, ol, key):
        self.ol = ol
        self.key = key
        self.entered = threading.Event()
        self.release = threading.Event()
        self._orig = ol.get_object

    def install(self):
        def slow_get(bucket, object_name, writer, *args, **kw):
            if object_name == self.key:
                self.entered.set()
                assert self.release.wait(30.0), "test never released"
            return self._orig(bucket, object_name, writer, *args, **kw)

        self.ol.get_object = slow_get

    def uninstall(self):
        self.release.set()
        self.ol.get_object = self._orig


def test_backpressure_sheds_503_queue(leakcheck, tmp_path):
    """With one worker and a one-deep handler queue, the third
    concurrent request is refused with 503 SlowDown *before* touching
    the codec - and the refusal is counted under reason=queue."""
    booted = _boot(
        tmp_path, "async",
        MINIO_TPU_SERVER_WORKERS="1", MINIO_TPU_SERVER_BACKLOG="1",
    )
    srv = booted.srv
    blocker = None
    threads = []
    try:
        c = S3Client(srv.endpoint)
        assert c.make_bucket("backp").status == 200
        assert c.put_object("backp", "slow", _pay(1024)).status == 200

        blocker = _BlockingLayer(srv.object_layer, "slow")
        blocker.install()

        results = {}

        def fetch(tag):
            results[tag] = S3Client(srv.endpoint).get_object("backp", "slow")

        # A occupies the single worker...
        threads.append(threading.Thread(target=fetch, args=("a",)))
        threads[-1].start()
        assert blocker.entered.wait(10.0)
        # ...B fills the one-slot queue...
        threads.append(threading.Thread(target=fetch, args=("b",)))
        threads[-1].start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            depth = srv.plane_stats.snapshot()["stage_depth"].get(
                "handler", 0
            )
            if depth >= 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("second request never queued")

        # ...so C must be shed at admission.
        shed = S3Client(srv.endpoint).get_object("backp", "slow")
        assert shed.status == 503
        assert shed.error_code == "SlowDown"
        snap = srv.plane_stats.snapshot()
        assert snap["shed"]["queue"] >= 1

        blocker.release.set()
        for t in threads:
            t.join(30.0)
        assert results["a"].status == 200
        assert results["b"].status == 200
    finally:
        if blocker is not None:
            blocker.uninstall()
        for t in threads:
            t.join(5.0)
        _teardown(booted)


def test_tenant_admission_sheds_503(leakcheck, tmp_path):
    """MINIO_TPU_TENANT_MAX_INFLIGHT=1 caps one access key to a single
    in-flight request; the overflow request sheds under reason=tenant."""
    booted = _boot(
        tmp_path, "async", MINIO_TPU_TENANT_MAX_INFLIGHT="1",
    )
    srv = booted.srv
    blocker = None
    t = None
    try:
        c = S3Client(srv.endpoint)
        # tenant slots are released a hair after the response flushes,
        # so back-to-back setup calls under cap=1 can see a transient
        # SlowDown - which is retryable by contract
        assert _retry_503(c.make_bucket, "tenantb").status == 200
        assert (
            _retry_503(c.put_object, "tenantb", "slow", _pay(512)).status
            == 200
        )

        blocker = _BlockingLayer(srv.object_layer, "slow")
        blocker.install()

        results = {}

        def fetch():
            # the setup PUT's tenant slot releases a hair after its
            # response flushes, so this GET can shed transiently too —
            # retry until it actually occupies the slot and parks
            results["a"] = _retry_503(
                S3Client(srv.endpoint).get_object, "tenantb", "slow"
            )

        t = threading.Thread(target=fetch)
        t.start()
        assert blocker.entered.wait(10.0)

        shed = S3Client(srv.endpoint).get_object("tenantb", "slow")
        assert shed.status == 503
        assert shed.error_code == "SlowDown"
        assert srv.plane_stats.snapshot()["shed"]["tenant"] >= 1

        blocker.release.set()
        t.join(30.0)
        assert results["a"].status == 200
    finally:
        if blocker is not None:
            blocker.uninstall()
        if t is not None:
            t.join(5.0)
        _teardown(booted)


# -- streaming PUT (no full-body materialisation) -------------------------


class _ChunkRecorder:
    """Pass-through reader that records every read() size so the test
    can prove the body was streamed, not slurped."""

    def __init__(self, inner):
        self._inner = inner
        self.chunks = []

    def read(self, n=-1):
        data = self._inner.read(n)
        self.chunks.append(len(data))
        return data

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_put_body_streams_to_codec(leakcheck, tmp_path):
    """The PUT hot path hands the codec an incremental reader: no
    single read ever returns the whole body (no b"".join style
    materialisation upstream of encode)."""
    booted = _boot(tmp_path, "async")
    srv = booted.srv
    size = 1 << 20
    recorded = {}
    orig = srv.object_layer.put_object

    def spying_put(bucket, object_name, reader, size=-1, *args, **kw):
        rec = _ChunkRecorder(reader)
        recorded["chunks"] = rec.chunks
        return orig(bucket, object_name, rec, size, *args, **kw)

    srv.object_layer.put_object = spying_put
    try:
        c = S3Client(srv.endpoint)
        assert c.make_bucket("stream").status == 200
        body = _pay(size, seed=11)
        assert c.put_object("stream", "big", body).status == 200
        chunks = [n for n in recorded["chunks"] if n > 0]
        assert chunks, "put_object never read the body"
        assert sum(chunks) == size
        assert max(chunks) < size, (
            "a single read returned the full body - the request plane "
            "materialised the PUT payload"
        )
        g = c.get_object("stream", "big")
        assert g.status == 200 and g.body == body
    finally:
        srv.object_layer.put_object = orig
        _teardown(booted)


# -- the reader bridge: one hand-over a read(n), filled on the loop --------


def _body_read():
    from minio_tpu.codec.telemetry import KERNEL_STATS

    snap = KERNEL_STATS.snapshot()
    waits = sum(
        r["count"] for r in snap["spans"] if r["name"] == "body_read_wait"
    )
    return {**snap["body_read"], "waits": waits}


def _moved(before):
    after = _body_read()
    return {k: after[k] - before[k] for k in after}


def test_put_10mib_crosses_in_one_handover(leakcheck, tmp_path):
    """A signed 10 MiB PUT read with the reference's 10 MiB block: the
    body crosses from the loop to its handler once (40-42 times when a
    read(n) returned one recv), every byte of it, and reads back."""
    size = 10 << 20
    booted = _boot(tmp_path, "async", block_size=size)
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("once").status == 200
        body = _pay(size, seed=30)
        before = _body_read()
        assert c.put_object("once", "block", body).status == 200
        moved = _moved(before)
        assert moved["handovers"] == moved["waits"] == 1
        assert moved["bytes"] == size
        assert 1 <= moved["loop_reads"] <= size // 4096
        before = _body_read()
        g = c.get_object("once", "block")
        assert g.status == 200 and g.body == body
        assert _moved(before) == {
            "handovers": 0, "bytes": 0, "loop_reads": 0, "waits": 0,
        }
    finally:
        _teardown(booted)


@pytest.mark.parametrize(
    "gap_s,stall_s,ok",
    [(0.13, 0.0, True), (0.0, 1.5, False)],
    ids=["trickle", "stall"],
)
def test_body_timeout_bounds_the_wait_for_the_next_bytes(
    leakcheck, tmp_path, gap_s, stall_s, ok
):
    """MINIO_TPU_BODY_TIMEOUT_S is per wait, not per hand-over: 1 KiB
    every 0.13 s (eight sleeps: 1.04 s, whatever the scheduler adds)
    keeps an 8 KiB read alive for twice the timeout; a
    client that goes silent for longer than it is cut, as before."""
    booted = _boot(
        tmp_path, "async", block_size=1 << 20,
        MINIO_TPU_BODY_TIMEOUT_S="0.5",
    )
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("slow").status == 200
        body = _pay(8192, seed=31)
        s = _connect(booted.srv)
        try:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.sendall(_signed_head(c, "PUT", "/slow/obj", body=body))
            before = _body_read()
            t0 = time.monotonic()
            for off in range(0, len(body) // 2, 1024):
                s.sendall(body[off:off + 1024])
                time.sleep(gap_s)
            time.sleep(stall_s)
            f = s.makefile("rb")
            if ok:
                for off in range(len(body) // 2, len(body), 1024):
                    s.sendall(body[off:off + 1024])
                    time.sleep(gap_s)
                status, _, _ = _read_response(f)
                assert status == 200
                assert time.monotonic() - t0 > 2 * 0.5
                moved = _moved(before)
                assert moved["handovers"] == 1 and moved["bytes"] == 8192
                assert moved["loop_reads"] >= 4
            else:
                # as before the change: cut without a reply
                assert _read_response(f) == (None, {}, b"")
        finally:
            s.close()
        g = c.get_object("slow", "obj")
        assert (g.status, g.body) == ((200, body) if ok else (404, g.body))
    finally:
        _teardown(booted)


def test_short_body_is_incomplete_and_closes(leakcheck, tmp_path):
    """Fewer bytes than Content-Length, then EOF: the full read comes
    back short, the PUT fails with IncompleteBody, nothing is stored."""
    booted = _boot(tmp_path, "async", block_size=1 << 20)
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("short").status == 200
        body = _pay(300_000, seed=32)
        s = _connect(booted.srv)
        try:
            s.sendall(_signed_head(c, "PUT", "/short/obj", body=body))
            s.sendall(body[:-100])
            s.shutdown(socket.SHUT_WR)
            f = s.makefile("rb")
            status, _, rbody = _read_response(f)
            assert status == 400 and b"IncompleteBody" in rbody
            assert f.read(1) == b""
        finally:
            s.close()
        assert c.get_object("short", "obj").status == 404
    finally:
        _teardown(booted)


@pytest.mark.parametrize(
    "sizes", [(3000, 5000), (300_000, 200_000)], ids=["small", "pieces"]
)
def test_pipelined_puts_each_get_their_own_bytes(
    leakcheck, tmp_path, sizes
):
    """Two PUTs written back to back: the first body's fill stops at
    its n, so the second head and body are still in the StreamReader."""
    booted = _boot(tmp_path, "async", block_size=1 << 20)
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("pipe2").status == 200
        bodies = {
            f"o{i}": _pay(n, seed=40 + i) for i, n in enumerate(sizes)
        }
        s = _connect(booted.srv)
        try:
            before = _body_read()
            s.sendall(b"".join(
                _signed_head(c, "PUT", f"/pipe2/{k}", body=v) + v
                for k, v in bodies.items()
            ))
            f = s.makefile("rb")
            for _ in bodies:
                status, _, _ = _read_response(f)
                assert status == 200
            moved = _moved(before)
            assert moved["handovers"] == 2
            assert moved["bytes"] == sum(sizes)
        finally:
            s.close()
        for k, v in bodies.items():
            g = c.get_object("pipe2", k)
            assert g.status == 200 and g.body == v
    finally:
        _teardown(booted)


@pytest.mark.parametrize(
    "head,status",
    [
        (b"GET /big HTTP/1.1\r\nX-Pad: " + b"a" * (70 << 10), 431),
        (b"GET /loris HTTP/1.1\r\nHost: x", 408),
    ],
    ids=["oversize-431", "slow-408"],
)
def test_head_limits_hold_after_a_body(leakcheck, tmp_path, head, status):
    """The body's read-ahead threshold is the body's alone: the next
    head on the same connection is still capped at 64 KiB (431 at once,
    not a 408 a timeout later) and still timed."""
    booted = _boot(
        tmp_path, "async", block_size=1 << 20,
        MINIO_TPU_HEADER_TIMEOUT_S="1.0",
    )
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("heads").status == 200
        body = _pay(300_000, seed=33)
        s = _connect(booted.srv)
        try:
            s.sendall(_signed_head(c, "PUT", "/heads/obj", body=body) + body)
            f = s.makefile("rb")
            assert _read_response(f)[0] == 200
            t0 = time.monotonic()
            s.sendall(head)
            got, _, _ = _read_response(f)
            assert got == status
            if status == 431:
                assert time.monotonic() - t0 < 0.9
            assert f.read(1) == b""
        finally:
            s.close()
    finally:
        _teardown(booted)


class _Wire:
    """A _LoopReader over a StreamReader that the test feeds by hand: the
    bridge alone, with no socket and no server around it."""

    def __init__(self, timeout=5.0):
        import asyncio

        from minio_tpu.server import aio

        self.loop = asyncio.new_event_loop()
        self.body_timeout = timeout
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="wire-loop", daemon=True
        )
        self._thread.start()
        self.stream = asyncio.StreamReader(limit=aio._MAX_HEAD, loop=self.loop)
        self.reader = aio._LoopReader(self, self.stream)

    def feed(self, data):
        """None is EOF."""
        if data is None:
            self.loop.call_soon_threadsafe(self.stream.feed_eof)
        else:
            self.loop.call_soon_threadsafe(self.stream.feed_data, data)

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=5)
        assert not self._thread.is_alive()
        self.loop.close()


DATA = _pay(3000, seed=60)
# fed (None is EOF), then [(n asked, bytes that come back)], then what
# body_read moved by: hand-overs, bytes, reads on the loop
WIRE_CASES = {
    "reads-full": ([DATA[:1000], DATA[1000:]], [(3000, DATA)], (1, 3000, None)),
    "stops-at-n": ([DATA], [(1000, DATA[:1000]), (2000, DATA[1000:])],
                   (2, 3000, 2)),
    "short-at-eof": ([DATA[:500], None], [(3000, DATA[:500]), (10, b"")],
                     (2, 500, None)),
    "capped": ([DATA], [(3000, DATA[:1024]), (1976, DATA[1024:2048]),
                        (952, DATA[2048:])], (3, 3000, 3)),
    "zero-is-no-handover": ([DATA], [(0, b"")], (0, 0, 0)),
    "minus-one-reads-to-eof": ([DATA[:700], None], [(-1, DATA[:700])],
                               (1, 700, 1)),
}


@pytest.mark.parametrize("case", WIRE_CASES)
def test_loop_reader_reads_full(leakcheck, monkeypatch, case):
    from minio_tpu.server import aio

    fed, reads, (handovers, nbytes, loop_reads) = WIRE_CASES[case]
    if case == "capped":
        monkeypatch.setattr(aio, "_MAX_HANDOVER", 1024)
    wire = _Wire()
    try:
        for piece in fed:
            wire.feed(piece)
        before = aio.body_read_counts()
        for n, want in reads:
            got = wire.reader.read(n)
            assert got == want and len(got) == len(want)
        after = aio.body_read_counts()
        moved = {k: after[k] - before[k] for k in after}
        assert (moved["handovers"], moved["bytes"]) == (handovers, nbytes)
        if loop_reads is not None:
            assert moved["loop_reads"] == loop_reads
        # the head's cap is the connection's again only once the request
        # is over (_handle_one): a read leaves the body's threshold set
        if any(n > 0 for n, _ in reads):
            assert wire.stream._limit == aio._BODY_READAHEAD
    finally:
        wire.close()


def test_loop_reader_times_out_on_the_next_bytes_only(leakcheck):
    """Bytes every 0.1 s for 0.6 s under a timeout of 0.3 s: the
    hand-over outlasts the timeout; silence for longer raises."""
    wire = _Wire(timeout=0.3)
    try:
        def trickle():
            for off in range(0, 600, 100):
                wire.feed(DATA[off:off + 100])
                time.sleep(0.1)

        t = threading.Thread(target=trickle, name="wire-trickle")
        t.start()
        try:
            assert wire.reader.read(600) == DATA[:600]
        finally:
            t.join(timeout=5)
        assert not t.is_alive()
        with pytest.raises(socket.timeout):
            wire.reader.read(10)
        # and a line of chunk framing still comes a byte at a time
        wire.feed(b"1f\r\nrest")
        assert wire.reader.readline(1024) == b"1f\r\n"
    finally:
        wire.close()


class _StreamPlane:
    """An internode plane that takes a chunked body as a stream."""

    def __init__(self):
        self.got = None

    def handle(self, tail, query, body, headers):
        return 200, b"", {}

    def handle_stream(self, tail, query, reader, headers):
        self.got = reader.read(-1)
        return 200, b"ok", {}


@pytest.mark.parametrize("step", [1, 7, 1 << 16], ids=lambda n: f"step{n}")
def test_chunked_framing_delivered_in_small_steps(leakcheck, tmp_path, step):
    """_ChunkedReader compares raw.read(2) with CRLF and reads a chunk
    with raw.read(want): both need a read that reads full.  Delivered a
    byte at a time the old bridge returned one byte of the two."""
    booted = _boot(tmp_path, "async")
    plane = _StreamPlane()
    booted.srv.register_internode("/testplane", plane.handle)
    try:
        chunks = [_pay(n, seed=50 + n) for n in (1, 300, 17)]
        wire = b"".join(
            b"%x\r\n%s\r\n" % (len(ch), ch) for ch in chunks
        ) + b"0\r\n\r\n"
        s = _connect(booted.srv)
        try:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.sendall(
                b"POST /testplane/v1/put HTTP/1.1\r\nHost: x\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n"
            )
            for off in range(0, len(wire), step):
                s.sendall(wire[off:off + step])
                if step < 64:
                    time.sleep(0.001)
            status, _, rbody = _read_response(s.makefile("rb"))
            assert (status, rbody) == (200, b"ok")
        finally:
            s.close()
        assert plane.got == b"".join(chunks)
    finally:
        _teardown(booted)


# -- multi-loop plane (MINIO_TPU_SERVER_LOOPS) ----------------------------


def test_loops1_bit_identical_to_multiloop(leakcheck, tmp_path):
    """LOOPS=1 is today's plane verbatim and the bisection oracle for
    the sharded one: the same object stored through 1 and 3 loops
    round-trips to identical bytes and ETag, and the single-loop boot
    takes the plain (non-SO_REUSEPORT) listener path."""
    payload = _pay(1 << 19, seed=23)
    got = {}
    for loops in ("1", "3"):
        booted = _boot(
            tmp_path / f"l{loops}", "async",
            MINIO_TPU_SERVER_LOOPS=loops,
        )
        try:
            plane = booted.srv._plane
            assert len(plane.loops) == int(loops)
            if loops == "1":
                assert plane.reuseport is False
            c = S3Client(booted.srv.endpoint)
            assert c.make_bucket("shard").status == 200
            r = c.put_object("shard", "obj", payload)
            assert r.status == 200
            g = c.get_object("shard", "obj")
            assert g.status == 200
            got[loops] = (r.headers["etag"], g.body)
        finally:
            _teardown(booted)
    assert got["1"][1] == payload
    assert got["1"] == got["3"]


@pytest.mark.parametrize("reuseport", ("auto", "off"))
def test_multiloop_roundtrip_and_readiness(
    leakcheck, tmp_path, reuseport
):
    """Both listener strategies (SO_REUSEPORT shards and the
    round-robin handoff fallback) serve the full S3 path at LOOPS=3,
    and readiness reports every loop serving."""
    booted = _boot(
        tmp_path, "async",
        MINIO_TPU_SERVER_LOOPS="3",
        MINIO_TPU_SERVER_REUSEPORT=reuseport,
    )
    try:
        srv = booted.srv
        plane = srv._plane
        assert len(plane.loops) == 3
        assert plane.reuseport is (reuseport == "auto")
        ok, doc = srv.readiness()
        assert ok
        import json

        parsed = json.loads(doc)
        assert parsed["server_loops"] is True
        assert parsed["loops"] == {
            "0": "serving", "1": "serving", "2": "serving"
        }
        c = S3Client(srv.endpoint)
        assert c.make_bucket("mlb").status == 200
        body = _pay(96 * 1024, seed=5)
        assert c.put_object("mlb", "obj", body).status == 200
        # fresh connection per GET so accepts spread across loops
        for _ in range(6):
            g = S3Client(srv.endpoint).get_object("mlb", "obj")
            assert g.status == 200 and g.body == body
    finally:
        _teardown(booted)


def test_multiloop_pipelined_ordering(leakcheck, tmp_path):
    """Per-connection pipelining is a per-loop affair: back-to-back
    requests on one connection come back in order even when other
    loops exist (a connection never migrates between loops)."""
    booted = _boot(tmp_path, "async", MINIO_TPU_SERVER_LOOPS="2")
    try:
        c = S3Client(booted.srv.endpoint)
        assert c.make_bucket("mpipe").status == 200
        bodies = {f"o{i}": _pay(2048, seed=i) for i in (1, 2, 3)}
        for k, v in bodies.items():
            assert c.put_object("mpipe", k, v).status == 200
        s = _connect(booted.srv)
        try:
            head = b"".join(
                _signed_head(c, "GET", f"/mpipe/o{i}") for i in (1, 2, 3)
            )
            s.sendall(head)
            f = s.makefile("rb")
            for key in ("o1", "o2", "o3"):
                status, _, body = _read_response(f)
                assert status == 200
                assert body == bodies[key]
        finally:
            s.close()
    finally:
        _teardown(booted)


def test_wedged_loop_degrades_only_its_shard(leakcheck, tmp_path):
    """Stalling one loop's thread (the chaos wedge behind the testgrid
    wedged_loop cell) must not stall connections owned by other loops.
    Handoff mode makes connection->loop placement deterministic
    (round-robin from loop 0), so conn N lands on loop N%3."""
    booted = _boot(
        tmp_path, "async",
        MINIO_TPU_SERVER_LOOPS="3",
        MINIO_TPU_SERVER_REUSEPORT="off",
    )
    socks = []
    try:
        srv = booted.srv
        c = S3Client(srv.endpoint)
        assert c.make_bucket("wedge").status == 200
        body = _pay(2048, seed=9)
        assert c.put_object("wedge", "obj", body).status == 200

        # three keep-alive connections, one per loop (round-robin);
        # earlier client requests consumed rr slots, so detect which
        # loop actually adopted each socket rather than assuming i%3
        plane = srv._plane
        placement = []
        for i in range(3):
            snap = [set(sl._conns) for sl in plane.loops]
            s = _connect(srv)
            socks.append(s)
            s.sendall(_signed_head(c, "GET", "/wedge/obj"))
            status, _, got = _read_response(s.makefile("rb"))
            assert status == 200 and got == body
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                gained = [
                    ix for ix, sl in enumerate(plane.loops)
                    if set(sl._conns) - snap[ix]
                ]
                if len(gained) == 1:
                    placement.append(gained[0])
                    break
                time.sleep(0.02)
            else:
                raise AssertionError(
                    f"conn {i} never registered with a loop: {gained}"
                )
        assert sorted(placement) == [0, 1, 2], placement

        # wedge the loop owning socks[1]; the other two loops must
        # keep serving their connections immediately
        wedged = placement[1]
        assert plane.wedge_loop(wedged, 3.0)
        time.sleep(0.5)  # past the scheduling grace: the spin is live
        for ix in (0, 2):
            t0 = time.monotonic()
            socks[ix].sendall(_signed_head(c, "GET", "/wedge/obj"))
            status, _, got = _read_response(socks[ix].makefile("rb"))
            assert status == 200 and got == body
            assert time.monotonic() - t0 < 2.5, (
                f"conn on loop {placement[ix]} stalled behind the "
                f"wedge on loop {wedged}"
            )
        # the wedged loop's own connection answers only after the
        # spin releases (response bytes flush through that loop)
        t0 = time.monotonic()
        socks[1].sendall(_signed_head(c, "GET", "/wedge/obj"))
        status, _, got = _read_response(socks[1].makefile("rb"))
        assert status == 200 and got == body
    finally:
        for s in socks:
            s.close()
        _teardown(booted)


class _CountingBlocker:
    """Wraps get_object for one key: counts concurrent handlers (the
    ground truth the shared budget's hwm is checked against) and parks
    them until released."""

    def __init__(self, ol, key):
        self.ol = ol
        self.key = key
        self.release = threading.Event()
        self._mu = threading.Lock()
        self.concurrent = 0
        self.max_concurrent = 0
        self._orig = ol.get_object

    def install(self):
        def counting_get(bucket, object_name, writer, *args, **kw):
            if object_name == self.key:
                with self._mu:
                    self.concurrent += 1
                    self.max_concurrent = max(
                        self.max_concurrent, self.concurrent
                    )
                try:
                    assert self.release.wait(30.0), "never released"
                finally:
                    with self._mu:
                        self.concurrent -= 1
            return self._orig(bucket, object_name, writer, *args, **kw)

        self.ol.get_object = counting_get

    def uninstall(self):
        self.release.set()
        self.ol.get_object = self._orig


def test_multiloop_tenant_cap_exact_across_loops(leakcheck, tmp_path):
    """The global per-tenant cap holds EXACTLY across loops under a
    concurrent flood: with cap=4 and 12 parallel GETs spread over 3
    loops, exactly 4 park in handlers, the rest shed 503 tenant, and
    the shared budget's high-water mark never exceeds the cap."""
    CAP, FLOOD = 4, 12
    booted = _boot(
        tmp_path, "async",
        MINIO_TPU_SERVER_LOOPS="3",
        MINIO_TPU_SERVER_WORKERS="18",
        MINIO_TPU_SERVER_BACKLOG="30",
        MINIO_TPU_TENANT_MAX_INFLIGHT=str(CAP),
    )
    srv = booted.srv
    blocker = None
    threads = []
    try:
        c = S3Client(srv.endpoint)
        assert _retry_503(c.make_bucket, "cap").status == 200
        assert _retry_503(
            c.put_object, "cap", "slow", _pay(512)
        ).status == 200

        blocker = _CountingBlocker(srv.object_layer, "slow")
        blocker.install()
        results = {}

        def fetch(tag):
            # one shot, no retry: the flood itself is the assertion
            results[tag] = S3Client(srv.endpoint).get_object(
                "cap", "slow"
            )

        for i in range(FLOOD):
            threads.append(
                threading.Thread(target=fetch, args=(i,))
            )
            threads[-1].start()
        # every request reached a verdict: parked in a handler or shed
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            parked = blocker.concurrent
            shed = srv.plane_stats.snapshot()["shed"]["tenant"]
            if parked + shed >= FLOOD:
                break
            time.sleep(0.05)
        assert blocker.concurrent == CAP, (
            f"cap not saturated: {blocker.concurrent}/{CAP} parked"
        )
        blocker.release.set()
        for t in threads:
            t.join(30.0)
        statuses = sorted(r.status for r in results.values())
        assert statuses.count(200) == CAP
        assert statuses.count(503) == FLOOD - CAP
        for r in results.values():
            if r.status == 503:
                assert r.error_code == "SlowDown"
        # the budget's own witness: admitted concurrency never crossed
        # the cap on any interleaving (TokenCounter invariant)
        hwm = srv.admission.budget.tenant_hwm()
        assert hwm.get("minioadmin", 0) <= CAP
        assert blocker.max_concurrent == CAP
    finally:
        if blocker is not None:
            blocker.uninstall()
        for t in threads:
            t.join(5.0)
        _teardown(booted)


def test_multiloop_shutdown_drains_every_loop(leakcheck, tmp_path):
    """S3Server.shutdown with N loops: stops accepting, waits for the
    in-flight request on whichever loop owns it, and a second call is
    an idempotent no-op.  Every loop lands in state=stopped."""
    booted = _boot(tmp_path, "async", MINIO_TPU_SERVER_LOOPS="2")
    srv = booted.srv
    blocker = None
    t = None
    try:
        c = S3Client(srv.endpoint)
        assert c.make_bucket("drain").status == 200
        assert c.put_object("drain", "slow", _pay(1024)).status == 200
        blocker = _BlockingLayer(srv.object_layer, "slow")
        blocker.install()
        results = {}

        def fetch():
            results["r"] = S3Client(srv.endpoint).get_object(
                "drain", "slow"
            )

        t = threading.Thread(target=fetch)
        t.start()
        assert blocker.entered.wait(10.0)

        def release_soon():
            time.sleep(0.5)
            blocker.release.set()

        rel = threading.Thread(target=release_soon)
        rel.start()
        srv.shutdown(drain_s=10.0)
        rel.join(5.0)
        t.join(10.0)
        assert results["r"].status == 200
        plane = srv._plane
        assert [sl.state for sl in plane.loops] == ["stopped"] * 2
        t0 = time.monotonic()
        srv.shutdown(drain_s=10.0)  # idempotent, returns immediately
        assert time.monotonic() - t0 < 1.0
        ok, _doc = srv.readiness()
        assert not ok  # draining servers are not ready
    finally:
        if blocker is not None:
            blocker.uninstall()
        if t is not None:
            t.join(5.0)
        _teardown(booted)


# -- lock-free shared budget ----------------------------------------------


def test_shared_budget_lock_free():
    """The MTPU3xx auditor proxies the admission module's threading:
    exercising the SharedBudget/TokenCounter fast path from many
    threads must mint ZERO audited locks beyond the PlaneStats
    aggregate mutex (constructed once, never touched per-admit by the
    per-loop path) — and leave the lock graph clean."""
    from minio_tpu.analysis.lockorder import LockOrderAuditor
    from minio_tpu.server import admission as adm_mod

    aud = LockOrderAuditor(targets=("minio_tpu.server.admission",))
    with aud.installed():
        stats = adm_mod.PlaneStats()
        baseline = aud._serial  # PlaneStats' one aggregate mutex
        assert baseline >= 1
        cells = [stats.add_loop() for _ in range(3)]
        budget = adm_mod.SharedBudget()
        errors = []

        def hammer(ix):
            try:
                cell = cells[ix % 3]
                for r in range(400):
                    tc = budget.tenant(f"t{r % 4}")
                    if tc.try_acquire(8):
                        cell.enter()
                        cell.shed_inc("tenant")
                        cell.leave()
                        tc.release()
                    if budget.select.try_acquire(4):
                        budget.select.release()
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not errors
        # the hot path minted no locks: lock-free to the auditor
        assert aud._serial == baseline
        for name, v in budget.tenant_values().items():
            assert v == 0, f"leaked slot on {name}"
        for name, hwm in budget.tenant_hwm().items():
            assert hwm <= 8, f"cap exceeded on {name}: {hwm}"
        assert budget.select.hwm <= 4
    assert aud.report() == []


def test_token_counter_exact_under_contention():
    """TokenCounter's one-sided invariant, empirically: with LIMIT=3
    and 8 threads spinning acquire/release, the *independently
    measured* concurrent-holder count never exceeds the limit (the
    counter may over-shed, never over-admit)."""
    from minio_tpu.server.admission import TokenCounter

    LIMIT, THREADS, ROUNDS = 3, 8, 500
    tc = TokenCounter()
    mu = threading.Lock()
    holders = {"cur": 0, "max": 0}
    admitted = {"n": 0}

    def worker():
        for _ in range(ROUNDS):
            if tc.try_acquire(LIMIT):
                with mu:
                    holders["cur"] += 1
                    holders["max"] = max(holders["max"], holders["cur"])
                    admitted["n"] += 1
                with mu:
                    holders["cur"] -= 1
                tc.release()

    threads = [threading.Thread(target=worker) for _ in range(THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert holders["max"] <= LIMIT
    assert tc.hwm <= LIMIT
    assert tc.value() == 0
    assert admitted["n"] > 0  # the cap gate did admit traffic
