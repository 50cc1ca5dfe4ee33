"""Spans inside the program (utils/spans.py): the primitive itself, the
request identifier across the iopool and the batcher, the three sinks,
the interpreter probe, the table of names, and the four tables that account
a request's wall and the server's CPU (requests, fanout, cpu, loops)."""

import asyncio
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from minio_tpu.codec import backend as backend_mod
from minio_tpu.codec.batcher import BatchingBackend
from minio_tpu.codec.erasure import Erasure
from minio_tpu.codec.telemetry import KERNEL_STATS, instrument
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.parallel import iopool
from minio_tpu.server.http import S3Server
from minio_tpu.storage.xl import XLStorage
from minio_tpu.utils import spans

from s3client import S3Client

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "minio_tpu")

# the table of ISSUE 25 (PERF.md section 3 keeps it): every name a boundary stamps
TABLE = [
    "aio_queue_wait", "s3_request", "body_read_wait", "resp_write_wait",
    "sigv4_verify", "hashreader_read", "ol_put_object", "ol_get_object",
    "get_first_write", "ol_get_object_info", "ol_delete_object", "nslock_wait", "meta_read_all",
    "xl_read_version", "xl_read_all", "xl_write_all", "xl_rename_data",
    "xl_delete_version", "xl_delete_file", "iopool_queue_wait", "iopool_job",
    "xl_shard_write", "xl_shard_fsync", "xl_shard_read", "iopool_result_wait",
    "put_close_wait", "put_rename_wait",
    "stream_assemble", "stream_codec_wait", "stream_disk",
    "stream_readahead_wait", "batch_queue_wait",
    "batch_flush", "flush_to_launch", "batch_result_wait", "seam_matrix",
    "seam_stage",
    "seam_launch", "seam_kernel_wait", "seam_d2h", "probe",
]


def constants() -> "dict[str, str]":
    return {k: v for k, v in vars(spans).items()
            if k.isupper() and isinstance(v, str) and k != "PREFIX"}


def counters(name: str) -> "list[int]":
    """[count, wall_ns, cpu_ns] of the name, summed over roles."""
    out = [0, 0.0, 0.0]
    for row in spans.snapshot()["spans"]:
        if row["name"] == name:
            out[0] += row["count"]
            out[1] += row["wall_seconds"]
            out[2] += row["cpu_seconds"] or 0.0
    return out


@pytest.fixture(autouse=True)
def clean_request():
    spans.end_request()
    yield
    spans.end_request()


# -- the primitive -------------------------------------------------------------


def test_nesting_and_parent_index():
    rid = spans.begin_request(True)
    with spans.span(spans.S3_REQUEST):
        with spans.span(spans.OL_GET_OBJECT):
            with spans.span(spans.NSLOCK_WAIT):
                pass
            with spans.span(spans.META_READ_ALL):
                with spans.span(spans.XL_READ_VERSION):
                    pass
        with spans.span(spans.RESP_WRITE_WAIT):
            pass
        assert spans.request_id() == rid
    recs = spans.end_request()
    assert [r["name"] for r in recs] == [
        "s3_request", "ol_get_object", "nslock_wait", "meta_read_all",
        "xl_read_version", "resp_write_wait"]
    assert [r["parent"] for r in recs] == [-1, 0, 1, 1, 3, 0]
    for r in recs[1:]:
        p = recs[r["parent"]]
        assert p["start_us"] <= r["start_us"]
        assert r["start_us"] + r["dur_us"] <= p["start_us"] + p["dur_us"]
    assert spans.request_id() == ""


def test_cpu_is_not_above_wall_and_sleep_is_not_cpu():
    before = counters("meta_read_all")
    with spans.span(spans.META_READ_ALL) as sp:
        t = time.monotonic()
        while time.monotonic() - t < 0.05:
            pass
        time.sleep(0.1)
    after = counters("meta_read_all")
    wall, cpu = after[1] - before[1], after[2] - before[2]
    assert after[0] - before[0] == 1
    assert 0.14 < wall < 5.0 and abs(wall - sp.seconds) < 1e-5
    assert 0.03 < cpu <= wall - 0.08  # the sleep is wall, not CPU


def test_only_the_spans_that_bound_a_layer_read_the_cpu_clock(monkeypatch):
    """A reading of the thread's CPU clock is a system call with the GIL held
    (5.6 us on the benchmark's host): the leaf spans are wall only."""
    calls = []
    monkeypatch.setattr(spans, "_cpu", lambda: calls.append(1) or 0)
    with spans.span(spans.XL_READ_ALL), spans.span(spans.SEAM_D2H), \
            spans.span(spans.IOPOOL_JOB):
        pass
    assert calls == []
    with spans.span(spans.S3_REQUEST), spans.span(spans.META_READ_ALL):
        pass
    assert len(calls) == 4
    assert {spans.S3_REQUEST, spans.META_READ_ALL, spans.BATCH_FLUSH} <= spans.CPU_SPANS
    assert not {spans.XL_READ_VERSION, spans.XL_READ_ALL, spans.IOPOOL_JOB} & spans.CPU_SPANS
    rows = {r["name"]: r for r in spans.snapshot()["spans"]}
    assert rows["xl_read_all"]["cpu_seconds"] is None
    assert rows["s3_request"]["cpu_seconds"] is not None


def test_wait_arithmetic():
    before = counters("iopool_queue_wait")
    since = spans.now() - 7_000_000
    end = spans.wait(spans.IOPOOL_QUEUE_WAIT, since, now_ns=since + 5_000_000)
    assert end == since + 5_000_000
    spans.wait(spans.IOPOOL_QUEUE_WAIT, since, now_ns=since + 2_000_000)
    after = counters("iopool_queue_wait")
    assert after[0] - before[0] == 2
    assert abs((after[1] - before[1]) - 0.007) < 1e-5
    assert after[2] - before[2] == 0  # a wait has no CPU time
    assert spans.wait(spans.IOPOOL_QUEUE_WAIT, since) >= since + 7_000_000


def test_without_a_listener_a_span_keeps_no_record():
    spans.begin_request(False)
    before = counters("xl_read_all")[0]
    with spans.span(spans.XL_READ_ALL) as sp:
        assert sp._rec is None and sp._ann is None
        assert spans._state().sink is None and spans._state().parent is None
    assert counters("xl_read_all")[0] == before + 1  # only the counters moved
    assert spans.end_request() is None


def test_a_span_that_outlives_its_parent_hangs_off_the_root():
    """Work begun asynchronously ends after the span it was submitted under:
    the rendered parent is the nearest span that really encloses it."""
    spans.begin_request(True)
    done = threading.Event()
    with spans.span(spans.S3_REQUEST):
        with spans.span(spans.STREAM_CODEC_WAIT):
            ctx = spans.capture()

            def later():
                with spans.adopt(ctx), spans.span(spans.BATCH_FLUSH):
                    time.sleep(0.05)
                done.set()

            threading.Thread(target=later).start()
        done.wait(5)
    recs = spans.end_request()
    flush = next(r for r in recs if r["name"] == "batch_flush")
    assert recs[flush["parent"]]["name"] == "s3_request"


class FakeAnnotation:
    enabled = False
    seen: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        FakeAnnotation.seen.append(("enter", self.name, self.kw))

    def __exit__(self, *exc):
        FakeAnnotation.seen.append(("exit", self.name, self.kw))


def test_profiler_annotation_only_while_a_session_runs(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", FakeAnnotation)
    FakeAnnotation.seen = []
    rid = spans.begin_request(False)
    with spans.span(spans.SEAM_LAUNCH):
        pass
    assert FakeAnnotation.seen == []  # no session: the annotation is not even built
    FakeAnnotation.enabled = True
    try:
        with spans.span(spans.BATCH_FLUSH, jobs=2):
            with spans.span(spans.SEAM_LAUNCH):
                pass
    finally:
        FakeAnnotation.enabled = False
    assert [(w, n) for w, n, _ in FakeAnnotation.seen] == [
        ("enter", "mtpu/batch_flush"), ("enter", "mtpu/seam_launch"),
        ("exit", "mtpu/seam_launch"), ("exit", "mtpu/batch_flush")]
    assert FakeAnnotation.seen[0][2] == {"req": rid, "jobs": 2}
    assert FakeAnnotation.seen[1][2] == {"req": rid}


def test_spans_does_not_import_jax():
    import subprocess
    import sys

    code = ("import sys; from minio_tpu.utils import spans\n"
            "with spans.span(spans.S3_REQUEST): pass\n"
            "assert 'jax' not in sys.modules and spans._tracing() is None\n"
            "assert spans.snapshot()['spans'][0]['name'] == 's3_request'\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       cwd=os.path.dirname(PKG), timeout=60)
    assert p.returncode == 0, p.stderr


# -- the request's identity across threads ---------------------------------------


def test_request_id_rides_a_real_iopool_job_and_is_restored():
    pool = iopool.IOPool(queues=2, depth=4, name_prefix="iopool-spans")
    try:
        seen = {}

        def job():
            seen["role"] = spans._state().role
            with spans.span(spans.XL_SHARD_READ):
                return spans.request_id()

        rid = spans.begin_request(True)
        with spans.span(spans.S3_REQUEST):
            fut = pool.submit("disk-a", job)
            assert fut.result_or_raise(5) == rid
        recs = spans.end_request()
        names = [r["name"] for r in recs]
        assert {"iopool_queue_wait", "iopool_job", "xl_shard_read"} <= set(names)
        job_rec = next(r for r in recs if r["name"] == "iopool_job")
        read_rec = next(r for r in recs if r["name"] == "xl_shard_read")
        assert job_rec["role"] == "iopool" == seen["role"] and recs[0]["role"] != "iopool"
        assert recs[read_rec["parent"]] is job_rec
        # the worker is given back as it was: the next job belongs to nobody
        assert pool.submit("disk-a", job).result_or_raise(5) == ""
    finally:
        pool.shutdown()


class _SeesRequests(backend_mod.CpuBackend):
    def __init__(self):
        self.seen = []

    def digest(self, shards, lengths=None):
        self.seen.append((threading.current_thread().name, spans.request_id()))
        with spans.span(spans.SEAM_LAUNCH):
            return super().digest(shards, lengths)


def test_request_id_rides_a_real_batcher_flush_and_is_restored():
    inner = _SeesRequests()
    b = BatchingBackend(inner, deadline_s=0.5)
    try:
        shards = np.arange(2 * 4 * 64, dtype=np.uint8).reshape(2, 4, 64)
        ids, recs, go = {}, {}, threading.Barrier(2)

        def client(i):
            ids[i] = spans.begin_request(True)
            with spans.span(spans.S3_REQUEST):
                go.wait(5)
                b.digest(shards)
            recs[i] = spans.end_request()

        with b._cv:
            b._enter(-1)  # a client that never submits: the flush waits its deadline out
        threads = [threading.Thread(target=client, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        with b._cv:
            b._exit(-1)
        assert len(inner.seen) == 1  # one coalesced flush served both
        where, req = inner.seen[0]
        assert where == "codec-batcher" and set(req.split(",")) == {ids[0], ids[1]}
        for i in range(2):
            names = [r["name"] for r in recs[i]]
            # the flush's spans ride every request it served
            assert {"batch_queue_wait", "batch_flush", "seam_launch",
                    "batch_result_wait"} <= set(names), names
            flush = next(r for r in recs[i] if r["name"] == "batch_flush")
            assert flush["role"] == "batcher"
        b.digest(shards)  # nobody's request: the dispatcher was given back
        assert inner.seen[1] == ("codec-batcher", "")
    finally:
        b.shutdown()


def test_seam_spans_and_flush_to_launch_through_the_device_backend(monkeypatch):
    monkeypatch.setenv("MINIO_MESH", "0")  # one device, as in the one-chip cells
    names = ("batch_queue_wait", "batch_flush", "flush_to_launch", "batch_result_wait",
             "seam_stage", "seam_launch", "seam_kernel_wait", "seam_d2h")
    before = {n: counters(n)[0] for n in names}
    ops_before = sum(r["calls"] for r in KERNEL_STATS.snapshot()["ops"])
    b = BatchingBackend(instrument(backend_mod.TpuBackend()), deadline_s=0.01)
    try:
        shards = np.arange(1 * 6 * 128, dtype=np.uint8).reshape(1, 6, 128)
        want = backend_mod.CpuBackend().digest(shards)
        assert (b.digest(shards) == want).all()
    finally:
        b.shutdown()
    moved = {n: counters(n)[0] - before[n] for n in names}
    assert moved == {n: 1 for n in names}, moved
    snap = KERNEL_STATS.snapshot()
    assert sum(r["calls"] for r in snap["ops"]) == ops_before + 1
    flush = next(r for r in snap["spans"] if r["name"] == "flush_to_launch")
    assert flush["role"] == "batcher" and flush["cpu_seconds"] is None


def test_stream_stages_and_spans_share_their_clock_readings():
    """kernel-stats.stages is fed from the spans' own readings: over one
    encode both tables move by the same seconds."""
    class Sink:
        def write(self, data):
            pass

    def stage_seconds(stage):
        return sum(r["seconds"] for r in KERNEL_STATS.snapshot()["stages"]
                   if r["op"] == "put" and r["stage"] == stage)

    import io

    a_disk, a_span = stage_seconds("disk"), counters("stream_disk")[1]
    b_asm, b_span = stage_seconds("assemble"), counters("stream_assemble")[1]
    er = Erasure(4, 2, block_size=4096)
    er.encode(io.BytesIO(os.urandom(3 * 4096 + 100)), [Sink() for _ in range(6)], 4)
    # six-digit rounding on both sides of both tables
    assert abs((stage_seconds("disk") - a_disk) - (counters("stream_disk")[1] - a_span)) < 1e-4
    assert abs((stage_seconds("assemble") - b_asm)
               - (counters("stream_assemble")[1] - b_span)) < 1e-4
    assert counters("stream_disk")[1] > a_span


# -- the counters' sink -------------------------------------------------------------


def test_snapshot_merges_threads_and_keeps_what_exited_threads_left():
    before = counters("ol_get_object_info")[0]

    def work(n):
        for _ in range(n):
            with spans.span(spans.OL_GET_OBJECT_INFO):
                pass

    threads = [threading.Thread(target=work, args=(n,), name=f"iopool-t{n}")
               for n in (3, 5)]
    for t in threads:
        t.start()
    work(2)
    for t in threads:
        t.join()
    assert counters("ol_get_object_info")[0] == before + 10  # exited threads are not lost
    assert counters("ol_get_object_info")[0] == before + 10  # ... nor counted twice
    rows = [r for r in spans.snapshot()["spans"] if r["name"] == "ol_get_object_info"]
    assert {r["role"] for r in rows} >= {"iopool", "other"}
    assert all(r["cpu_seconds"] <= r["wall_seconds"] + 1e-6 for r in rows)


@pytest.mark.parametrize("thread_name,role", [
    ("aio-loop-0", "loop"), ("aio2-worker-3", "handler"), ("iopool-7", "iopool"),
    ("codec-batcher", "batcher"), ("codec-batcher-sub1", "batcher"),
    ("aio0-stream-1", "other"), ("MainThread", "other"),
    ("codec-warmer", "warmer"), ("data-crawler", "crawler"), ("interp-probe", "probe")])
def test_role_comes_from_the_thread_name(thread_name, role):
    assert spans._role_of(thread_name) == role


def test_kernel_stats_carries_spans_and_probe_and_keeps_the_old_tables():
    with spans.span(spans.XL_READ_ALL):
        pass
    snap = KERNEL_STATS.snapshot()
    assert {"ops", "batch", "stages", "iopool", "h2d", "d2h", "device_passes",
            "streams", "hedge", "placement"} <= set(snap)
    row = next(r for r in snap["spans"] if r["name"] == "xl_read_all")
    assert set(row) == {"role", "name", "count", "wall_seconds", "cpu_seconds"}
    assert {"samples", "late_seconds", "late_max_seconds", "loops"} <= set(snap["probe"])
    assert set(snap["batch"]) == {"flushes", "jobs", "blocks", "wait_seconds"}
    assert set(snap["iopool"]) == {"queues", "depth_hwm", "slowest_job_seconds"}


def test_iopool_depth_mark_lives_on_the_queue():
    iopool.reset_pool()
    pool = iopool.get_pool()
    gate = threading.Event()
    futs = [pool.submit("disk-hwm", gate.wait) for _ in range(4)]
    assert iopool.depth_hwm() >= 3  # one running, three behind it
    assert KERNEL_STATS.snapshot()["iopool"]["depth_hwm"] >= 3
    assert not hasattr(KERNEL_STATS, "record_io_depth")
    gate.set()
    for f in futs:
        f.result_or_raise(5)
    iopool.reset_pool()
    assert iopool.depth_hwm() == 0


# -- the probe ---------------------------------------------------------------------


def test_probe_counts_samples_and_stops_at_shutdown(tmp_path, leakcheck):
    assert not spans.PROBE.running()
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    srv = S3Server(ErasureObjects(disks, block_size=4096), address="127.0.0.1:0").start()
    try:
        assert spans.PROBE.running()
        a = spans.PROBE.snapshot()
        time.sleep(0.5)
        b = spans.PROBE.snapshot()
        assert 10 <= b["samples"] - a["samples"] <= 30  # 50 a second at most
        assert b["late_seconds"] >= a["late_seconds"] >= 0
        assert b["late_max_seconds"] < 0.5
        loops = {c["loop"]: c["samples"] for c in b["loops"]}
        assert loops and all(n > 0 for n in loops.values())
    finally:
        srv.shutdown()
    assert not spans.PROBE.running()
    assert not any(t.name == "interp-probe" for t in threading.enumerate())


def test_probe_is_shared_by_the_servers_of_a_process():
    spans.PROBE.start()
    spans.PROBE.start()
    try:
        spans.PROBE.stop()
        assert spans.PROBE.running()  # one user is left
    finally:
        spans.PROBE.stop()
    assert not spans.PROBE.running()


def test_loop_probe_sees_a_stalled_loop():
    cell = spans.LoopProbe(0)
    loop = asyncio.new_event_loop()

    async def main():
        cell.start(loop)
        await asyncio.sleep(0.05)
        time.sleep(0.1)  # the loop is held: its timer fires late  # noqa: MTPU108
        await asyncio.sleep(0.05)
        cell.stop()

    try:
        loop.run_until_complete(main())
    finally:
        loop.close()
    assert cell.samples >= 2 and cell.late_max_ns > 50_000_000
    assert cell.late_ns >= cell.late_max_ns


# -- admin trace, and the server end to end -------------------------------------------


@pytest.fixture()
def server(tmp_path):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=4096, min_part_size=1)
    srv = S3Server(ol, address="127.0.0.1:0").start()
    yield srv
    srv.shutdown()


def entry_of(server, api: str) -> dict:
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        _, items = server.tracer.poll(0)
        for e in items:
            if e["api"] == api:
                return e
        time.sleep(0.05)
    raise AssertionError(f"no trace entry for {api}")


def test_admin_trace_entry_of_a_put_carries_its_spans(server):
    c = S3Client(server.endpoint)
    c.make_bucket("spanbkt")
    server.tracer.poll(0)  # someone listens from here on
    r = c.put_object("spanbkt", "obj", os.urandom(3 * 4096))
    assert r.status == 200
    e = entry_of(server, "PutObject")
    recs = e["spans"]
    root = recs[0]
    assert root["name"] == "s3_request" and root["parent"] == -1 and root["start_us"] == 0
    assert e["request_id"] == r.headers["x-amz-request-id"]
    assert 0 <= e["queue_wait_us"] < 5_000_000  # before the root: beside the spans
    assert re.fullmatch(r"[0-9A-F]{16}", e["request_id"])
    names = {s["name"] for s in recs}
    assert {"sigv4_verify", "ol_put_object", "nslock_wait", "hashreader_read",
            "body_read_wait", "stream_assemble", "stream_codec_wait", "stream_disk",
            "iopool_job", "iopool_queue_wait", "xl_shard_write", "xl_rename_data",
            "resp_write_wait"} <= names, names
    for i, s in enumerate(recs[1:], 1):
        p = recs[s["parent"]]
        assert 0 <= s["parent"] < len(recs) and s["parent"] != i
        assert p["start_us"] <= s["start_us"], (s, p)
        assert s["start_us"] + s["dur_us"] <= p["start_us"] + p["dur_us"], (s, p)
        assert s.get("cpu_us", 0) <= s["dur_us"]
    assert "cpu_us" in root and "cpu_us" not in next(s for s in recs if s["name"] == "xl_shard_write")
    # what ran on the handler's own thread adds up to no more than the request
    handler = root["role"]
    own = [s for s in recs[1:] if s["parent"] == 0 and s["role"] == handler]
    assert sum(s["dur_us"] for s in own) <= root["dur_us"]
    assert {s["role"] for s in recs} >= {handler, "iopool"}


def test_without_a_subscriber_requests_leave_no_trace_entry(server):
    c = S3Client(server.endpoint)
    c.make_bucket("quiet")
    before = counters("s3_request")[0]
    assert c.put_object("quiet", "k", b"x").status == 200
    time.sleep(0.2)
    assert server.tracer.ring.since(0)[1] == []
    assert counters("s3_request")[0] > before  # the counters are always on


def test_request_id_is_the_same_in_header_and_error_body(server):
    c = S3Client(server.endpoint)
    r = c.get_object("no-such-bucket", "k")
    assert r.status == 404
    rid = r.headers["x-amz-request-id"]
    assert re.fullmatch(r"[0-9A-F]{16}", rid)
    assert f"<RequestId>{rid}</RequestId>" in r.body.decode()
    assert c.get_object("no-such-bucket", "k").headers["x-amz-request-id"] != rid


def test_served_requests_move_every_layer_counter_and_export_the_probe(server):
    c = S3Client(server.endpoint)
    c.make_bucket("layers")
    data = os.urandom(2 * 4096)
    names = ("aio_queue_wait", "s3_request", "sigv4_verify", "ol_put_object", "ol_get_object",
             "ol_get_object_info", "ol_delete_object", "meta_read_all", "xl_read_version",
             "xl_read_all", "xl_write_all", "xl_shard_read", "xl_shard_fsync",
             "xl_delete_file", "iopool_result_wait", "seam_kernel_wait", "seam_d2h")
    before = {n: counters(n)[0] for n in names}
    assert c.put_object("layers", "k", data).status == 200
    assert c.get_object("layers", "k").body == data
    assert c.head_object("layers", "k").status == 200
    assert c.request("DELETE", "/layers/k").status == 204
    still = [n for n in names if counters(n)[0] == before[n]]
    assert not still, still
    roles = {r["role"] for r in spans.snapshot()["spans"] if r["name"] == "s3_request"}
    assert "handler" in roles
    text = c.request("GET", "/minio-tpu/prometheus/metrics").body.decode()
    for family in ("miniotpu_interpreter_probe_late_seconds_total",
                   "miniotpu_interpreter_probe_samples_total",
                   "miniotpu_server_loop_lag_seconds_total"):
        assert re.search(rf"^{family}(\{{[^}}]*\}})? [0-9.e+-]+$", text, re.M), family


# -- kernel-stats.requests: self time by verb ------------------------------------------


def verb_row(verb: str) -> "dict | None":
    return next((r for r in spans.snapshot()["requests"] if r["verb"] == verb), None)


def _nested():
    with spans.span(spans.OL_PUT_OBJECT):
        with spans.span(spans.NSLOCK_WAIT):
            time.sleep(0.002)
        with spans.span(spans.STREAM_DISK):
            with spans.span(spans.XL_SHARD_WRITE):
                time.sleep(0.002)
    with spans.span(spans.RESP_WRITE_WAIT):
        pass
    return {"s3_request", "ol_put_object", "nslock_wait", "stream_disk",
            "xl_shard_write", "resp_write_wait"}


def _child_on_another_thread():
    """An iopool job under adopt: its time is its own thread's, the request's
    thread was waiting in the span it holds open meanwhile."""
    with spans.span(spans.STREAM_DISK):
        ctx = spans.capture()

        def job():
            with spans.adopt(ctx), spans.span(spans.IOPOOL_JOB), \
                    spans.span(spans.XL_SHARD_WRITE):
                time.sleep(0.01)

        t = threading.Thread(target=job, name="iopool-self-time")
        t.start()
        t.join(5)
        assert not t.is_alive()
    return {"s3_request", "stream_disk"}


def _child_outlives_its_parent():
    done = threading.Event()
    with spans.span(spans.STREAM_CODEC_WAIT):
        ctx = spans.capture()

        def later():
            with spans.adopt(ctx), spans.span(spans.BATCH_FLUSH):
                time.sleep(0.03)
            done.set()

        threading.Thread(target=later, name="codec-batcher-self-time").start()
    assert done.wait(5)
    return {"s3_request", "stream_codec_wait"}


@pytest.mark.parametrize("body", [_nested, _child_on_another_thread,
                                  _child_outlives_its_parent])
def test_self_times_sum_to_the_roots_wall_to_the_nanosecond(body):
    verb = "Sum-" + body.__name__
    spans.begin_request(False)
    with spans.span(spans.S3_REQUEST) as root:
        names = body()
    spans.end_request(verb, 1_234_000)
    n, wall, cpu, queue, own = spans._state().verbs[verb]
    assert (n, wall, queue) == (1, root.wall_ns, 1_234_000)
    assert 0 <= cpu <= wall
    assert set(own) == names  # spans of other threads are not the request thread's
    assert sum(ns for _, ns in own.values()) == wall  # exact: integers
    assert all(ns >= 0 for _, ns in own.values())
    row = verb_row(verb)
    assert set(row) == {"verb", "count", "wall_seconds", "cpu_seconds",
                        "queue_wait_seconds", "self"}
    assert abs(sum(v[1] for v in row["self"].values()) - row["wall_seconds"]) < 1e-9
    assert row["queue_wait_seconds"] == 0.001234
    if body is not _nested:
        # the child's 10-30 ms ran elsewhere: the span that waited keeps them
        held = "stream_disk" if body is _child_on_another_thread else "s3_request"
        assert own[held][1] >= 9_000_000


def test_a_span_is_what_its_children_on_the_same_thread_leave():
    spans.begin_request(False)
    with spans.span(spans.S3_REQUEST):
        with spans.span(spans.OL_GET_OBJECT) as ol:
            with spans.span(spans.META_READ_ALL) as a:
                time.sleep(0.005)
            with spans.span(spans.META_READ_ALL) as b:
                pass
            spans.wait(spans.GET_FIRST_WRITE, ol.t0)  # a hand-over: no part of the nesting
    spans.end_request("Leave")
    own = spans._state().verbs["Leave"][4]
    assert own["meta_read_all"] == [2, a.wall_ns + b.wall_ns]
    assert own["ol_get_object"] == [1, ol.wall_ns - a.wall_ns - b.wall_ns]
    assert "get_first_write" not in own


def test_the_fold_by_verb():
    def serve(verb, queue_ns):
        spans.begin_request(False)
        with spans.span(spans.S3_REQUEST), spans.span(spans.SIGV4_VERIFY):
            pass
        spans.end_request(verb, queue_ns)

    before = verb_row("other")["count"] if verb_row("other") else 0
    for verb, q in (("FoldA", 5), ("FoldB", 7), ("FoldA", 11), ("", 0)):
        serve(verb, q)
    verbs = spans._state().verbs
    assert verbs["FoldA"][0] == 2 and verbs["FoldA"][3] == 16
    assert verbs["FoldB"][0] == 1 and verbs["FoldB"][3] == 7
    assert verbs["FoldA"][4]["sigv4_verify"][0] == 2
    assert verb_row("other")["count"] == before + 1  # an action nobody resolved
    assert [r["verb"] for r in spans.snapshot()["requests"]] == sorted(
        r["verb"] for r in spans.snapshot()["requests"])


def test_nothing_is_kept_without_begin_request():
    st = spans._state()
    kept = {v: row[0] for v, row in st.verbs.items()}
    with spans.span(spans.S3_REQUEST), spans.span(spans.SIGV4_VERIFY):
        assert st.acc is None
    assert spans.end_request("Nobody") is None
    assert {v: row[0] for v, row in st.verbs.items()} == kept
    spans.begin_request(False)
    assert spans.end_request("Nobody") is None  # a request that opened no span
    assert "Nobody" not in st.verbs and st.acc is None


def test_verb_rows_outlive_their_thread():
    def serve():
        spans.begin_request(False)
        with spans.span(spans.S3_REQUEST):
            pass
        spans.end_request("Outlive")

    threads = [threading.Thread(target=serve, name=f"aio0-worker-{i}") for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(5)
    assert verb_row("Outlive")["count"] == 3
    assert verb_row("Outlive")["count"] == 3  # folded once


def test_a_put_waits_under_the_two_names_and_not_the_anonymous_one(tmp_path):
    import io

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=4096)
    ol.make_bucket("named")
    before = {p: dict(spans.snapshot()["fanout"].get(p, {"count": 0}))
              for p in ("put_flush", "put_close", "put_rename")}
    spans.begin_request(False)
    with spans.span(spans.S3_REQUEST):
        ol.put_object("named", "k", io.BytesIO(os.urandom(3 * 4096)), 3 * 4096)
    spans.end_request("NamedPut")
    own = spans._state().verbs["NamedPut"][4]
    assert own["put_close_wait"][0] == 1 and own["put_rename_wait"][0] == 1
    assert "iopool_result_wait" not in own
    fan = spans.snapshot()["fanout"]
    for p in before:
        assert fan[p]["count"] == before[p]["count"] + 1, p
    # the call sites: the anonymous wait went from the two that got a name
    with open(os.path.join(PKG, "objectlayer", "erasure_object.py"), encoding="utf-8") as f:
        text = f.read()
    assert "span_name=spans.PUT_CLOSE_WAIT" in text and "span_name=spans.PUT_RENAME_WAIT" in text
    assert len(re.findall(r"\bspans\.IOPOOL_RESULT_WAIT\b", SOURCES)) == 3  # 3 in iopool.py


# -- kernel-stats.fanout: the slowest job of a wait ----------------------------------------


@pytest.mark.parametrize("slow", ["queue", "run"])
def test_fanout_tells_a_late_start_from_a_long_run(slow):
    pool = iopool.IOPool(queues=4, depth=4, name_prefix="iopool-fan")
    try:
        a = spans.snapshot()["fanout"].get("put_close", dict.fromkeys(
            ("count", "wall_seconds", "last_queue_seconds", "last_run_seconds"), 0))
        anon = counters("iopool_result_wait")[0]
        if slow == "queue":
            blocker = pool.submit("disk-a", lambda: time.sleep(0.15))
            ops = [("disk-a", lambda: None), ("disk-b", lambda: None)]
        else:
            blocker = None
            ops = [("disk-a", lambda: time.sleep(0.15)), ("disk-b", lambda: None)]
        assert iopool.fanout(ops, pool, span_name=spans.PUT_CLOSE_WAIT) == [None, None]
        if blocker is not None:
            blocker.result_or_raise(5)
        b = spans.snapshot()["fanout"]["put_close"]
        assert set(b) == {"count", "wall_seconds", "last_queue_seconds", "last_run_seconds"}
        d = {k: b[k] - a[k] for k in b}
        assert d["count"] == 1 and 0.1 < d["wall_seconds"] < 5
        late, long_ = d["last_queue_seconds"], d["last_run_seconds"]
        if slow == "queue":
            assert late > 0.1 and long_ < 0.05
        else:
            assert long_ > 0.1 and late < 0.05
        # the wait opens once every job is submitted: a job is queued a little before it
        assert late + long_ <= d["wall_seconds"] + 0.05
        assert counters("iopool_result_wait")[0] == anon  # named, so not doubled
        # with no name it is the anonymous wait, and no phase
        assert iopool.fanout([("disk-b", lambda: None)], pool) == [None]
        assert counters("iopool_result_wait")[0] == anon + 1
        assert spans.snapshot()["fanout"]["put_close"]["count"] == b["count"]
    finally:
        pool.shutdown()


def test_a_flush_is_told_by_the_job_that_made_its_quorum():
    pool = iopool.IOPool(queues=4, depth=4, name_prefix="iopool-quorum")
    try:
        fl = iopool.ShardFlusher(pool)
        gate = threading.Event()
        jobs = [(0, "disk-0", lambda: None, 1),
                (1, "disk-1", lambda: time.sleep(0.05), 1),
                (2, "disk-2", lambda: gate.wait(5), 1)]
        assert fl.flush(jobs, quorum=2) == set()
        job = fl.quorum_job
        gate.set()
        fl.drain()
        assert job.queued_ns <= job.started_ns <= job.done_ns
        assert 40_000_000 < job.done_ns - job.started_ns < 2_000_000_000  # slot 1's, not the straggler's
    finally:
        pool.shutdown()


def test_a_job_keeps_its_three_stamps_for_free(monkeypatch):
    pool = iopool.IOPool(queues=2, depth=4, name_prefix="iopool-stamps")
    try:
        reads = []
        real = spans.now
        fut = pool.submit("disk-a", lambda: None)
        fut.result_or_raise(5)
        monkeypatch.setattr(spans, "now", lambda: reads.append(1) or real())
        fut = pool.submit("disk-a", lambda: time.sleep(0.01))
        fut.result_or_raise(5)
        monkeypatch.undo()
        # enqueue, dequeue, the job's start and end: the readings the spans
        # made before the stamps were kept (the waiter's own span is two more)
        assert len(reads) <= 6
        assert 0 < fut.queued_ns <= fut.started_ns < fut.done_ns
        assert fut.done_ns - fut.started_ns >= 10_000_000
    finally:
        pool.shutdown()


# -- kernel-stats.cpu and .loops: read at snapshot time ---------------------------------------


def test_cpu_by_role_is_the_schedulers_account():
    stop, burnt = threading.Event(), threading.Event()

    def spin():
        t = time.thread_time()
        while time.thread_time() - t < 0.25:
            pass
        burnt.set()
        stop.wait(10)

    sleeper = threading.Thread(target=stop.wait, args=(10,), name="codec-batcher-cpu-test")
    spinner = threading.Thread(target=spin, name="iopool-cpu-test")
    a = spans.snapshot()["cpu"]
    sleeper.start()
    spinner.start()
    assert burnt.wait(30)
    b = spans.snapshot()["cpu"]  # both alive: all they burnt is on the books
    stop.set()
    for t in (sleeper, spinner):
        t.join(5)
        assert not t.is_alive()
    c = spans.snapshot()["cpu"]
    assert 0.2 < b["iopool"] - a.get("iopool", 0) < 0.5
    assert b.get("batcher", 0) - a.get("batcher", 0) < 0.05

    def roles(t):
        return sum(v for k, v in t.items() if k != "process_seconds")

    d_roles, d_proc = roles(b) - roles(a), b["process_seconds"] - a["process_seconds"]
    assert d_proc > 0.2 and abs(d_roles - d_proc) <= 0.05 * d_proc + 0.03, (d_roles, d_proc)
    # monotone, every row: a thread that has exited keeps what it was last seen with
    for x, y in ((a, b), (b, c)):
        assert all(y[k] >= v for k, v in x.items()), (x, y)
    assert c["iopool"] - b["iopool"] < 0.05
    assert set(c) >= {"iopool", "batcher", "other", "process_seconds"}
    assert roles(c) <= c["process_seconds"] * 1.02 + 0.05


def test_cpu_of_a_task_that_is_gone_reads_none():
    t = threading.Thread(target=lambda: None)
    t.start()
    tid = t.native_id
    t.join(5)
    deadline = time.monotonic() + 5  # join() returns a moment before the task is gone
    while spans._task_cpu_ns(tid) is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert spans._task_cpu_ns(tid) is None  # looked up by the kernel: no dangling handle
    assert spans._task_cpu_ns(threading.get_native_id()) > 0
    # ... and it is the number /proc prints for the thread
    me = threading.get_native_id()
    with open(f"/proc/self/task/{me}/schedstat") as f:
        printed = int(f.read().split()[0])
    assert 0 <= spans._task_cpu_ns(me) - printed < 50_000_000


def test_a_snapshot_makes_no_call_that_hands_the_gil_back(monkeypatch):
    """Files under /proc cost a hand-back of the GIL an open, a read, a close and a
    directory entry (170-180 ms a listing under load on the chip's host): the CPU
    table reads clocks and nothing else."""
    import builtins

    def refuse(*a, **kw):
        raise AssertionError("a file system call inside a snapshot")

    spans.snapshot()
    for mod, name in ((os, "listdir"), (os, "scandir"), (os, "stat"), (os, "open"),
                      (builtins, "open")):
        monkeypatch.setattr(mod, name, refuse)
    cpu = spans.snapshot()["cpu"]
    monkeypatch.undo()
    assert cpu["process_seconds"] > 0 and cpu["other"] > 0


def test_native_is_what_the_tasks_python_does_not_know_have_burnt():
    """The remainder against the kernel's own list of the process's tasks: the CPU
    clocks of the tids that no Python thread owns."""
    known = {t.native_id for t in threading.enumerate()}
    a = spans.snapshot()["cpu"]
    others = [int(t) for t in os.listdir("/proc/self/task") if int(t) not in known]
    burnt = sum(spans._task_cpu_ns(t) or 0 for t in others) / 1e9
    b = spans.snapshot()["cpu"]
    # native also keeps what exited threads burnt unseen: at least the live ones' CPU
    assert b["native"] >= a["native"] >= 0
    assert b["native"] >= burnt - 0.05


def test_a_thread_of_no_python_name_is_native(monkeypatch):
    """XLA's and PJRT's pools: a tid that threading.enumerate() does not know."""
    monkeypatch.setattr(spans.threading, "enumerate", lambda: [])
    a = spans.snapshot()["cpu"].get("native", 0)
    t = time.thread_time()
    while time.thread_time() - t < 0.05:
        pass
    assert spans.snapshot()["cpu"]["native"] - a > 0.03


def test_loops_are_the_handlers_counters_before_the_merge():
    def handler(requests):
        for _ in range(requests):
            since = spans.now() - 2_000_000
            spans.wait(spans.AIO_QUEUE_WAIT, since, now_ns=since + 2_000_000)
            spans.begin_request(False)
            with spans.span(spans.S3_REQUEST):
                pass
            spans.end_request("Loops")

    threads = [threading.Thread(target=handler, args=(n,), name=name)
               for n, name in ((3, "aio71-worker-0"), (2, "aio71-worker-3"), (4, "aio93-worker-1"))]
    for t in threads:
        t.start()
    threads[0].join(5)
    rows = {r["loop"]: r for r in spans.snapshot()["loops"]}  # live and exited alike
    for t in threads[1:]:
        t.join(5)
    rows = {r["loop"]: r for r in spans.snapshot()["loops"]}
    assert rows[71] == {"loop": 71, "requests": 5, "queue_wait_seconds": 0.01}
    assert rows[93] == {"loop": 93, "requests": 4, "queue_wait_seconds": 0.008}
    assert spans._loop_of("aio2-worker-3") == 2 and spans._loop_of("aio-loop-0") is None
    # by role they still merge as they did
    assert sum(r["count"] for r in spans.snapshot()["spans"]
               if r["name"] == "s3_request" and r["role"] == "handler") >= 9


def test_spans_and_probe_keep_their_shape_beside_the_new_tables():
    with spans.span(spans.XL_READ_ALL):
        pass
    snap = spans.snapshot()
    assert list(snap) == ["spans", "probe", "requests", "fanout", "cpu", "loops"]
    assert all(list(r) == ["role", "name", "count", "wall_seconds", "cpu_seconds"]
               for r in snap["spans"])
    assert list(snap["probe"]) == ["samples", "late_seconds", "late_max_seconds",
                                   "interval_seconds", "loops"]
    cell = spans.PROBE.add_loop(977)
    try:
        probe = spans.snapshot()
        row = next(r for r in probe["probe"]["loops"] if r["loop"] == 977)
        assert list(row) == ["samples", "late_seconds", "late_max_seconds", "loop"]
        # a loop that no connection landed on is a row of kernel-stats.loops too
        assert {"loop": 977, "requests": 0, "queue_wait_seconds": 0.0} in probe["loops"]
    finally:
        spans.PROBE.loops = [c for c in spans.PROBE.loops if c is not cell]
    ks = KERNEL_STATS.snapshot()
    assert {"requests", "fanout", "cpu", "loops", "spans", "probe"} <= set(ks)


def test_served_requests_are_accounted_by_verb_phase_and_loop(server):
    c = S3Client(server.endpoint)
    c.make_bucket("account")
    data = os.urandom(3 * 4096 + 5)
    have = {r["verb"]: r["count"] for r in spans.snapshot()["requests"]}
    fans = {p: r["count"] for p, r in spans.snapshot()["fanout"].items()}
    served = sum(r["requests"] for r in spans.snapshot()["loops"])
    assert c.put_object("account", "k", data).status == 200
    assert c.get_object("account", "k").body == data
    assert c.head_object("account", "k").status == 200
    assert c.request("DELETE", "/account/k").status == 204
    assert c.list_objects("account").status == 200
    verbs = ("PutObject", "GetObject", "HeadObject", "DeleteObject", "ListBucket")
    deadline = time.monotonic() + 5
    while True:
        # a request is folded once its root has closed, a moment after its answer left
        ks = json.loads(c.request("GET", "/minio-tpu/admin/v1/kernel-stats").body)
        rows = {r["verb"]: r for r in ks["requests"]}
        if all(v in rows and rows[v]["count"] > have.get(v, 0) for v in verbs):
            break
        assert time.monotonic() < deadline, sorted(rows)
        time.sleep(0.05)
    for verb in verbs:
        r = rows[verb]
        assert r["count"] == have.get(verb, 0) + 1, verb
        assert abs(sum(v[1] for v in r["self"].values()) - r["wall_seconds"]) < 1e-9 * len(r["self"])
        assert 0 <= r["cpu_seconds"] <= r["wall_seconds"] and r["queue_wait_seconds"] >= 0
        assert "s3_request" in r["self"] and "sigv4_verify" in r["self"]
    assert {"put_close_wait", "put_rename_wait", "stream_disk", "ol_put_object"} <= set(
        rows["PutObject"]["self"])
    assert "ol_get_object_info" in rows["HeadObject"]["self"]
    for p in ("put_flush", "put_close", "put_rename", "get_reads"):
        assert ks["fanout"][p]["count"] > fans.get(p, 0), p
        assert ks["fanout"][p]["wall_seconds"] >= 0
    assert sum(r["requests"] for r in ks["loops"]) >= served + 5
    assert ks["cpu"]["process_seconds"] > 0 and ks["cpu"]["handler"] > 0


# -- the table ---------------------------------------------------------------------


def package_sources() -> str:
    out = []
    for d, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py") and os.path.join(d, f) != os.path.join(PKG, "utils", "spans.py"):
                with open(os.path.join(d, f), encoding="utf-8") as fh:
                    out.append(fh.read())
    return "\n".join(out)


SOURCES = package_sources()


def test_the_table_and_the_constants_are_the_same_set():
    assert sorted(constants().values()) == sorted(TABLE)
    assert len(set(TABLE)) == len(TABLE)


@pytest.mark.parametrize("name", TABLE)
def test_every_name_is_a_constant_that_some_boundary_uses(name):
    const = next(k for k, v in constants().items() if v == name)
    # a span that nothing opens is dead code
    assert re.search(rf"\bspans\.{const}\b", SOURCES), const


def test_no_knob_was_added_and_one_went():
    from minio_tpu.config import knobs

    with open(knobs.__file__, encoding="utf-8") as f:
        text = f.read()
    assert "MINIO_TPU_NO_INSTRUMENT" not in text
    assert "MINIO_TPU_" not in open(spans.__file__, encoding="utf-8").read()


def test_a_long_lived_request_stops_recording_at_the_cap(monkeypatch):
    monkeypatch.setattr(spans, "MAX_RECORDS", 5)
    spans.begin_request(True)
    before = counters("resp_write_wait")[0]
    with spans.span(spans.S3_REQUEST):
        for _ in range(20):
            with spans.span(spans.RESP_WRITE_WAIT):
                pass
    assert len(spans.end_request()) == 5
    assert counters("resp_write_wait")[0] == before + 20  # the counters go on
