"""Prometheus exposition-format validation + hot-path telemetry
(cmd/metrics.go distributions, cmd/xl-storage-disk-id-check.go per-disk
API metrics, codec kernel telemetry).

Contains a mini text-format (0.0.4) parser that validates structural
invariants of EVERY emitted family - HELP/TYPE before samples, label
escaping, histogram bucket monotonicity, +Inf == _count, _sum
consistency - and runs it against live server output.
"""

import json
import time

import numpy as np
import pytest

from minio_tpu.codec.telemetry import KERNEL_STATS, KernelStats, instrument
from minio_tpu.iam import IAMSys
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.server.http import S3Server
from minio_tpu.server.metrics import Histogram, Metrics
from minio_tpu.storage import metered
from minio_tpu.storage.xl import XLStorage

from s3client import S3Client

ADMIN = "/minio-tpu/admin/v1"
METRICS_PATH = "/minio-tpu/prometheus/metrics"

# -- mini exposition parser ----------------------------------------------

_UNESCAPE = {"\\": "\\", '"': '"', "n": "\n"}


def _parse_sample(line):
    """One sample line -> (name, labels dict, float value); understands
    the spec's label escapes (backslash, quote, newline)."""
    if "{" not in line:
        name, _, val = line.partition(" ")
        return name, {}, float(val)
    name, _, rest = line.partition("{")
    labels = {}
    i = 0
    while True:
        j = rest.index("=", i)
        key = rest[i:j]
        assert rest[j + 1] == '"', f"unquoted label value in {line!r}"
        k = j + 2
        buf = []
        while True:
            ch = rest[k]
            if ch == "\\":
                buf.append(_UNESCAPE[rest[k + 1]])
                k += 2
            elif ch == '"':
                k += 1
                break
            else:
                buf.append(ch)
                k += 1
        labels[key] = "".join(buf)
        if rest[k] == ",":
            i = k + 1
        else:
            assert rest[k] == "}", f"garbage after labels in {line!r}"
            return name, labels, float(rest[k + 1 :].strip())


_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def parse_exposition(text):
    """Parse + structurally validate a text-format document.

    Returns {family: {"type", "help", "samples": [(name, labels, value)]}}.
    Raises AssertionError on any spec violation.
    """
    families = {}
    for line in text.splitlines():
        if line.startswith("# HELP "):
            name, _, help_ = line[len("# HELP ") :].partition(" ")
            assert name not in families, f"duplicate HELP for {name}"
            families[name] = {"help": help_, "type": None, "samples": []}
        elif line.startswith("# TYPE "):
            name, _, mtype = line[len("# TYPE ") :].partition(" ")
            assert name in families and families[name]["help"], (
                f"TYPE before HELP for {name}"
            )
            assert families[name]["type"] is None, f"duplicate TYPE {name}"
            assert mtype in ("counter", "gauge", "histogram"), mtype
            families[name]["type"] = mtype
        elif line.startswith("#") or not line.strip():
            continue
        else:
            name, labels, value = _parse_sample(line)
            fam = families.get(name)
            if fam is None:
                # histogram series sample: resolve to the base family
                for suffix in _HIST_SUFFIXES:
                    if name.endswith(suffix):
                        base = families.get(name[: -len(suffix)])
                        if base is not None and base["type"] == "histogram":
                            fam = base
                            break
            assert fam is not None, f"sample before HELP/TYPE: {line!r}"
            assert fam["type"] is not None, f"sample before TYPE: {line!r}"
            assert value >= 0 or fam["type"] == "gauge", line
            fam["samples"].append((name, labels, value))
    _validate_histograms(families)
    return families


def _validate_histograms(families):
    for name, fam in families.items():
        if fam["type"] != "histogram":
            continue
        series = {}  # labelset minus le -> {"buckets": [(le, v)], ...}
        for sname, labels, value in fam["samples"]:
            key = tuple(
                sorted((k, v) for k, v in labels.items() if k != "le")
            )
            s = series.setdefault(key, {"buckets": []})
            if sname == f"{name}_bucket":
                le = labels["le"]
                s["buckets"].append(
                    (float("inf") if le == "+Inf" else float(le), value)
                )
            elif sname == f"{name}_sum":
                s["sum"] = value
            elif sname == f"{name}_count":
                s["count"] = value
            else:
                raise AssertionError(f"stray histogram sample {sname}")
        # a histogram family with no observations yet legally exposes
        # just its HELP/TYPE header - nothing to validate
        for key, s in series.items():
            assert "sum" in s and "count" in s, (name, key, s)
            buckets = sorted(s["buckets"])
            assert buckets and buckets[-1][0] == float("inf"), (
                f"{name}{dict(key)} missing +Inf bucket"
            )
            counts = [v for _, v in buckets]
            assert counts == sorted(counts), (
                f"{name}{dict(key)} buckets not monotone: {counts}"
            )
            assert counts[-1] == s["count"], (
                f"{name}{dict(key)} +Inf {counts[-1]} != _count {s['count']}"
            )
            if s["count"]:
                # mean must sit within the observable value range
                mean = s["sum"] / s["count"]
                assert mean >= 0, (name, key, s)


def get_family(families, name):
    assert name in families, f"family {name} missing"
    return families[name]


# -- unit: primitives ----------------------------------------------------


def test_histogram_primitive():
    h = Histogram((0.1, 1.0, 5.0))
    for v in (0.05, 0.1, 0.7, 1.0, 3.0, 99.0):
        h.observe("api", v)
    h.observe("other", 0.2)
    rows = {key: (cum, total, count) for key, cum, total, count in h.collect()}
    cum, total, count = rows["api"]
    # cumulative includes the +Inf slot; le=.1 catches 0.05+0.1
    assert cum == [2, 4, 5, 6] and count == 6
    assert abs(total - (0.05 + 0.1 + 0.7 + 1.0 + 3.0 + 99.0)) < 1e-9
    assert rows["other"][2] == 1
    # negative observations clamp to zero instead of corrupting buckets
    h.observe("api", -1.0)
    assert {k: c for k, c, _t, _n in h.collect()}["api"][0] == 3


def test_label_escaping_roundtrip():
    m = Metrics()
    nasty = 'disk\\with"quotes\nand newline'
    m.observe(nasty, 200, 0.01)
    families = parse_exposition(m.render().decode())
    fam = get_family(families, "miniotpu_s3_requests_total")
    labels = [lab for _n, lab, _v in fam["samples"]]
    assert {"api": nasty, "code": "200"} in labels


def test_kernel_stats_registry():
    ks = KernelStats()
    ks.record_op("encode", "tpu", 1024, 0.5)
    ks.record_op("encode", "tpu", 1024, 0.25)
    ks.record_op("digest", "cpu", 10, 0.1)
    ks.record_batch_flush(3, 12, 0.006)
    ks.record_stream("encode", 4096)
    ks.record_heal_required()
    snap = ks.snapshot()
    enc = next(o for o in snap["ops"] if o["op"] == "encode")
    assert enc["backend"] == "tpu" and enc["calls"] == 2
    assert enc["bytes"] == 2048 and abs(enc["seconds"] - 0.75) < 1e-9
    assert snap["batch"] == {
        "flushes": 1, "jobs": 3, "blocks": 12, "wait_seconds": 0.006,
    }
    assert snap["streams"] == [
        {"kind": "encode", "streams": 1, "bytes": 4096}
    ]
    assert snap["heal_required"] == 1
    ks.reset()
    snap = ks.snapshot()
    assert snap["ops"] == [] and snap["batch"]["flushes"] == 0


def test_instrument_preserves_name_and_is_idempotent():
    """The batcher pads merged batches only for name == "tpu"; the
    telemetry wrapper must not mask the concrete backend's name."""
    from minio_tpu.codec.backend import CpuBackend

    wrapped = instrument(CpuBackend())
    assert wrapped.name == "cpu"
    assert instrument(wrapped) is wrapped


def test_metered_disk_ledger(tmp_path):
    d = metered.wrap(XLStorage(str(tmp_path / "md")))
    assert metered.is_metered(d)
    assert metered.wrap(d) is d  # idempotent
    assert metered.wrap(None) is None
    d.make_vol("vol")
    d.write_all("vol", "f", b"payload")
    assert d.read_all("vol", "f") == b"payload"
    with pytest.raises(Exception):
        d.read_all("vol", "nope")
    stats = d.api_stats()
    assert stats["write_all"]["calls"] == 1
    assert stats["write_all"]["errors"] == 0
    assert stats["write_all"]["seconds"] > 0
    assert stats["read_all"]["calls"] == 2
    assert stats["read_all"]["errors"] == 1
    assert stats["read_all"]["seconds"] > 0
    # streaming quantiles ride along (successful calls only)
    assert stats["read_all"]["p50_seconds"] > 0
    assert stats["read_all"]["p99_seconds"] >= stats["read_all"]["p50_seconds"]
    assert d.api_p99("read_all") == pytest.approx(
        stats["read_all"]["p99_seconds"], abs=1e-6
    )
    # unmetered passthrough still works (root, endpoint, is_online)
    assert d.root == str(tmp_path / "md")
    assert d.is_online()


def test_metered_stacks_inside_diskcheck(tmp_path):
    """Production stacking DiskIDCheck(MeteredDisk(xl)): api_stats is
    reachable through the outer wrapper and `unwrapped` still leads to
    a layer that passes raw format probes through (heal contract)."""
    from minio_tpu.storage.diskcheck import DiskIDCheck

    xl = XLStorage(str(tmp_path / "sd"))
    chain = DiskIDCheck(metered.wrap(xl), "some-disk-id")
    assert metered.is_metered(chain)
    assert metered.wrap(chain) is chain  # no double-wrap
    assert callable(getattr(chain, "api_stats", None))
    inner = chain.unwrapped
    # the heal monitor's single unwrap hop reaches a disk whose
    # read_all works without identity checks (unformatted drives)
    inner.make_vol("v")
    inner.write_all("v", "probe", b"x")
    assert inner.read_all("v", "probe") == b"x"


# -- live server ---------------------------------------------------------


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    root = tmp_path_factory.mktemp("metrdisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=4096)
    iam = IAMSys("minioadmin", "minioadmin", ol)
    srv = S3Server(ol, address="127.0.0.1:0", iam=iam).start()
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def client(server):
    c = S3Client(server.endpoint)
    c.make_bucket("metrbkt")
    c.put_object("metrbkt", "obj1", b"x" * 32768)
    r = c.get_object("metrbkt", "obj1")
    assert r.status == 200 and len(r.body) == 32768
    time.sleep(0.3)  # observation lands just after the response bytes
    return c


def _scrape(c):
    r = c.request("GET", METRICS_PATH)
    assert r.status == 200, r.body
    return r.body.decode()


def test_live_document_parses_and_validates(server, client):
    families = parse_exposition(_scrape(client))
    # every family present in the document passed structural checks;
    # spot-check the core legacy ones survived the render rewrite
    for name in (
        "miniotpu_s3_requests_total",
        "miniotpu_s3_request_seconds_total",
        "miniotpu_disk_storage_used_bytes",
        "miniotpu_disks_total",
        "miniotpu_process_uptime_seconds",
        "miniotpu_audit_entries_dropped_total",
    ):
        get_family(families, name)


def test_live_request_histograms(server, client):
    families = parse_exposition(_scrape(client))
    for fam_name in (
        "miniotpu_s3_request_duration_seconds",
        "miniotpu_s3_ttfb_seconds",
    ):
        fam = get_family(families, fam_name)
        assert fam["type"] == "histogram"
        apis = {
            lab["api"]
            for n, lab, _v in fam["samples"]
            if n == f"{fam_name}_count"
        }
        assert {"PutObject", "GetObject"} <= apis, apis
        # ttfb <= duration for every api seen by both
        counts = {
            lab["api"]: v
            for n, lab, v in fam["samples"]
            if n == f"{fam_name}_count"
        }
        assert counts["GetObject"] >= 1


def test_live_codec_families(server, client):
    families = parse_exposition(_scrape(client))
    ops = get_family(families, "miniotpu_codec_ops_total")
    backends = {lab["backend"] for _n, lab, _v in ops["samples"]}
    assert backends and backends <= {"tpu", "cpu"}, backends
    opnames = {lab["op"] for _n, lab, _v in ops["samples"]}
    # digest-only parity-plane PUTs register as encode_digest
    assert opnames & {"encode", "encode_digest"}, opnames
    assert "digest" in opnames, opnames
    by_op = {
        (lab["op"], lab["backend"]): v
        for _n, lab, v in get_family(
            families, "miniotpu_codec_bytes_total"
        )["samples"]
    }
    assert any(
        v > 0
        for (op, _be), v in by_op.items()
        if op in ("encode", "encode_digest")
    )
    secs = get_family(families, "miniotpu_codec_seconds_total")
    assert any(v > 0 for _n, _lab, v in secs["samples"])
    streams = get_family(families, "miniotpu_codec_streams_total")
    kinds = {lab["op"] for _n, lab, _v in streams["samples"]}
    assert {"encode", "decode"} <= kinds, kinds


def test_live_disk_api_families(server, client):
    families = parse_exposition(_scrape(client))
    calls = get_family(families, "miniotpu_disk_api_calls_total")
    disks = {lab["disk"] for _n, lab, _v in calls["samples"]}
    assert len(disks) == 4, disks  # every disk in the set reports
    apis = {lab["api"] for _n, lab, _v in calls["samples"]}
    # the PUT path touches metadata + shard writes on each disk
    assert "rename_data" in apis or "create_file" in apis, apis
    secs = get_family(families, "miniotpu_disk_api_seconds_total")
    assert any(v > 0 for _n, _lab, v in secs["samples"])
    get_family(families, "miniotpu_disk_api_errors_total")


def test_codec_roundtrip_records_nonzero(server, client):
    """Acceptance: a PutObject+GetObject round-trip through the erasure
    layer leaves non-zero bytes and seconds in the kernel registry."""
    KERNEL_STATS.reset()
    client.put_object("metrbkt", "rt-obj", b"r" * 65536)
    r = client.get_object("metrbkt", "rt-obj")
    assert r.status == 200 and len(r.body) == 65536
    # the decode stream is recorded just after the last body byte hits
    # the (unbuffered) socket - give the handler thread a beat
    for _ in range(50):
        snap = KERNEL_STATS.snapshot()
        if any(s["kind"] == "decode" for s in snap["streams"]):
            break
        time.sleep(0.02)
    # digest-only parity plane PUTs record encode_digest; legacy eager
    # encodes record encode - the round-trip must land one of them
    enc = [
        o for o in snap["ops"] if o["op"] in ("encode", "encode_digest")
    ]
    dig = [o for o in snap["ops"] if o["op"] == "digest"]
    assert enc and all(o["bytes"] > 0 and o["seconds"] > 0 for o in enc)
    assert dig and all(o["bytes"] > 0 and o["seconds"] > 0 for o in dig)
    by_kind = {s["kind"]: s for s in snap["streams"]}
    assert by_kind["encode"]["bytes"] >= 65536
    assert by_kind["decode"]["bytes"] >= 65536


def test_admin_kernel_stats_route(server, client):
    r = client.request("GET", f"{ADMIN}/kernel-stats")
    assert r.status == 200, r.body
    doc = json.loads(r.body)
    assert {"ops", "batch", "streams", "heal_required"} <= set(doc)
    assert any(
        o["op"] in ("encode", "encode_digest") for o in doc["ops"]
    )
    # the parity-plane counters ride the same snapshot
    assert "d2h" in doc and "parity_cache" in doc
    # so do the Pallas/portable split and the device the passes ran on
    assert {"device_passes", "pallas_passes", "portable_passes"} <= set(doc)
    assert doc["device"]["platform"] == "cpu"
    assert doc["device"]["backend"] == "tpu"


@pytest.mark.parametrize("size", [1, 4096, 3 * 4096 + 5])
def test_admin_kernel_stats_body_read_adds_up_over_a_put(server, client, size):
    """``body_read`` (server/aio.py::BODY_READ, beside ``meta_read``): a PUT's
    body crosses from the loop to its handler once a 4096-byte block of this
    server, every byte of it, and each crossing is one ``body_read_wait``;
    ``direct`` is the part of those bytes that the socket read straight into
    the handler's buffer."""

    def counts():
        r = client.request("GET", f"{ADMIN}/kernel-stats")
        assert r.status == 200, r.body
        doc = json.loads(r.body)
        assert set(doc["body_read"]) == {
            "handovers", "bytes", "loop_reads", "direct"
        }
        waits = sum(
            s["count"] for s in doc["spans"] if s["name"] == "body_read_wait"
        )
        return {**doc["body_read"], "waits": waits}

    before = counts()
    assert client.put_object("metrbkt", "body", b"b" * size).status == 200
    after = counts()
    moved = {k: after[k] - before[k] for k in after}
    blocks = -(-size // 4096)
    assert moved["handovers"] == moved["waits"] == blocks
    assert moved["bytes"] == size
    assert blocks <= moved["loop_reads"] <= size
    assert 0 <= moved["direct"] <= moved["bytes"]


# what a client does to a key that holds an object -> kernel-stats.remove
# moves by [named, walked, calls] over this server's 4 drives
REMOVALS_SERVED = {
    # 4 staging dirs, one rmdir each; 4 replaced data dirs: a part, the dir
    "overwrite-put": ("PUT", [8, 0, 12]),
    # the journal, the part, the data dir, the object's directory: 4 a drive
    "delete": ("DELETE", [4, 0, 16]),
    "get": ("GET", [0, 0, 0]),
}


@pytest.mark.parametrize("case", REMOVALS_SERVED)
def test_admin_kernel_stats_remove_counts_a_served_removal(server, client, case):
    """``remove`` (storage/xl.py::REMOVE, beside ``meta_read``): the drives'
    removals that were told the names of what they remove, and the system
    calls they made; ``walked`` stays 0 where every drive finds what the
    quorum's FileInfo names."""
    method, want = REMOVALS_SERVED[case]

    def counts():
        r = client.request("GET", f"{ADMIN}/kernel-stats")
        assert r.status == 200, r.body
        doc = json.loads(r.body)
        assert set(doc["remove"]) == {"named", "walked", "calls"}
        spans = sum(
            s["count"] for s in doc["spans"] if s["name"] == "xl_delete_file"
        )
        return {**doc["remove"], "spans": spans}

    key = f"removed-{case}"
    assert client.put_object("metrbkt", key, b"r" * 5000).status == 200
    before = counts()
    if method == "PUT":
        assert client.put_object("metrbkt", key, b"s" * 5000).status == 200
    elif method == "DELETE":
        assert client.request("DELETE", f"/metrbkt/{key}").status == 204
    else:
        assert client.get_object("metrbkt", key).body == b"r" * 5000
    after = counts()
    moved = {k: after[k] - before[k] for k in after}
    assert [moved["named"], moved["walked"], moved["calls"]] == want
    # the span stays round a drive's removal, whichever way it goes
    assert moved["spans"] == (0 if method == "GET" else 4)
    if method == "DELETE":
        assert client.get_object("metrbkt", key).status == 404


@pytest.fixture(scope="module")
def guarded_server(tmp_path_factory):
    """A server over the stack ``server/__main__`` builds: every drive a
    DiskIDCheck(MeteredDisk(XLStorage)), which is where liveness is kept."""
    from minio_tpu.server.__main__ import build_object_layer

    root = tmp_path_factory.mktemp("guarded")
    ol = build_object_layer([str(root / "d{1...4}")])
    iam = IAMSys("minioadmin", "minioadmin", ol)
    srv = S3Server(ol, address="127.0.0.1:0", iam=iam).start()
    c = S3Client(srv.endpoint)
    c.make_bucket("livebkt")
    yield srv, c
    srv.shutdown()


# request -> snapshots of the live drives it takes (x 4 drives = asked)
LIVENESS_SERVED = {"STAT": 2, "GET": 3, "PUT": 3, "DELETE": 3}


@pytest.mark.parametrize("verb", LIVENESS_SERVED)
def test_admin_kernel_stats_liveness_counts_the_questions(guarded_server, verb):
    """``liveness`` (storage/diskcheck.py::LIVENESS, beside ``meta_read`` and
    ``remove``): a request asks every drive of the set two or three times
    whether it is there and looks at most once a drive a second."""
    _, c = guarded_server

    def counts():
        r = c.request("GET", f"{ADMIN}/kernel-stats")
        assert r.status == 200, r.body
        doc = json.loads(r.body)
        assert set(doc["liveness"]) == {"asked", "looked", "reset"}
        return doc["liveness"]

    key = f"live-{verb}"
    assert c.put_object("livebkt", key, b"l" * 5000).status == 200
    before, t0 = counts(), time.monotonic()
    if verb == "STAT":
        assert c.request("HEAD", f"/livebkt/{key}").status == 200
    elif verb == "GET":
        assert c.get_object("livebkt", key).body == b"l" * 5000
    elif verb == "PUT":
        assert c.put_object("livebkt", key, b"m" * 5000).status == 200
    else:
        assert c.request("DELETE", f"/livebkt/{key}").status == 204
    after, took = counts(), time.monotonic() - t0
    moved = {k: after[k] - before[k] for k in after}
    # what else asks in between: the admin call itself asks no drive
    assert moved["asked"] == 4 * LIVENESS_SERVED[verb]
    assert moved["looked"] <= 4 * int(took + 1)
    assert moved["reset"] == 0


def test_admin_healthinfo_includes_api_stats(server, client):
    r = client.request("GET", f"{ADMIN}/healthinfo")
    assert r.status == 200, r.body
    drives = json.loads(r.body)["nodes"][0]["drives"]
    assert len(drives) == 4
    for d in drives:
        assert d["state"] == "ok"
        stats = d["api_stats"]
        # the probe itself guarantees write_all/read_all entries
        assert stats["write_all"]["calls"] >= 1
        assert stats["read_all"]["calls"] >= 1


def test_batcher_occupancy_counters():
    """Jobs routed through the BatchingBackend land in the flush
    telemetry: flushes, job count, and queue wait accumulate."""
    from minio_tpu.codec.backend import CpuBackend
    from minio_tpu.codec.batcher import BatchingBackend

    ks_before = KERNEL_STATS.snapshot()["batch"]
    be = BatchingBackend(instrument(CpuBackend()), deadline_s=0.001)
    try:
        shards = np.zeros((2, 4, 64), dtype=np.uint8)
        be.digest(shards)
        be.digest(shards)
    finally:
        be.shutdown()
    after = KERNEL_STATS.snapshot()["batch"]
    assert after["flushes"] >= ks_before["flushes"] + 1
    assert after["jobs"] >= ks_before["jobs"] + 2
    assert after["blocks"] >= ks_before["blocks"] + 4
    assert after["wait_seconds"] >= ks_before["wait_seconds"]


def test_server_plane_render_unit():
    """render(plane=...) emits the three request-plane families with
    zero-filled shed reasons, straight from a stats snapshot."""
    from minio_tpu.server.admission import SHED_REASONS, PlaneStats

    stats = PlaneStats()
    stats.register_stage("parse", lambda: 3)
    stats.register_stage("handler", lambda: 1)
    stats.enter()
    stats.shed_inc("queue")
    stats.shed_inc("queue")
    m = Metrics()
    families = parse_exposition(
        m.render(plane=stats.snapshot()).decode()
    )
    fam = get_family(families, "miniotpu_server_inflight_requests")
    assert fam["type"] == "gauge"
    assert fam["samples"][0][2] == 1.0
    fam = get_family(families, "miniotpu_server_stage_queue_depth")
    depths = {lab["stage"]: v for _n, lab, v in fam["samples"]}
    assert depths == {"parse": 3.0, "handler": 1.0}
    fam = get_family(families, "miniotpu_server_shed_total")
    assert fam["type"] == "counter"
    sheds = {lab["reason"]: v for _n, lab, v in fam["samples"]}
    assert set(sheds) == set(SHED_REASONS)  # zero-filled
    assert sheds["queue"] == 2.0
    assert sheds["quota"] == 0.0 and sheds["tenant"] == 0.0


def test_read_cache_families_zero_filled_when_off():
    """With the tiered read cache off, render() still carries every
    miniotpu_cache_* family with one zero sample per tier."""
    from minio_tpu import cache as rcache

    rcache.reset_read_cache()
    families = parse_exposition(Metrics().render().decode())
    for fam_name, mtype in (
        ("miniotpu_cache_hits_total", "counter"),
        ("miniotpu_cache_misses_total", "counter"),
        ("miniotpu_cache_evictions_total", "counter"),
        ("miniotpu_cache_rejects_total", "counter"),
        ("miniotpu_cache_entries", "gauge"),
        ("miniotpu_cache_occupancy_bytes", "gauge"),
        ("miniotpu_cache_budget_bytes", "gauge"),
    ):
        fam = get_family(families, fam_name)
        assert fam["type"] == mtype
        cells = {lab["tier"]: v for _n, lab, v in fam["samples"]}
        assert cells == {"device": 0.0, "host": 0.0}, fam_name
    for fam_name in (
        "miniotpu_cache_demotions_total",
        "miniotpu_cache_invalidations_total",
    ):
        fam = get_family(families, fam_name)
        assert fam["samples"][0][2] == 0.0
    fam = get_family(families, "miniotpu_cache_admission_events_total")
    kinds = {lab["kind"]: v for _n, lab, v in fam["samples"]}
    assert set(kinds) == {"recorded", "seeded", "admitted", "rejected"}
    assert all(v == 0.0 for v in kinds.values())


def test_select_families_zero_filled():
    """miniotpu_select_* render with a stable, zero-filled label set
    (every engine and fallback reason) before any scan has run."""
    from minio_tpu.s3select.device import STATS, SelectStats

    saved = STATS.snapshot()
    STATS.reset()
    try:
        families = parse_exposition(Metrics().render().decode())
        fam = get_family(families, "miniotpu_select_requests_total")
        assert fam["type"] == "counter"
        engines = {lab["engine"]: v for _n, lab, v in fam["samples"]}
        assert set(engines) == set(SelectStats.ENGINES)
        assert all(v == 0.0 for v in engines.values())
        fam = get_family(families, "miniotpu_select_fallback_total")
        reasons = {lab["reason"]: v for _n, lab, v in fam["samples"]}
        assert set(reasons) == set(SelectStats.REASONS)
        assert all(v == 0.0 for v in reasons.values())
        for name in (
            "miniotpu_select_scanned_bytes_total",
            "miniotpu_select_returned_bytes_total",
            "miniotpu_select_device_seconds_total",
        ):
            fam = get_family(families, name)
            assert fam["type"] == "counter"
            assert fam["samples"][0][2] == 0.0, name
    finally:
        # restore cross-test counters (STATS is a process singleton)
        STATS.reset()
        for e, n in saved["requests"].items():
            for _ in range(n):
                STATS.request(e)
        for r, n in saved["fallbacks"].items():
            for _ in range(n):
                STATS.fallback(r)
        STATS.io(saved["scanned_bytes"], saved["returned_bytes"])
        STATS.device_time(saved["device_seconds"])


def test_h2d_families_zero_filled():
    """The host->device staging families render with a stable,
    zero-filled label set (both planes) before any codec traffic."""
    KERNEL_STATS.reset()
    families = parse_exposition(Metrics().render().decode())
    for name in (
        "miniotpu_codec_h2d_bytes_total",
        "miniotpu_codec_h2d_transfers_total",
    ):
        fam = get_family(families, name)
        assert fam["type"] == "counter"
        planes = {lab["plane"]: v for _n, lab, v in fam["samples"]}
        assert set(planes) == {"data", "parity"}, name
        assert all(v == 0.0 for v in planes.values()), name


def test_h2d_families_reflect_live_counters():
    KERNEL_STATS.record_h2d("data", 4096)
    KERNEL_STATS.record_h2d("data", 4096)
    families = parse_exposition(Metrics().render().decode())
    fam = get_family(families, "miniotpu_codec_h2d_bytes_total")
    planes = {lab["plane"]: v for _n, lab, v in fam["samples"]}
    assert planes["data"] >= 8192.0
    fam = get_family(families, "miniotpu_codec_h2d_transfers_total")
    planes = {lab["plane"]: v for _n, lab, v in fam["samples"]}
    assert planes["data"] >= 2.0


def test_select_families_reflect_live_counters():
    from minio_tpu.s3select.device import STATS

    STATS.request("device")
    STATS.fallback("hazard")
    STATS.io(1024, 64)
    families = parse_exposition(Metrics().render().decode())
    fam = get_family(families, "miniotpu_select_requests_total")
    engines = {lab["engine"]: v for _n, lab, v in fam["samples"]}
    assert engines["device"] >= 1.0
    fam = get_family(families, "miniotpu_select_fallback_total")
    reasons = {lab["reason"]: v for _n, lab, v in fam["samples"]}
    assert reasons["hazard"] >= 1.0
    fam = get_family(families, "miniotpu_select_scanned_bytes_total")
    assert fam["samples"][0][2] >= 1024.0


def test_read_cache_families_reflect_live_counters(monkeypatch):
    from minio_tpu import cache as rcache

    monkeypatch.setenv("MINIO_TPU_READ_CACHE", "host")
    rcache.reset_read_cache()
    try:
        c = rcache.read_cache()
        assert c is not None
        data = np.zeros((1, 2, 64), dtype=np.uint8)
        digests = np.zeros((1, 2, 8), dtype=np.uint32)
        key = ("b", "o", "dd", 1, 0, 1, 64)

        class _BE:
            @staticmethod
            def verify(d, g):
                return np.ones((d.shape[0], d.shape[1]), dtype=bool)

        c.put(key, "b/o", data, digests, source="put")
        assert c.lookup(_BE, key, "b/o") is not None
        families = parse_exposition(Metrics().render().decode())
        fam = get_family(families, "miniotpu_cache_hits_total")
        cells = {lab["tier"]: v for _n, lab, v in fam["samples"]}
        assert cells["host"] == 1.0
        fam = get_family(families, "miniotpu_cache_occupancy_bytes")
        cells = {lab["tier"]: v for _n, lab, v in fam["samples"]}
        assert cells["host"] == float(data.nbytes + digests.nbytes)
        fam = get_family(
            families, "miniotpu_cache_admission_events_total"
        )
        kinds = {lab["kind"]: v for _n, lab, v in fam["samples"]}
        assert kinds["recorded"] >= 2.0
    finally:
        rcache.reset_read_cache()


def test_live_server_plane_families(server, client):
    """The live scrape carries the request-plane families: inflight
    counts this very scrape, and all pipeline stages report a depth."""
    families = parse_exposition(_scrape(client))
    fam = get_family(families, "miniotpu_server_inflight_requests")
    # the scrape route renders before the inflight accounting point,
    # so it does not count itself
    assert fam["samples"][0][2] >= 0.0
    fam = get_family(families, "miniotpu_server_stage_queue_depth")
    stages = {lab["stage"] for _n, lab, _v in fam["samples"]}
    assert {"parse", "handler", "codec"} <= stages, stages
    from minio_tpu.server.admission import SHED_REASONS

    fam = get_family(families, "miniotpu_server_shed_total")
    reasons = {lab["reason"] for _n, lab, _v in fam["samples"]}
    assert reasons == set(SHED_REASONS)


def test_server_loop_families_render_unit():
    """A multi-loop plane snapshot fans out into the four per-loop
    families, one series per loop (x reason for sheds), zero-filled
    from the loop list - a scrape's shape never depends on which loop
    saw traffic.  Single-loop-free snapshots omit the families."""
    from minio_tpu.server.admission import SHED_REASONS, PlaneStats

    stats = PlaneStats()
    cells = [stats.add_loop() for _ in range(2)]
    cells[0].register_stage("parse", lambda: 5)   # open connections
    cells[0].register_stage("handler", lambda: 2)
    cells[1].register_stage("parse", lambda: 0)
    cells[1].register_stage("handler", lambda: 0)
    cells[0].enter()
    cells[0].shed_inc("tenant")
    doc = Metrics().render(plane=stats.snapshot()).decode()
    families = parse_exposition(doc)

    fam = get_family(families, "miniotpu_server_loop_connections")
    assert fam["type"] == "gauge"
    conns = {lab["loop"]: v for _n, lab, v in fam["samples"]}
    assert conns == {"0": 5.0, "1": 0.0}
    fam = get_family(families, "miniotpu_server_loop_inflight_requests")
    infl = {lab["loop"]: v for _n, lab, v in fam["samples"]}
    assert infl == {"0": 1.0, "1": 0.0}
    fam = get_family(
        families, "miniotpu_server_loop_handler_queue_depth"
    )
    depths = {lab["loop"]: v for _n, lab, v in fam["samples"]}
    assert depths == {"0": 2.0, "1": 0.0}
    fam = get_family(families, "miniotpu_server_loop_shed_total")
    assert fam["type"] == "counter"
    sheds = {
        (lab["loop"], lab["reason"]): v for _n, lab, v in fam["samples"]
    }
    assert set(sheds) == {
        (lp, r) for lp in ("0", "1") for r in SHED_REASONS
    }  # zero-filled per loop x reason
    assert sheds[("0", "tenant")] == 1.0
    assert sum(sheds.values()) == 1.0

    # the aggregate view still sums the cells (oracle compatibility)
    fam = get_family(families, "miniotpu_server_inflight_requests")
    assert fam["samples"][0][2] == 1.0

    # a plane with no loop cells does not emit the per-loop families
    flat = parse_exposition(
        Metrics().render(plane=PlaneStats().snapshot()).decode()
    )
    assert "miniotpu_server_loop_connections" not in flat


def test_live_server_loop_families():
    """A live async multi-loop server's scrape carries all four
    per-loop families with a series for every configured loop."""
    import os
    import tempfile

    from minio_tpu.server.admission import SHED_REASONS

    env = {"MINIO_TPU_SERVER": "async", "MINIO_TPU_SERVER_LOOPS": "2"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    srv = None
    try:
        with tempfile.TemporaryDirectory() as root:
            disks = [
                XLStorage(os.path.join(root, f"d{i}")) for i in range(4)
            ]
            ol = ErasureObjects(disks, block_size=4096)
            srv = S3Server(ol, address="127.0.0.1:0").start()
            c = S3Client(srv.endpoint)
            assert c.make_bucket("loopm").status == 200
            assert c.put_object("loopm", "o", b"y" * 4096).status == 200
            families = parse_exposition(_scrape(c))
            for name in (
                "miniotpu_server_loop_connections",
                "miniotpu_server_loop_inflight_requests",
                "miniotpu_server_loop_handler_queue_depth",
            ):
                fam = get_family(families, name)
                loops = {lab["loop"] for _n, lab, _v in fam["samples"]}
                assert loops == {"0", "1"}, (name, loops)
            fam = get_family(families, "miniotpu_server_loop_shed_total")
            cells = {
                (lab["loop"], lab["reason"])
                for _n, lab, _v in fam["samples"]
            }
            assert cells == {
                (lp, r) for lp in ("0", "1") for r in SHED_REASONS
            }
            srv.shutdown()
            srv = None
    finally:
        if srv is not None:
            srv.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
