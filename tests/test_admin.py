"""Admin API + Prometheus metrics + structured logging
(cmd/admin-router.go, cmd/metrics.go, cmd/logger).
"""

import json

import pytest

from minio_tpu.iam import IAMSys
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.server.http import S3Server
from minio_tpu.storage.xl import XLStorage

from s3client import S3Client

BLOCK = 4096
ADMIN = "/minio-tpu/admin/v1"


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    root = tmp_path_factory.mktemp("admdisks")
    disks = [XLStorage(str(root / f"d{i}")) for i in range(4)]
    ol = ErasureObjects(disks, block_size=BLOCK)
    iam = IAMSys("minioadmin", "minioadmin", ol)
    srv = S3Server(ol, address="127.0.0.1:0", iam=iam).start()
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def root_client(server):
    c = S3Client(server.endpoint)
    c.make_bucket("admbkt")
    c.put_object("admbkt", "obj1", b"hello metrics")
    return c


def test_admin_info(server, root_client):
    r = root_client.request("GET", f"{ADMIN}/info")
    assert r.status == 200, r.body
    doc = json.loads(r.body)
    assert doc["mode"] == "erasure"
    assert doc["storage"]["disks"] == 4
    assert len(doc["disks"]) == 4
    assert all(d["state"] == "ok" for d in doc["disks"])
    assert doc["uptime_seconds"] >= 0


def test_admin_storageinfo(server, root_client):
    r = root_client.request("GET", f"{ADMIN}/storageinfo")
    assert r.status == 200
    doc = json.loads(r.body)
    assert doc["online"] == 4 and doc["parity"] == 2


def test_admin_requires_owner(server, root_client):
    srv = server
    srv.iam.add_user("peon", "peonsecret123", "readwrite")
    peon = S3Client(srv.endpoint, "peon", "peonsecret123")
    r = peon.request("GET", f"{ADMIN}/info")
    assert r.status == 403
    # anonymous outright rejected
    anon = S3Client(srv.endpoint)
    assert anon.request("GET", f"{ADMIN}/info", sign=False).status == 403


def test_admin_heal_endpoint(server, root_client):
    r = root_client.request(
        "POST", f"{ADMIN}/heal",
        query={"bucket": "admbkt", "object": "obj1", "dryRun": "true"},
    )
    assert r.status == 200, r.body
    doc = json.loads(r.body)
    assert doc["bucket"] == "admbkt" and doc["dry_run"] is True
    # bucket-level heal
    r = root_client.request(
        "POST", f"{ADMIN}/heal", query={"bucket": "admbkt"}
    )
    assert r.status == 200
    # missing bucket arg
    r = root_client.request("POST", f"{ADMIN}/heal")
    assert r.status == 400


def test_admin_iam_management(server, root_client):
    c = root_client
    pol = {
        "Version": "2012-10-17",
        "Statement": [
            {
                "Effect": "Allow",
                "Action": ["s3:GetObject"],
                "Resource": ["arn:aws:s3:::admbkt/*"],
            }
        ],
    }
    r = c.request(
        "PUT", f"{ADMIN}/add-canned-policy", query={"name": "adm-ro"},
        body=json.dumps(pol).encode(),
    )
    assert r.status == 200, r.body
    r = c.request(
        "PUT", f"{ADMIN}/add-user", query={"accessKey": "adminmade"},
        body=json.dumps(
            {"secretKey": "adminmadesecret", "policy": "adm-ro"}
        ).encode(),
    )
    assert r.status == 200, r.body
    # the new user works immediately
    u = S3Client(server.endpoint, "adminmade", "adminmadesecret")
    assert u.get_object("admbkt", "obj1").status == 200
    assert u.put_object("admbkt", "nope", b"x").status == 403
    # listings show them
    r = c.request("GET", f"{ADMIN}/list-users")
    assert "adminmade" in json.loads(r.body)
    r = c.request("GET", f"{ADMIN}/list-canned-policies")
    assert "adm-ro" in json.loads(r.body)
    # service account for the user
    r = c.request(
        "POST", f"{ADMIN}/service-account", query={"parent": "adminmade"}
    )
    creds = json.loads(r.body)
    sa = S3Client(server.endpoint, creds["accessKey"], creds["secretKey"])
    assert sa.get_object("admbkt", "obj1").status == 200
    # disable then remove
    r = c.request(
        "PUT", f"{ADMIN}/set-user-status",
        query={"accessKey": "adminmade", "status": "disabled"},
    )
    assert r.status == 200
    assert u.get_object("admbkt", "obj1").status == 403
    r = c.request(
        "DELETE", f"{ADMIN}/remove-user", query={"accessKey": "adminmade"}
    )
    assert r.status == 200
    assert u.get_object("admbkt", "obj1").status == 403
    # unknown user maps to a 4xx, not a 500
    r = c.request(
        "DELETE", f"{ADMIN}/remove-user", query={"accessKey": "ghost9"}
    )
    assert r.status == 400


def test_metrics_endpoint(server, root_client):
    import time

    c = root_client
    c.get_object("admbkt", "obj1")
    c.get_object("admbkt", "missing-xyz")  # a 404 sample
    time.sleep(0.3)  # observation lands just after the response bytes
    # unauthenticated scrape is rejected by default (JWT mode)
    assert (
        c.request(
            "GET", "/minio-tpu/prometheus/metrics", sign=False
        ).status
        == 403
    )
    r = c.request("GET", "/minio-tpu/prometheus/metrics")
    assert r.status == 200
    text = r.body.decode()
    assert 'miniotpu_s3_requests_total{api="GetObject",code="200"}' in text
    assert 'miniotpu_s3_requests_total{api="GetObject",code="404"}' in text
    assert "miniotpu_s3_request_seconds_total" in text
    assert "miniotpu_disk_storage_used_bytes" in text
    assert "miniotpu_disks_total 4" in text
    assert "miniotpu_process_uptime_seconds" in text
    # tx moves with object downloads (review finding: dead counter)
    import re as _re

    tx = int(_re.search(r"miniotpu_s3_tx_bytes_total (\d+)", text).group(1))
    assert tx >= len(b"hello metrics")
    # counters move
    c.get_object("admbkt", "obj1")
    time.sleep(0.3)
    r2 = c.request("GET", "/minio-tpu/prometheus/metrics")
    import re

    def count_of(body):
        m = re.search(
            r'requests_total\{api="GetObject",code="200"\} (\d+)',
            body.decode(),
        )
        return int(m.group(1))

    assert count_of(r2.body) == count_of(r.body) + 1


def test_reserved_router_bucket(server, root_client):
    r = root_client.make_bucket("minio-tpu")
    assert r.status == 403


def test_structured_log_shape(capsys):
    from minio_tpu.utils import log

    log.setup()
    log.logger("test").info("hello", extra=log.kv(bucket="bk", n=3))
    out = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(out)
    assert doc["msg"] == "hello"
    assert doc["bucket"] == "bk" and doc["n"] == 3
    assert doc["level"] == "info"


def test_admin_healthinfo(server, root_client):
    """OBD diagnostics: platform + per-drive microprobe
    (admin-handlers.go OBDInfoHandler)."""
    r = root_client.request("GET", f"{ADMIN}/healthinfo")
    assert r.status == 200, r.body
    doc = json.loads(r.body)
    node = doc["nodes"][0]
    assert node["state"] == "online"
    assert node["cpus"] >= 1
    assert node["mem_total_bytes"] > 0
    # what the codec runs on, as JAX reports it - never guessed
    import jax

    dev = node["device"]
    assert dev["backend"] == "tpu"
    assert dev["platform"] == jax.devices()[0].platform == "cpu"
    assert dev["device_kind"] == jax.devices()[0].device_kind
    assert dev["device_count"] == len(jax.devices())
    assert [d["id"] for d in dev["devices"]] == [
        d.id for d in jax.devices()
    ]
    assert {"jax", "jaxlib", "libtpu", "compile_cache"} <= set(dev)
    drives = node["drives"]
    assert len(drives) == 4
    for d in drives:
        assert d["state"] == "ok"
        assert d["write_mibps"] > 0 and d["read_mibps"] > 0
        assert d["total"] > 0


def test_admin_background_heal_status(server, root_client):
    r = root_client.request(
        "GET", f"{ADMIN}/background-heal/status"
    )
    assert r.status == 200, r.body
    node = json.loads(r.body)["nodes"][0]
    assert node["state"] == "online"
    assert {"enabled", "queued", "healed", "failed"} <= set(node)


def test_admin_service_action_validated(server, root_client, monkeypatch):
    from minio_tpu.server.admin import AdminAPI

    fired = []
    monkeypatch.setattr(
        AdminAPI, "_signal_self",
        staticmethod(lambda action: fired.append(action)),
    )
    r = root_client.request(
        "POST", f"{ADMIN}/service", query={"action": "bogus"}
    )
    assert r.status == 400
    assert fired == []
    r = root_client.request(
        "POST", f"{ADMIN}/service", query={"action": "stop"}
    )
    assert r.status == 200, r.body
    assert fired == ["stop"]
    assert json.loads(r.body)["action"] == "stop"
