"""The benchmark's own S3 client: SigV4 over a kept-alive HTTP connection.

A copy of the idea of ``tests/s3client.py``, not of its code: the signing
here imports nothing of the program, so a later PR can change the server's
``auth`` module without moving the yardstick.  One ``Client`` belongs to one
thread.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import urllib.parse

EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()
ADMIN = "/minio-tpu/admin/v1"


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def sign_v4(method: str, path: str, query: "dict[str, str]", headers: "dict[str, str]",
            payload_hash: str, access: str, secret: str, amz_date: str,
            region: str) -> str:
    """The Authorization header value (AWS Signature Version 4, service s3)."""
    signed = sorted(headers)
    canonical_query = "&".join(
        f"{urllib.parse.quote(k, safe='-_.~')}={urllib.parse.quote(v, safe='-_.~')}"
        for k, v in sorted(query.items())
    )
    canonical = "\n".join([
        method,
        urllib.parse.quote(path, safe="/-_.~"),
        canonical_query,
        "".join(f"{h}:{' '.join(headers[h].split())}\n" for h in signed),
        ";".join(signed),
        payload_hash,
    ])
    date = amz_date[:8]
    scope = f"{date}/{region}/s3/aws4_request"
    to_sign = "\n".join([
        "AWS4-HMAC-SHA256", amz_date, scope,
        hashlib.sha256(canonical.encode()).hexdigest(),
    ])
    key = _hmac(_hmac(_hmac(_hmac(("AWS4" + secret).encode(), date), region), "s3"),
                "aws4_request")
    sig = hmac.new(key, to_sign.encode(), hashlib.sha256).hexdigest()
    return (f"AWS4-HMAC-SHA256 Credential={access}/{scope}, "
            f"SignedHeaders={';'.join(signed)}, Signature={sig}")


class Response:
    __slots__ = ("status", "headers", "body")

    def __init__(self, status: int, headers: "dict[str, str]", body: bytes):
        self.status = status
        self.headers = headers
        self.body = body


class Client:
    """One kept-alive connection; reconnects once when the server closed an
    idle connection before any byte of the reply (counted in ``reconnects``)."""

    def __init__(self, host: str, port: int, access: str = "minioadmin",
                 secret: str = "minioadmin", region: str = "us-east-1",
                 timeout: float = 300.0):
        self.host, self.port = host, port
        self.access, self.secret, self.region = access, secret, region
        self.timeout = timeout
        self.reconnects = 0
        self._conn: "http.client.HTTPConnection | None" = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, query: "dict[str, str] | None" = None,
                body: "tuple[bytes | memoryview, ...]" = (),
                body_sha256: str = EMPTY_SHA256) -> Response:
        """``body`` is a tuple of pieces sent one after another (no copy of a
        10 MiB payload); ``body_sha256`` is the hex SHA-256 of their join."""
        query = query or {}
        amz_date = datetime.datetime.now(datetime.timezone.utc).strftime("%Y%m%dT%H%M%SZ")
        headers = {
            "host": f"{self.host}:{self.port}",
            "x-amz-content-sha256": body_sha256,
            "x-amz-date": amz_date,
        }
        headers["authorization"] = sign_v4(
            method, path, query, headers, body_sha256, self.access, self.secret,
            amz_date, self.region)
        length = sum(len(p) for p in body)
        if length or method in ("PUT", "POST"):
            headers["content-length"] = str(length)
        qs = urllib.parse.urlencode(query)
        url = urllib.parse.quote(path, safe="/-_.~") + (f"?{qs}" if qs else "")
        for attempt in (0, 1):
            fresh = self._conn is None
            if fresh:
                self._conn = http.client.HTTPConnection(self.host, self.port,
                                                        timeout=self.timeout)
            conn = self._conn
            try:
                conn.putrequest(method, url, skip_host=True, skip_accept_encoding=True)
                for k, v in headers.items():
                    conn.putheader(k, v)
                conn.endheaders()
                try:
                    for piece in body:
                        conn.send(piece)
                except (BrokenPipeError, ConnectionResetError):
                    if not fresh:
                        raise
                    # a server that sheds answers 503 and closes without reading the
                    # body: the answer is there to be read though the send failed
                resp = conn.getresponse()
                data = resp.read()
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError, http.client.CannotSendRequest):
                self.close()
                if fresh or attempt:
                    raise
                self.reconnects += 1
                continue
            if resp.will_close:
                self.close()
            return Response(resp.status, {k.lower(): v for k, v in resp.getheaders()}, data)
        raise AssertionError("unreachable")

    def admin(self, route: str, method: str = "GET", **query: str) -> dict:
        import json

        r = self.request(method, f"{ADMIN}/{route}", query=query)
        if r.status != 200:
            raise RuntimeError(f"admin {route} -> {r.status} {r.body[:200]!r}")
        return json.loads(r.body)
