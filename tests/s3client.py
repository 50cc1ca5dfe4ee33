"""Minimal SigV4-signing S3 client for black-box server tests.

The in-process stand-in for the SDK clients the reference's mint suite
uses; no boto3 in this image, so requests are built and signed by hand
(like cmd/test-utils_test.go signRequestV4).
"""

from __future__ import annotations

import datetime
import hashlib
import http.client
import urllib.parse
import xml.etree.ElementTree as ET

from minio_tpu.server import auth


class S3Response:
    def __init__(self, status: int, headers: dict, body: bytes):
        self.status = status
        self.headers = headers
        self.body = body

    @property
    def xml(self) -> ET.Element:
        return ET.fromstring(self.body)

    def xml_text(self, tag: str) -> str:
        """First matching tag text, namespace-insensitive."""
        for el in self.xml.iter():
            if el.tag.split("}")[-1] == tag:
                return el.text or ""
        return ""

    def xml_all(self, tag: str) -> list[str]:
        return [
            el.text or ""
            for el in self.xml.iter()
            if el.tag.split("}")[-1] == tag
        ]

    @property
    def error_code(self) -> str:
        try:
            return self.xml_text("Code")
        except ET.ParseError:
            return ""


class S3Client:
    def __init__(
        self,
        endpoint: str,
        access_key: str = "minioadmin",
        secret_key: str = "minioadmin",
        region: str = "us-east-1",
        timeout: float = 30,
    ):
        self.timeout = timeout
        parsed = urllib.parse.urlsplit(endpoint)
        self.host = parsed.hostname
        self.tls = parsed.scheme == "https"
        self.port = parsed.port or (443 if self.tls else 80)
        self.access_key = access_key
        self.secret_key = secret_key
        self.region = region

    def _connect(self):
        if self.tls:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            return http.client.HTTPSConnection(
                self.host, self.port, timeout=self.timeout, context=ctx
            )
        return http.client.HTTPConnection(
            self.host, self.port, timeout=self.timeout
        )

    def request(
        self,
        method: str,
        path: str,
        query: "dict[str, str] | None" = None,
        body: bytes = b"",
        headers: "dict[str, str] | None" = None,
        sign: bool = True,
    ) -> S3Response:
        url, headers = self.signed(
            method, path, query, body, headers, sign
        )
        conn = self._connect()
        try:
            conn.request(method, url, body=body or None, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            return S3Response(
                resp.status, {k.lower(): v for k, v in resp.getheaders()}, data
            )
        finally:
            conn.close()

    def signed(
        self,
        method: str,
        path: str,
        query: "dict[str, str] | None" = None,
        body: bytes = b"",
        headers: "dict[str, str] | None" = None,
        sign: bool = True,
    ) -> "tuple[str, dict[str, str]]":
        """(url, headers) of the request, for a test that drives the
        connection itself (``_connect()``)."""
        query = dict(query or {})
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        amz_date = datetime.datetime.now(
            datetime.timezone.utc
        ).strftime("%Y%m%dT%H%M%SZ")
        phash = hashlib.sha256(body).hexdigest()
        headers.setdefault("host", f"{self.host}:{self.port}")
        if sign:
            headers["x-amz-date"] = amz_date
            headers["x-amz-content-sha256"] = phash
            signed = sorted(headers)
            qmap = {k: [v] for k, v in query.items()}
            sig = auth.sign_v4(
                method, path, qmap, headers, signed, phash,
                self.access_key, self.secret_key, amz_date, self.region,
            )
            scope = f"{amz_date[:8]}/{self.region}/s3/aws4_request"
            headers["authorization"] = (
                f"{auth.SIGN_V4_ALGORITHM} "
                f"Credential={self.access_key}/{scope}, "
                f"SignedHeaders={';'.join(signed)}, Signature={sig}"
            )
        qs = urllib.parse.urlencode(query)
        url = urllib.parse.quote(path) + (f"?{qs}" if qs else "")
        return url, headers

    # -- conveniences -----------------------------------------------------

    def make_bucket(self, bucket):
        return self.request("PUT", f"/{bucket}")

    def put_object(self, bucket, key, data: bytes, headers=None):
        return self.request(
            "PUT", f"/{bucket}/{key}", body=data, headers=headers
        )

    def get_object(self, bucket, key, headers=None, query=None):
        return self.request(
            "GET", f"/{bucket}/{key}", headers=headers, query=query
        )

    def head_object(self, bucket, key, headers=None):
        return self.request("HEAD", f"/{bucket}/{key}", headers=headers)

    def delete_object(self, bucket, key):
        return self.request("DELETE", f"/{bucket}/{key}")

    def delete_object_version(self, bucket, key, version_id):
        return self.request(
            "DELETE", f"/{bucket}/{key}", query={"versionId": version_id}
        )

    def list_objects(self, bucket, **query):
        return self.request("GET", f"/{bucket}", query=query)


    # -- streaming SigV4 (aws-chunked) ------------------------------------

    def put_object_streaming(
        self, bucket, key, data: bytes, chunk_size: int = 64 * 1024,
        signed: bool = True, bad_trailer: bool = False,
        corrupt_final_sig: bool = False,
    ):
        """Upload with the aws-chunked framing the AWS SDKs/CLI use
        (STREAMING-AWS4-HMAC-SHA256-PAYLOAD)."""
        import hmac as hmac_mod

        path = f"/{bucket}/{key}"
        amz_date = datetime.datetime.now(
            datetime.timezone.utc
        ).strftime("%Y%m%dT%H%M%SZ")
        scope = f"{amz_date[:8]}/{self.region}/s3/aws4_request"
        payload_decl = (
            auth.STREAMING_PAYLOAD
            if signed
            else auth.STREAMING_UNSIGNED_TRAILER
        )
        # build the encoded body
        chunks = [
            data[i : i + chunk_size]
            for i in range(0, len(data), chunk_size)
        ] + [b""]
        headers = {
            "host": f"{self.host}:{self.port}",
            "x-amz-date": amz_date,
            "x-amz-content-sha256": payload_decl,
            "x-amz-decoded-content-length": str(len(data)),
            "content-encoding": "aws-chunked",
        }
        if not signed:
            # declare the trailing checksum like the AWS SDKs do
            headers["x-amz-trailer"] = "x-amz-checksum-crc32"
        signed_hdrs = sorted(headers)
        sig = auth.sign_v4(
            "PUT", path, {}, headers, signed_hdrs, payload_decl,
            self.access_key, self.secret_key, amz_date, self.region,
        )
        headers["authorization"] = (
            f"{auth.SIGN_V4_ALGORITHM} "
            f"Credential={self.access_key}/{scope}, "
            f"SignedHeaders={';'.join(signed_hdrs)}, Signature={sig}"
        )
        key_bytes = auth._signing_key(
            self.secret_key, amz_date[:8], self.region, "s3"
        )
        prev = sig
        body = bytearray()
        for c in chunks:
            if signed:
                sts = "\n".join(
                    [
                        "AWS4-HMAC-SHA256-PAYLOAD",
                        amz_date,
                        scope,
                        prev,
                        auth.EMPTY_SHA256,
                        hashlib.sha256(c).hexdigest(),
                    ]
                )
                csig = hmac_mod.new(
                    key_bytes, sts.encode(), hashlib.sha256
                ).hexdigest()
                prev = csig
                if corrupt_final_sig and not c:
                    csig = "0" * 64
                body += f"{len(c):x};chunk-signature={csig}\r\n".encode()
            else:
                body += f"{len(c):x}\r\n".encode()
            if c:
                body += c + b"\r\n"
        if not signed:
            import base64 as b64
            import zlib

            crc = zlib.crc32(data).to_bytes(4, "big")
            if bad_trailer:
                crc = bytes(b ^ 0xFF for b in crc)
            cksum = b64.b64encode(crc).decode()
            body += f"x-amz-checksum-crc32:{cksum}\r\n".encode()
        body += b"\r\n"
        conn = self._connect()
        try:
            conn.request("PUT", path, body=bytes(body), headers=headers)
            resp = conn.getresponse()
            rbody = resp.read()
            return S3Response(
                resp.status,
                {k.lower(): v for k, v in resp.getheaders()},
                rbody,
            )
        finally:
            conn.close()

    # -- SigV2 ------------------------------------------------------------

    def request_v2(
        self, method, path, query=None, body: bytes = b"",
        headers=None,
    ):
        query = dict(query or {})
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        headers.setdefault("host", f"{self.host}:{self.port}")
        headers.setdefault(
            "date",
            datetime.datetime.now(datetime.timezone.utc).strftime(
                "%a, %d %b %Y %H:%M:%S GMT"
            ),
        )
        qmap = {k: [v] for k, v in query.items()}
        date_str = "" if "x-amz-date" in headers else headers["date"]
        sig = auth.sign_v2(
            method, path, qmap, headers, self.secret_key, date_str
        )
        headers["authorization"] = f"AWS {self.access_key}:{sig}"
        qs = urllib.parse.urlencode(query)
        url = urllib.parse.quote(path) + (f"?{qs}" if qs else "")
        conn = self._connect()
        try:
            conn.request(method, url, body=body or None, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            return S3Response(
                resp.status,
                {k.lower(): v for k, v in resp.getheaders()},
                data,
            )
        finally:
            conn.close()

    # -- POST policy ------------------------------------------------------

    def post_policy_upload(
        self, bucket, key, data: bytes, conditions=None,
        expires_in: int = 600, extra_fields=None, status: str = "",
    ):
        import base64 as b64
        import hmac as hmac_mod
        import json

        amz_date = datetime.datetime.now(
            datetime.timezone.utc
        ).strftime("%Y%m%dT%H%M%SZ")
        scope = f"{amz_date[:8]}/{self.region}/s3/aws4_request"
        credential = f"{self.access_key}/{scope}"
        exp = (
            datetime.datetime.now(datetime.timezone.utc)
            + datetime.timedelta(seconds=expires_in)
        ).strftime("%Y-%m-%dT%H:%M:%S.000Z")
        conds = [
            {"bucket": bucket},
            ["eq", "$key", key],
            {"x-amz-credential": credential},
            {"x-amz-date": amz_date},
            {"x-amz-algorithm": auth.SIGN_V4_ALGORITHM},
        ] + list(conditions or [])
        # every submitted field must be covered by a condition
        if status:
            conds.append({"success_action_status": status})
        for ek, ev in (extra_fields or {}).items():
            if ek not in ("x-amz-signature", "policy"):
                conds.append({ek: ev})
        policy = b64.b64encode(
            json.dumps({"expiration": exp, "conditions": conds}).encode()
        ).decode()
        key_bytes = auth._signing_key(
            self.secret_key, amz_date[:8], self.region, "s3"
        )
        sig = hmac_mod.new(
            key_bytes, policy.encode(), hashlib.sha256
        ).hexdigest()
        fields = {
            "key": key,
            "policy": policy,
            "x-amz-algorithm": auth.SIGN_V4_ALGORITHM,
            "x-amz-credential": credential,
            "x-amz-date": amz_date,
            "x-amz-signature": sig,
        }
        if status:
            fields["success_action_status"] = status
        fields.update(extra_fields or {})
        boundary = "----tpuboundary42"
        body = bytearray()
        for fk, fv in fields.items():
            body += (
                f"--{boundary}\r\nContent-Disposition: form-data; "
                f'name="{fk}"\r\n\r\n{fv}\r\n'
            ).encode()
        body += (
            f"--{boundary}\r\nContent-Disposition: form-data; "
            f'name="file"; filename="upload.bin"\r\n'
            f"Content-Type: application/octet-stream\r\n\r\n"
        ).encode()
        body += data + f"\r\n--{boundary}--\r\n".encode()
        headers = {
            "host": f"{self.host}:{self.port}",
            "content-type": f"multipart/form-data; boundary={boundary}",
        }
        conn = self._connect()
        try:
            conn.request(
                "POST", f"/{bucket}", body=bytes(body), headers=headers
            )
            resp = conn.getresponse()
            rbody = resp.read()
            return S3Response(
                resp.status,
                {k.lower(): v for k, v in resp.getheaders()},
                rbody,
            )
        finally:
            conn.close()
