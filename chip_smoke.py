#!/usr/bin/env python3
"""chip_smoke.py - does the served PUT/GET path start, compile and answer
right on the chip?

    python3 chip_smoke.py [--seed N]

One owner of the chip at a time.  This parent process never imports JAX;
it runs three children one after another and checks what they report:

1. ``codec`` - drives the production codec seam directly
   (TpuBackend.encode_digest_begin/_end, drain, reconstruct_and_verify,
   reconstruct, digest) at EC 4+2 / 8+4 / 16+4 with full 10 MiB
   blockSizeV1 blocks, B = 1 and 8, plus the ragged width of EC 12+4,
   bit for bit against the CPU codec; on more than one device also the
   mesh path and one routed pass per device.
2. ``python -m minio_tpu.server --parity 4`` over 12 drive directories
   (EC 8+4, BASELINE config 2), driven through HTTP + SigV4 from this
   process (tests/s3client.py, no JAX): >= 256 MiB loaded as 10 MiB
   objects by 8 concurrent clients, a multi-block object with a short
   tail, a 4 KiB object and an odd-length one; every GET compared byte
   for byte with its PUT payload; the 16 sizes of `mixed-randsize`; one
   64 MiB object (seven blocks in one stream: two batches a direction,
   no launch above the seam's 32 MiB), read whole and as a range across
   its fourth block's end; STAT and DELETE; the shard files of two
   drives removed - one pair of drives under half the objects, another
   pair under the rest - and everything read again (device reconstruct,
   several loss patterns, one program: the codec child counts it); one
   object healed and read healthy; SIGTERM and a clean exit.
3. the same server a second time on the same drives: objects
   acknowledged before the restart read back identical, and the compile
   cache is seen to hit.

Exit code 0 only if every phase passed on a TPU; then the last two
stdout lines are the ``"event": "summary"`` report (counts, ending in
``"claim": null``) and the result line, which has exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
(``jax.devices()[0].platform``, ``.device_kind``, ``len(jax.devices())``).
It exits non-zero, and prints no result line, when JAX finds no
accelerator, when it stands alone without the ``minio_tpu`` package, when
MINIO_TPU_CODEC_INTERPRET is set, when a tile-aligned width ran the
portable branch instead of the Pallas kernel, when any response was a
5xx, or when any comparison failed.  No network; every process it starts
is stopped before it returns.

``--rehearse-cpu`` is the sandbox rehearsal: the same phases at a cut
size over XLA:CPU.  Every line it prints says ``platform=cpu`` and its
result is no statement about a chip.  The plain invocation cannot reach
it.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20
BLOCK = 10 * MIB  # blockSizeV1
ADMIN = "/minio-tpu/admin/v1"
BUCKET = "smoke"
# drives whose shard files the degraded phase removes: the first pair
# under every other object (and the one that is healed), the second
# under the rest, so that the reads decode from different loss patterns
LOST_DRIVES = (2, 7)
LOST_DRIVES_TOO = (4, 11)


class SmokeFailure(Exception):
    """A phase failed; the message is the one line that says why."""


def check(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


# ---------------------------------------------------------------------------
# child 1: the codec seam, in a process of its own (this one imports JAX)
# ---------------------------------------------------------------------------


def codec_child(seed: int, rehearse: bool) -> int:
    sys.path.insert(0, HERE)
    from minio_tpu.utils import jaxenv

    cache_dir = jaxenv.setup_compile_cache()  # before the first JAX use

    import numpy as np

    from minio_tpu.codec.backend import CpuBackend, TpuBackend
    from minio_tpu.codec.erasure import Erasure
    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.ops import codec_step, rs_pallas
    from minio_tpu.parallel import rules as prules

    info = jaxenv.device_info()  # raises if the chip is missing or held
    env = {
        "platform": info["platform"],
        "device_kind": info["device_kind"],
        "device_count": info["device_count"],
        "jax": info["jax"],
        "jaxlib": info["jaxlib"],
        "libtpu": info["libtpu"],
        "cache_dir": cache_dir,
    }

    def say(**fields) -> None:
        print(json.dumps({"phase": "codec", **env, **fields}), flush=True)

    if info["platform"] != "tpu" and not rehearse:
        print(
            f"chip_smoke: JAX found platform {info['platform']!r}, not a "
            "TPU; nothing was run",
            file=sys.stderr,
        )
        return 3
    say(event="device", devices=info["devices"])

    import jax

    ndev = info["device_count"]
    ref = CpuBackend()
    say(
        event="reference",
        cpu_codec="native" if CpuBackend._native_fused() else "numpy",
    )
    rng = np.random.default_rng(seed)
    # single-device seam first, even on a multi-chip host: pin device 0
    one = TpuBackend(devices=jax.devices()[:1])
    batches = (1, 2) if rehearse else (1, 8)

    def drive(be, k, m, B, block, label):
        """One shape through the whole seam, compared with the CPU codec."""
        L = Erasure(k, m).shard_size_padded(block)
        data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
        want_par, want_dig = ref.encode(data, m)
        t0 = time.monotonic()
        dig, pref = be.encode_digest_end(be.encode_digest_begin(data, m))
        par = be.drain(pref)
        cold = time.monotonic() - t0
        check(np.array_equal(dig, want_dig), f"{label}: digests differ")
        check(np.array_equal(par, want_par), f"{label}: parity differs")
        t0 = time.monotonic()
        dig2, pref2 = be.encode_digest_end(be.encode_digest_begin(data, m))
        be.drain(pref2)
        warm = time.monotonic() - t0
        check(np.array_equal(dig2, want_dig), f"{label}: rerun differs")

        n = k + m
        whole = np.concatenate([data, par], axis=1)
        # a second lost pair first: a loss pattern is an operand, so the
        # pair after it must find its program compiled
        shards, present = whole.copy(), [True] * n
        for lost in (1, k)[: min(m, 2)]:
            present[lost] = False
            shards[:, lost] = 0x5A
        rec = be.reconstruct(shards, tuple(present), k, m)
        check(np.array_equal(rec, data), f"{label}: reconstruct (2) differs")
        programs = codec_step.reconstruct_words_batch._cache_size()
        shards, present = whole.copy(), [True] * n
        for lost in (0, n - 1)[: min(m, 2)]:
            present[lost] = False
            shards[:, lost] = 0xA5  # garbage where the shard is gone
        t0 = time.monotonic()
        got, ok = be.reconstruct_and_verify(
            shards, dig, tuple(present), k, m
        )
        vr_cold = time.monotonic() - t0
        want, want_ok = ref.reconstruct_and_verify(
            shards, dig, tuple(present), k, m
        )
        check(np.array_equal(got, data), f"{label}: heal decode differs")
        check(np.array_equal(got, want), f"{label}: heal != cpu codec")
        check(np.array_equal(ok, want_ok), f"{label}: ok mask differs")
        t0 = time.monotonic()
        rec = be.reconstruct(shards, tuple(present), k, m)
        rec_cold = time.monotonic() - t0
        check(np.array_equal(rec, data), f"{label}: reconstruct differs")
        check(
            codec_step.reconstruct_words_batch._cache_size() == programs,
            f"{label}: a second lost pair compiled a second reconstruct",
        )
        t0 = time.monotonic()
        dg = be.digest(shards[:, :k])
        dg_cold = time.monotonic() - t0
        check(
            np.array_equal(dg, ref.digest(shards[:, :k])),
            f"{label}: read digest differs",
        )
        say(
            event="shape",
            shape=label,
            shard_bytes=L,
            tile_aligned=(L // 4) % rs_pallas._TW == 0,
            cold_seconds={
                "encode_digest+drain": round(cold, 3),
                "reconstruct_and_verify": round(vr_cold, 3),
                "reconstruct": round(rec_cold, 3),
                "digest": round(dg_cold, 3),
            },
            warm_encode_digest_drain_seconds=round(warm, 4),
            lost_pairs=2,
            reconstruct_programs=1,
            bit_identical=True,
        )

    KERNEL_STATS.reset()
    for k, m in ((4, 2), (8, 4), (16, 4)):
        for B in batches:
            drive(one, k, m, B, BLOCK, f"ec{k}+{m} B={B} 10MiB")
    snap = KERNEL_STATS.snapshot()
    fused = (
        "encode_words_fused1",
        "verify_and_reconstruct_words",
        "reconstruct_words_batch",
    )
    if not rehearse:
        left = {
            name: n
            for name, n in snap["portable_passes"].items()
            if name in fused
        }
        check(
            not left,
            f"tile-aligned widths ran the portable branch: {left}",
        )
        for name in fused:
            check(
                snap["pallas_passes"].get(name, 0) > 0,
                f"no Pallas-compiled pass of {name}",
            )
    say(
        event="aligned",
        device_passes=snap["device_passes"],
        pallas_passes=snap["pallas_passes"],
        portable_passes=snap["portable_passes"],
    )
    # a ragged width: EC 12+4 cuts a 10 MiB block into 873,824-byte
    # shards, 53.3 of the 16 KiB Pallas tiles.  The seam stages them at
    # the 56-tile rung with their length an operand, so they too take
    # the kernels (the portable form is what another platform runs)
    drive(one, 12, 4, 1, BLOCK, "ec12+4 B=1 10MiB ragged")
    ragged = KERNEL_STATS.snapshot()
    if rehearse:
        check(
            ragged["portable_passes"].get("encode_words_fused1", 0) > 0,
            "the rehearsal's passes were not counted as portable",
        )
    else:
        for name in fused:
            check(
                ragged["pallas_passes"].get(name, 0)
                > snap["pallas_passes"].get(name, 0),
                f"the ragged width ran no Pallas-compiled pass of {name}",
            )
            check(
                not ragged["portable_passes"].get(name),
                f"the ragged width ran the portable branch of {name}",
            )
    check(
        "917504" in ragged["ragged"]["staged_rows"],
        f"873,824-byte rows were not staged at 56 tiles: {ragged['ragged']}",
    )

    if ndev > 1:
        # whatever device count is visible works: the mesh path (stripe
        # axis at B >= ndev, shard axis at B = 1) and one routed
        # single-device pass per device
        span = TpuBackend()
        for B in (1, ndev, 2 * ndev):
            drive(span, 8, 4, B, BLOCK, f"mesh ec8+4 B={B} 10MiB")
        for d in jax.devices():
            with prules.placed((d,)):
                drive(span, 8, 4, 1, BLOCK, f"routed dev{d.id} ec8+4 B=1")
    after = jaxenv.device_info()
    idle = [
        d["id"]
        for d in after["devices"]
        if not rehearse and not d["peak_bytes_in_use"]
    ]
    check(not idle, f"no codec work reached devices {idle}")
    snap = KERNEL_STATS.snapshot()
    say(
        event="done",
        device_passes=snap["device_passes"],
        pallas_passes=snap["pallas_passes"],
        portable_passes=snap["portable_passes"],
        h2d=snap["h2d"],
        d2h=snap["d2h"],
        memory=after["devices"],
        compile_cache=after["compile_cache"],
    )
    return 0


# ---------------------------------------------------------------------------
# the parent: no JAX from here on
# ---------------------------------------------------------------------------


class Counted:
    """S3 client that counts what it saw; a 5xx anywhere fails the run."""

    def __init__(self, endpoint: str):
        sys.path.insert(0, os.path.join(HERE, "tests"))
        sys.path.insert(0, HERE)
        import s3client

        # a first request may sit behind a cold compile
        self._client = s3client.S3Client(endpoint, timeout=600)
        self._mu = threading.Lock()
        self.requests = 0
        self.server_errors: "list[str]" = []

    def request(self, method, path, **kw):
        r = self._client.request(method, path, **kw)
        with self._mu:
            self.requests += 1
            if r.status >= 500:
                self.server_errors.append(
                    f"{method} {path} -> {r.status} {r.body[:200]!r}"
                )
        return r

    def admin(self, method, route, **query):
        r = self.request(method, f"{ADMIN}/{route}", query=query)
        check(r.status == 200, f"admin {route} -> {r.status} {r.body[:200]!r}")
        return json.loads(r.body)


def payload(seed: int, key: str, size: int) -> bytes:
    return random.Random(f"{seed}/{key}").randbytes(size)


class Server:
    """One ``python -m minio_tpu.server`` child and its log."""

    def __init__(self, workdir: str, drives: "list[str]", name: str,
                 rehearse: bool):
        self.log_path = os.path.join(workdir, f"{name}.log")
        self.port = _free_port()
        env = dict(os.environ, PYTHONPATH=HERE, PYTHONUNBUFFERED="1")
        if rehearse:
            env["MINIO_ERASURE_BACKEND"] = "tpu"  # device path over XLA:CPU
            env["JAX_PLATFORMS"] = "cpu"
        else:
            env.pop("MINIO_ERASURE_BACKEND", None)  # auto must find the chip
        self.t_start = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "minio_tpu.server",
                "--address", f"127.0.0.1:{self.port}",
                "--parity", "4", *drives,
            ],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )
        self.endpoint = f"http://127.0.0.1:{self.port}"

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def wait_ready(self, timeout: float = 300.0) -> float:
        import http.client

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            check(
                self.proc.poll() is None,
                "server exited during boot (code "
                f"{self.proc.returncode}): {self.log_text()[-1500:]}",
            )
            try:
                c = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=5
                )
                c.request("GET", "/minio/health/ready")
                status = c.getresponse().status
                c.close()
                if status == 200:
                    return time.monotonic() - self.t_start
            except OSError:
                pass  # not listening yet
            time.sleep(0.25)
        raise SmokeFailure(
            f"server not ready in {timeout:.0f}s: {self.log_text()[-1500:]}"
        )

    def codec_line(self) -> str:
        for line in self.log_text().splitlines():
            if line.startswith("minio-tpu codec "):
                return line
        raise SmokeFailure("server never logged its codec backend")

    def stop(self) -> None:
        """SIGTERM, and require the graceful path to finish."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server ignored SIGTERM for 120 s") from None
        check(code == 0, f"server exited {code} after SIGTERM")
        check(
            "shutdown complete" in self.log_text(),
            "server exited without completing its shutdown",
        )

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def width_ladder(full_shard: int) -> "list[int]":
    """The staged widths the codec seam may launch for shards of up to
    ``full_shard`` bytes, worked out here from the rule and not taken
    from the program: whole 16 KiB tiles, every count up to 8, then
    steps of an eighth of the next power of two."""
    tile, out, t = 16384, [], 0
    while t * tile < full_shard:
        t += 1
        if t > 8:
            step = (1 << (t - 1).bit_length()) >> 3
            t = -(-t // step) * step
        out.append(t * tile)
    return out


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parallel(fn, items, clients: int) -> None:
    """Run fn(item) over items from ``clients`` threads; first error wins."""
    todo = list(items)
    mu = threading.Lock()
    errors: "list[BaseException]" = []

    def worker():
        while True:
            with mu:
                if not todo or errors:
                    return
                item = todo.pop(0)
            try:
                fn(item)
            except BaseException as e:  # re-raised below, never dropped
                with mu:
                    errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def served_phases(seed: int, rehearse: bool, env: dict, workdir: str,
                  say) -> dict:
    drives = [os.path.join(workdir, f"drive{i}") for i in range(1, 13)]
    for d in drives:
        os.makedirs(d)
    n_big = 3 if rehearse else 26  # 26 x 10 MiB = 260 MiB >= 256 MiB
    sizes = {f"big-{i:02d}": BLOCK for i in range(n_big)}
    sizes["multi-tail"] = 3 * BLOCK + 1_234_567  # 4 blocks, short tail
    sizes["small-4k"] = 4096
    sizes["odd"] = 1_000_003  # ragged shard width
    summary: dict = {}

    def get_all(c: Counted, keys, what: str) -> None:
        def one(key):
            r = c.request("GET", f"/{BUCKET}/{key}")
            check(r.status == 200, f"{what}: GET {key} -> {r.status}")
            check(
                r.body == payload(seed, key, sizes[key]),
                f"{what}: GET {key} differs from its PUT payload",
            )

        _parallel(one, keys, 8)

    # ---- first server: load, read, stat, delete, degrade, heal ----------
    srv = Server(workdir, drives, "server1", rehearse)
    try:
        boot1 = srv.wait_ready()
        line = srv.codec_line()
        say(event="boot", server=1, boot_seconds=round(boot1, 2), codec=line)
        want = "platform='cpu'" if rehearse else "platform='tpu'"
        check("backend='tpu'" in line and want in line,
              f"server did not resolve the device backend: {line}")
        c = Counted(srv.endpoint)
        check(c.request("PUT", f"/{BUCKET}").status == 200, "make bucket")

        t0 = time.monotonic()
        r = c.request("PUT", f"/{BUCKET}/big-00",
                      body=payload(seed, "big-00", BLOCK))
        first_put1 = time.monotonic() - t0
        check(r.status == 200, f"first PUT -> {r.status} {r.body[:200]!r}")

        def put(key):
            r = c.request("PUT", f"/{BUCKET}/{key}",
                          body=payload(seed, key, sizes[key]))
            check(r.status == 200, f"PUT {key} -> {r.status} {r.body[:200]!r}")

        t0 = time.monotonic()
        _parallel(put, [k for k in sizes if k != "big-00"], 8)
        load_s = time.monotonic() - t0
        t0 = time.monotonic()
        r = c.request("GET", f"/{BUCKET}/big-00")
        first_get1 = time.monotonic() - t0
        check(r.status == 200 and r.body == payload(seed, "big-00", BLOCK),
              "first GET differs from its PUT payload")
        get_all(c, list(sizes), "healthy")
        say(event="loaded", objects=len(sizes),
            bytes=sum(sizes.values()), load_seconds=round(load_s, 2),
            first_put_seconds=round(first_put1, 3),
            first_get_seconds=round(first_get1, 3), healthy_reads="identical")

        # objects of every size: the 16 sizes of the benchmark's cell
        # `mixed-randsize` (log2-uniform over 40 KiB-10 MiB, none a
        # whole number of kernel tiles), PUT and GET once each.  Their
        # shards are staged at rungs of the seam's width ladder with
        # their lengths as operands, so the Pallas kernels run on them
        # and no width outside the ladder is ever launched.
        ragged = {
            f"rs-{j:02d}": round(40960 * 256 ** ((j + 0.5) / 16))
            // (16 if rehearse else 1)
            for j in range(16)
        }
        before = c.admin("GET", "kernel-stats")

        def put_get(key):
            body = payload(seed, key, ragged[key])
            r = c.request("PUT", f"/{BUCKET}/{key}", body=body)
            check(r.status == 200, f"PUT {key} -> {r.status} {r.body[:200]!r}")
            r = c.request("GET", f"/{BUCKET}/{key}")
            check(r.status == 200 and r.body == body,
                  f"ragged: GET {key} ({ragged[key]} bytes) differs from its PUT")

        _parallel(put_get, list(ragged), 8)
        after = c.admin("GET", "kernel-stats")
        rungs = width_ladder(BLOCK // 8)
        staged = {int(w) for w in after["ragged"]["staged_rows"]}
        check(staged <= set(rungs),
              f"widths launched off the ladder: {sorted(staged - set(rungs))}")
        rose = (after["pallas_passes"].get("encode_words_fused1", 0)
                - before["pallas_passes"].get("encode_words_fused1", 0))
        if not rehearse:
            check(rose >= 1, "ragged PUTs ran no Pallas encode pass: "
                  f"{after['pallas_passes']} {after['portable_passes']}")
            check(not after["portable_passes"].get("encode_words_fused1"),
                  f"an encode took the portable form: {after['portable_passes']}")
        r0, r1 = before["ragged"], after["ragged"]
        say(event="ragged", objects=len(ragged), bytes=sum(ragged.values()),
            pallas_encode_passes=rose, widths_true=r1["widths_true"],
            widths_staged=sorted(staged), ladder_rungs=len(rungs),
            pad_ratio=round((r1["staged_bytes"] - r0["staged_bytes"])
                            / max(1, r1["true_bytes"] - r0["true_bytes"]), 4),
            mixed_launches=r1["mixed_launches"] - r0["mixed_launches"],
            compile_cache=after["device"]["compile_cache"])

        # one object of many blocks: 64 MiB is six full blocks and a
        # 4 MiB tail in one stream (the size of the benchmark's cell
        # `mixed-64m`), so the PUT is two batches through the
        # double-buffered encode, the whole GET two through the
        # read-ahead, a batch of four full blocks goes out as launches
        # of two, and a range across the fourth block's end (where the
        # PUT's first batch ended) reads blocks four and five
        big = 64 << 20
        body = payload(seed, "blocks-7", big)
        before = c.admin("GET", "kernel-stats")
        r = c.request("PUT", f"/{BUCKET}/blocks-7", body=body)
        check(r.status == 200, f"PUT blocks-7 -> {r.status} {r.body[:200]!r}")
        r = c.request("GET", f"/{BUCKET}/blocks-7")
        check(r.status == 200 and r.body == body,
              "64 MiB: whole GET differs from its PUT")
        lo, hi = 4 * BLOCK - 1000, 4 * BLOCK + 999
        r = c.request("GET", f"/{BUCKET}/blocks-7",
                      headers={"Range": f"bytes={lo}-{hi}"})
        check(r.status == 206 and r.body == body[lo:hi + 1],
              f"64 MiB: range GET across the fourth block's end -> {r.status}")
        check(c.request("DELETE", f"/{BUCKET}/blocks-7").status == 204,
              "DELETE blocks-7")
        after = c.admin("GET", "kernel-stats")
        moved = {
            d: {f: after["stream"][d][f] - before["stream"][d][f]
                for f in after["stream"][d]}
            for d in ("encode", "decode")
        }
        check(moved["encode"]["blocks"] >= 7 and moved["encode"]["batches"] >= 2
              and moved["encode"]["tail_groups"] >= 1,
              f"the 64 MiB PUT was not two batches of seven blocks: {moved}")
        check(moved["decode"]["blocks"] >= 9 and moved["decode"]["batches"] >= 3,
              f"the 64 MiB GETs did not read seven blocks in two batches and "
              f"two in one: {moved}")
        peak = max((int(s) for s, n in after["launch"]["sizes"].items()
                    if n != before["launch"]["sizes"].get(s, 0)), default=0)
        check(0 < peak <= 32 << 20,
              f"a launch of {peak} bytes: over the seam's 32 MiB")
        say(event="multiblock", bytes=big, stream=moved, launch_peak_bytes=peak,
            launches=after["launch"]["count"] - before["launch"]["count"],
            split_calls=(after["launch"]["split_calls"]
                         - before["launch"]["split_calls"]))
        del body

        for key in ("big-01", "multi-tail", "small-4k"):
            r = c.request("HEAD", f"/{BUCKET}/{key}")
            check(r.status == 200, f"STAT {key} -> {r.status}")
            check(int(r.headers["content-length"]) == sizes[key],
                  f"STAT {key}: wrong length")
        gone = f"big-{n_big - 1:02d}"
        check(c.request("DELETE", f"/{BUCKET}/{gone}").status == 204,
              f"DELETE {gone}")
        check(c.request("GET", f"/{BUCKET}/{gone}").status == 404,
              f"GET {gone} after DELETE is not 404")
        check(c.request("HEAD", f"/{BUCKET}/{gone}").status == 404,
              f"STAT {gone} after DELETE is not 404")
        del sizes[gone]

        # degrade: the shard files of two drives go away, one pair of
        # drives under every other object, another under the rest
        removed = 0
        for j, key in enumerate(sorted(sizes)):
            first = j % 2 == 0 or key == "multi-tail"  # the healed one
            for i in LOST_DRIVES if first else LOST_DRIVES_TOO:
                shutil.rmtree(os.path.join(drives[i - 1], BUCKET, key))
                removed += 1
        check(removed == 2 * len(sizes),
              f"removed {removed} shard dirs, expected {2 * len(sizes)}")
        before = c.admin("GET", "kernel-stats").get("reconstruct", {})
        get_all(c, list(sizes), "degraded")
        recon = c.admin("GET", "kernel-stats")["reconstruct"]
        seen = recon["patterns_seen"] - before.get("patterns_seen", 0)
        check(seen >= 2, f"two lost pairs gave {seen} loss pattern(s)")
        say(event="degraded", lost_drives=[list(LOST_DRIVES),
                                           list(LOST_DRIVES_TOO)],
            shard_dirs_removed=removed, degraded_reads="identical",
            reconstruct=recon)

        healed = c.admin("POST", "heal", bucket=BUCKET, object="multi-tail")
        for i in LOST_DRIVES:
            check(
                os.path.isdir(
                    os.path.join(drives[i - 1], BUCKET, "multi-tail")
                ),
                f"heal left drive{i} without multi-tail",
            )
        get_all(c, ["multi-tail"], "healed")
        say(event="healed", object="multi-tail", result=healed,
            healed_read="identical")

        stats1 = c.admin("GET", "kernel-stats")
        health = c.admin("GET", "healthinfo")
        dev = health["nodes"][0]["device"]
        check(dev["platform"] == env["platform"],
              f"healthinfo names platform {dev['platform']!r}")
        check(dev["device_count"] == env["device_count"],
              "healthinfo device count differs from the codec child's")
        pallas = stats1["pallas_passes"]
        if not rehearse:
            check(pallas.get("encode_words_fused1", 0) > 0
                  or pallas.get("mesh_encode_hash", 0) > 0,
                  f"no Pallas-compiled encode pass: {stats1['device_passes']}")
            check(pallas.get("verify_and_reconstruct_words", 0) > 0
                  or pallas.get("mesh_verify_reconstruct", 0) > 0,
                  "no Pallas-compiled verify+reconstruct pass: "
                  f"{stats1['device_passes']}")
            idle = [d["id"] for d in dev["devices"]
                    if not d["peak_bytes_in_use"]]
            check(not idle, f"no codec work reached devices {idle}")
        say(event="server-stats", server=1,
            device_passes=stats1["device_passes"], pallas_passes=pallas,
            portable_passes=stats1["portable_passes"],
            h2d=stats1["h2d"], d2h=stats1["d2h"], batch=stats1["batch"],
            placement=stats1["placement"], memory=dev["devices"],
            compile_cache=dev["compile_cache"])
        srv.stop()
        errors1, requests1 = c.server_errors, c.requests
    finally:
        srv.kill()

    # ---- second server, same drives: durability and the compile cache ---
    srv = Server(workdir, drives, "server2", rehearse)
    try:
        boot2 = srv.wait_ready()
        c = Counted(srv.endpoint)
        t0 = time.monotonic()
        r = c.request("GET", f"/{BUCKET}/big-00")
        first_get2 = time.monotonic() - t0
        check(r.status == 200 and r.body == payload(seed, "big-00", BLOCK),
              "after restart: GET big-00 differs from its PUT payload")
        get_all(c, list(sizes), "after restart")
        t0 = time.monotonic()
        r = c.request("PUT", f"/{BUCKET}/again",
                      body=payload(seed, "again", BLOCK))
        first_put2 = time.monotonic() - t0
        check(r.status == 200, f"PUT after restart -> {r.status}")
        stats2 = c.admin("GET", "kernel-stats")
        cc1 = stats1["device"]["compile_cache"]
        cc2 = stats2["device"]["compile_cache"]
        check(cc2["dir"] == env["cache_dir"],
              f"server caches in {cc2['dir']}, codec child in "
              f"{env['cache_dir']}")
        check(cc2["hits"] > 0, f"second start saw no compile-cache hit: {cc2}")
        say(event="restart", server=2, boot_seconds=round(boot2, 2),
            reads_after_restart="identical",
            first_get_seconds={"first": round(first_get1, 3),
                               "second": round(first_get2, 3)},
            first_put_seconds={"first": round(first_put1, 3),
                               "second": round(first_put2, 3)},
            compile_cache={"first": cc1, "second": cc2})
        srv.stop()
        errors = errors1 + c.server_errors
        check(not errors, f"{len(errors)} responses >= 500: {errors[:3]}")
        summary.update(
            requests=requests1 + c.requests,
            responses_5xx=0,
            objects=len(sizes),
            bytes_loaded=sum(sizes.values()) + BLOCK,
            reads={"healthy": "identical", "degraded": "identical",
                   "healed": "identical", "after_restart": "identical"},
            pallas_passes=stats1["pallas_passes"],
            compile_cache={"first": cc1, "second": cc2},
        )
    finally:
        srv.kill()
    return summary


def result_line(env: dict) -> str:
    """The last stdout line of a run that passed: these keys and no
    others, the device as JAX names it."""
    return json.dumps({
        "ok": True,
        "device": {"platform": env["platform"], "kind": env["device_kind"],
                   "count": env["device_count"]},
    })


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox rehearsal over XLA:CPU at a cut size; "
                    "says platform=cpu on every line, proves nothing "
                    "about a chip")
    ap.add_argument("--child", choices=["codec"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "codec":
        try:
            return codec_child(args.seed, args.rehearse_cpu)
        except SmokeFailure as e:
            print(f"chip_smoke: codec: {e}", file=sys.stderr)
            return 1

    if os.environ.get("MINIO_TPU_CODEC_INTERPRET"):
        print("chip_smoke: MINIO_TPU_CODEC_INTERPRET is set; an interpreted "
              "kernel proves nothing about the chip", file=sys.stderr)
        return 2
    if not os.path.isfile(
        os.path.join(HERE, "minio_tpu", "server", "__main__.py")
    ):
        print(f"chip_smoke: no minio_tpu package next to {__file__}; run it "
              "from the root of a checkout", file=sys.stderr)
        return 2

    t_start = time.monotonic()
    child_env = dict(os.environ, PYTHONPATH=HERE)
    if args.rehearse_cpu:
        child_env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--child", "codec",
           "--seed", str(args.seed)]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    # 1. the codec child owns the chip, reports, and exits
    proc = subprocess.Popen(cmd, cwd=HERE, env=child_env,
                            stdout=subprocess.PIPE, text=True)
    env: dict = {}
    codec_done: dict = {}
    try:
        for line in proc.stdout:
            print(line, end="", flush=True)  # pass the child's report on
            if not line.startswith("{"):
                continue  # something a library wrote to stdout
            doc = json.loads(line)
            if not env:
                env = {k: doc[k] for k in (
                    "platform", "device_kind", "device_count", "jax",
                    "jaxlib", "libtpu", "cache_dir")}
            if doc.get("event") == "done":
                codec_done = doc
        code = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if code != 0 or not codec_done:
        print(f"chip_smoke: codec child failed (exit {code})",
              file=sys.stderr)
        return code or 1

    def say(**fields) -> None:
        print(json.dumps({"phase": "served", **env, **fields}), flush=True)

    # 2. + 3. only now may another process take the chip
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        served = served_phases(args.seed, args.rehearse_cpu, env, workdir,
                               say)
    except SmokeFailure as e:
        print(f"chip_smoke: served path: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    say(
        event="summary",
        rehearsal=args.rehearse_cpu,
        seconds=round(time.monotonic() - t_start, 1),
        codec={k: codec_done[k] for k in (
            "device_passes", "pallas_passes", "portable_passes", "h2d",
            "d2h", "memory")},
        served=served,
        claim=None,
    )
    if args.rehearse_cpu:
        return 0  # a rehearsal prints no result line: it saw no chip
    print(result_line(env), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
