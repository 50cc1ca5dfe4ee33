"""Codec kernel telemetry: what the fused device passes actually did.

The paper's thesis lives in the byte-crunching hot paths (RS erasure,
bitrot hashing) running as batched device passes behind the
``reedsolomon.Encoder``-shaped seam (backend.py).  This module measures
those passes in production:

* ``KernelStats`` - process-wide registry of per-op counters: calls,
  bytes processed, and the seconds the calling thread spent inside the
  seam, labeled by the resolved backend (``tpu``/``cpu``); plus batcher
  occupancy (jobs coalesced per flush, queue wait) and erasure-stream
  totals.  Its snapshot also carries the tables of utils/spans.py:
  ``spans`` and ``probe``, the always-on counters merged over the
  threads; ``requests`` (self time by S3 verb on the request's own
  thread: adds up to the root's wall) and ``fanout`` (a wait for drive
  jobs that ran abreast, by the one job that ended it), kept always, a
  dict update a span and an add a wait; ``cpu`` (the process's CPU by
  thread role) and ``loops`` (requests and handler-queue wait by event
  loop), which cost the hot path nothing: whoever asks for a snapshot
  pays for them (one clock reading a Python thread, no file).  CPU by
  role comes from the scheduler's books because a clock reading a span
  is a system call under the GIL (utils/spans.py) and sees only threads
  that open spans - not the loops' wire work, the runtime's own pools.
* ``InstrumentedBackend`` - a CodecBackend decorator recording every
  encode / encode_begin-end / digest / reconstruct /
  reconstruct_and_verify through the seam.
  It wraps the CONCRETE backend (below the batching layer), so a
  coalesced flush counts as one call and queue wait stays out of it -
  queue wait is the batcher's own series.

``ops.seconds`` is what the calling thread's clock saw between entering
the codec call and leaving it (for the async begin/end pair, dispatch
time plus materialization time).  On a host-only backend that is compute
time.  On a device backend it is NOT device time: it holds staging,
dispatch, the kernel, the read-back and every wait for the GIL in
between, in one sum (5.8 ms a call against 0.3-0.8 ms of kernel on a
v5e).  The four seam spans of utils/spans.py split it: ``seam_stage``
(bytes -> words, pad, device_put), ``seam_launch`` (the jitted call,
returns at enqueue), ``seam_kernel_wait`` (block_until_ready on the
results) and ``seam_d2h`` (the copy to the host alone).

Everything is exported as ``miniotpu_codec_*`` Prometheus families
(server/metrics.py) and snapshot-dumpable via ``admin kernel-stats``
(server/admin.py).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..utils import spans
from .backend import CodecBackend


class KernelStats:
    """Thread-safe registry of codec hot-path counters."""

    def __init__(self):
        self._mu = threading.Lock()
        # (op, backend) -> [calls, bytes, seconds]
        self._ops: "dict[tuple[str, str], list]" = {}
        # batcher occupancy: flushes, jobs, blocks, queue-wait seconds
        self._batch = [0, 0, 0, 0.0]
        # erasure-layer streams: kind -> [streams, bytes]
        self._streams: "dict[str, list]" = {}
        self._heal_required = 0
        # per-stream stage breakdown: (op, stage) -> [streams, seconds]
        # op in {"put","get"}, stage in {"assemble","codec",
        # "codec_fused","disk"} - codec_fused is encode time on a
        # backend whose parity+digest pass is fused (erasure._codec_stage)
        self._stages: "dict[tuple[str, str], list]" = {}
        # iopool fan-out plane: queue -> [jobs, bytes, busy_seconds]
        self._iopool: "dict[str, list]" = {}
        self._iopool_depth_hwm = 0
        self._iopool_slowest_s = 0.0
        # hedged shard reads: kind in {launched, won, wasted}; and
        # shard_reads, every GET shard read launched (hedges included),
        # so that launched / shard_reads is the hedged share
        self._hedge: "dict[str, int]" = {}
        # served reads decoded by reconstruct (erasure stream): calls,
        # healthy_calls (every drive of the set online when the read
        # began: a hedge or a demotion chose parity, no drive was lost),
        # rows_rebuilt / bytes_rebuilt (data rows not among the
        # survivors); and the seam's decode-plan table: hits, misses
        self._recon = {
            "calls": 0, "healthy_calls": 0, "rows_rebuilt": 0,
            "bytes_rebuilt": 0,
        }
        self._plan = {"hit": 0, "miss": 0}
        # device->host readback by plane: plane -> [transfers, bytes];
        # plane in {"data", "parity"} (digests ride the data plane).
        # The parity-plane PUT restructure exists to drive the parity
        # row of this table to the post-ack drain band only
        self._d2h: "dict[str, list]" = {}
        # host->device staging by plane (mirror of _d2h)
        self._h2d: "dict[str, list]" = {}
        # device-program launches by jitted entry point, and the subset
        # of them that ran a Pallas kernel (the rest ran the XLA form:
        # ragged widths, non-TPU platforms, XLA-only passes)
        self._passes: "dict[str, int]" = {}
        self._pallas_passes: "dict[str, int]" = {}
        # launches of the served entry points that take lengths
        # (encode_words_fused1, digest_words): real rows, the
        # callers' shard bytes, the bytes of the width they were staged
        # at, launches that held rows of more than one true length; and
        # the distinct true widths seen since boot and the rows launched
        # at each staged width (its keys are bounded by the width ladder)
        self._ragged = dict.fromkeys(
            ("launches", "rows", "true_bytes", "staged_bytes",
             "mixed_launches"), 0
        )
        self._widths_true: "set[int]" = set()
        self._staged_rows: "dict[int, int]" = {}
        # what a stream is made of, by direction (encode | decode):
        # streams that ended, the blocks and batches of blocks they
        # carried, and the batches in which a ragged tail block shared
        # the batch with full ones (one add a batch: erasure.py)
        self._stream = {
            d: dict.fromkeys(
                ("streams", "blocks", "batches", "tail_groups"), 0
            )
            for d in ("encode", "decode")
        }
        # launches of the three served entry points at the seam
        # (encode_words_fused1, digest_words, reconstruct_words_batch):
        # how many, the bytes of their inputs as staged, the seam calls
        # that went out as more than one, and launches by input bytes -
        # the two ladders bound its keys; the largest key is the largest
        # launch since boot, and a window's is the largest whose count
        # moved
        self._launch = dict.fromkeys(("count", "bytes", "split_calls"), 0)
        self._launch_sizes: "dict[int, int]" = {}
        # submesh placement: outcome ("span"|"route") -> batches, and
        # per-submesh in-flight depth (current + high-water mark)
        self._placement: "dict[str, int]" = {}
        self._submesh_depth: "dict[str, int]" = {}
        self._submesh_depth_hwm: "dict[str, int]" = {}

    # -- recording --------------------------------------------------------

    def record_op(
        self, op: str, backend: str, nbytes: int, seconds: float
    ) -> None:
        with self._mu:
            row = self._ops.setdefault((op, backend), [0, 0, 0.0])
            row[0] += 1
            row[1] += nbytes
            row[2] += seconds

    def record_batch_flush(
        self, jobs: int, blocks: int, wait_s: float
    ) -> None:
        with self._mu:
            self._batch[0] += 1
            self._batch[1] += jobs
            self._batch[2] += blocks
            self._batch[3] += wait_s

    def record_stream(self, kind: str, nbytes: int) -> None:
        with self._mu:
            row = self._streams.setdefault(kind, [0, 0])
            row[0] += 1
            row[1] += nbytes
            if kind in self._stream:  # heal streams are not batched here
                self._stream[kind]["streams"] += 1

    def record_stream_batch(
        self, direction: str, blocks: int, tail: bool = False
    ) -> None:
        """One batch of blocks of an erasure stream (direction = encode
        | decode); ``tail``: a ragged last block beside full ones."""
        with self._mu:
            row = self._stream[direction]
            row["blocks"] += blocks
            row["batches"] += 1
            row["tail_groups"] += bool(tail)

    def record_launches(self, sizes: "list[int]") -> None:
        """The launches one seam call of a served entry point went out
        as: the staged input bytes of each."""
        with self._mu:
            row = self._launch
            row["count"] += len(sizes)
            row["bytes"] += sum(sizes)
            row["split_calls"] += len(sizes) > 1
            for size in sizes:
                self._launch_sizes[size] = (
                    self._launch_sizes.get(size, 0) + 1
                )

    def record_heal_required(self) -> None:
        with self._mu:
            self._heal_required += 1

    def record_d2h(self, plane: str, nbytes: int) -> None:
        """One device->host codec transfer (plane = data|parity)."""
        with self._mu:
            row = self._d2h.setdefault(plane, [0, 0])
            row[0] += 1
            row[1] += nbytes

    def record_h2d(self, plane: str, nbytes: int) -> None:
        """One host->device codec staging transfer (plane = data|parity)."""
        with self._mu:
            row = self._h2d.setdefault(plane, [0, 0])
            row[0] += 1
            row[1] += nbytes

    def record_ragged(self, lengths, width: int) -> None:
        """One launch of a served entry point: the true bytes of its
        real rows (a padding row has length 0 and is left out: it is in
        ``h2d``) and the width they were staged at."""
        lengths = np.asarray(lengths)
        lengths = lengths[lengths > 0]
        distinct = set(np.unique(lengths).tolist())
        with self._mu:
            r = self._ragged
            r["launches"] += 1
            r["rows"] += int(lengths.size)
            r["true_bytes"] += int(lengths.sum())
            r["staged_bytes"] += int(lengths.size) * width
            r["mixed_launches"] += len(distinct) > 1
            self._widths_true |= distinct
            self._staged_rows[width] = (
                self._staged_rows.get(width, 0) + int(lengths.size)
            )

    def record_pass(self, kernel: str, pallas: bool = False) -> None:
        """One device-program launch (jitted codec pass) by entry-point
        name — backend.py records these at every launch site, and says
        whether the launch ran a Pallas kernel."""
        with self._mu:
            self._passes[kernel] = self._passes.get(kernel, 0) + 1
            if pallas:
                self._pallas_passes[kernel] = (
                    self._pallas_passes.get(kernel, 0) + 1
                )

    def record_stages(self, op: str, stages: "dict[str, float]") -> None:
        """One stream's stage breakdown (assemble / codec / disk)."""
        with self._mu:
            for stage, seconds in stages.items():
                row = self._stages.setdefault((op, stage), [0, 0.0])
                row[0] += 1
                row[1] += seconds

    def record_io_job(
        self, queue: str, nbytes: int, seconds: float, depth: int
    ) -> None:
        """One completed iopool job; ``depth`` is the queue's backlog
        at dequeue (the slowest-disk signal: a healthy disk drains to
        zero, a straggler's queue stays deep)."""
        with self._mu:
            row = self._iopool.setdefault(queue, [0, 0, 0.0])
            row[0] += 1
            row[1] += nbytes
            row[2] += seconds
            if depth > self._iopool_depth_hwm:
                self._iopool_depth_hwm = depth
            if seconds > self._iopool_slowest_s:
                self._iopool_slowest_s = seconds

    def record_hedge(self, kind: str, n: int = 1) -> None:
        """One hedged-read event: ``launched`` (duplicate read fired),
        ``won`` (the hedge produced intact shard cells), ``wasted``
        (abandoned without contributing); and ``shard_reads``, the
        shard reads a GET's block group launched, hedges among them
        (counted once a group, ``n`` at a time)."""
        with self._mu:
            self._hedge[kind] = self._hedge.get(kind, 0) + n

    def record_reconstruct(
        self, healthy: bool, rows_rebuilt: int, row_bytes: int
    ) -> None:
        """One served-read reconstruct call of the erasure stream."""
        with self._mu:
            r = self._recon
            r["calls"] += 1
            r["healthy_calls"] += bool(healthy)
            r["rows_rebuilt"] += rows_rebuilt
            r["bytes_rebuilt"] += rows_rebuilt * row_bytes

    def record_decode_plan(self, hit: bool) -> None:
        """One look-up of a loss pattern's survivors + matrix (seam)."""
        with self._mu:
            self._plan["hit" if hit else "miss"] += 1

    def record_placement(self, outcome: str) -> None:
        """One batch placement decision (outcome = span|route)."""
        with self._mu:
            self._placement[outcome] = self._placement.get(outcome, 0) + 1

    def record_submesh_depths(self, depths: "dict[str, int]") -> None:
        """Live per-submesh queue depths from the placement router."""
        with self._mu:
            for name, depth in depths.items():
                self._submesh_depth[name] = depth
                if depth > self._submesh_depth_hwm.get(name, 0):
                    self._submesh_depth_hwm[name] = depth

    # -- reading ----------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-friendly dump (admin kernel-stats)."""
        # the enqueue-side mark lives on the pool's queues (their own
        # lock is held there anyway); the dequeue-side one is ours
        from ..parallel import iopool

        enqueue_hwm = iopool.depth_hwm()
        # utils/spans.py: [{role, name, count, wall_seconds, cpu_seconds}],
        # the interpreter probe, and requests / fanout / cpu / loops;
        # merged outside our mutex
        span_tables = spans.snapshot()
        with self._mu:
            return {
                **span_tables,
                "ops": [
                    {
                        "op": op,
                        "backend": be,
                        "calls": calls,
                        "bytes": nbytes,
                        "seconds": round(secs, 6),
                    }
                    for (op, be), (calls, nbytes, secs) in sorted(
                        self._ops.items()
                    )
                ],
                "batch": {
                    "flushes": self._batch[0],
                    "jobs": self._batch[1],
                    "blocks": self._batch[2],
                    "wait_seconds": round(self._batch[3], 6),
                },
                "streams": [
                    {"kind": kind, "streams": n, "bytes": nbytes}
                    for kind, (n, nbytes) in sorted(
                        self._streams.items()
                    )
                ],
                "heal_required": self._heal_required,
                "d2h": [
                    {"plane": plane, "transfers": n, "bytes": nbytes}
                    for plane, (n, nbytes) in sorted(self._d2h.items())
                ],
                "h2d": [
                    {"plane": plane, "transfers": n, "bytes": nbytes}
                    for plane, (n, nbytes) in sorted(self._h2d.items())
                ],
                "device_passes": dict(sorted(self._passes.items())),
                "pallas_passes": dict(sorted(self._pallas_passes.items())),
                "portable_passes": {
                    kernel: n - self._pallas_passes.get(kernel, 0)
                    for kernel, n in sorted(self._passes.items())
                    if n > self._pallas_passes.get(kernel, 0)
                },
                "parity_cache": _parity_cache_stats(),
                "hedge": {
                    kind: self._hedge.get(kind, 0)
                    for kind in ("launched", "won", "wasted", "shard_reads")
                },
                "reconstruct": {
                    **self._recon,
                    "patterns_seen": _patterns_seen(),
                    "matrix_cache": dict(self._plan),
                },
                "ragged": {
                    **self._ragged,
                    "widths_true": len(self._widths_true),
                    "widths_staged": len(self._staged_rows),
                    # rows launched by staged width: a window's widths
                    # are those whose count moved between two snapshots
                    "staged_rows": {
                        str(w): n
                        for w, n in sorted(self._staged_rows.items())
                    },
                },
                "stream": {d: dict(row) for d, row in self._stream.items()},
                "launch": {
                    **self._launch,
                    "max_bytes": max(self._launch_sizes, default=0),
                    "sizes": {
                        str(size): n
                        for size, n in sorted(self._launch_sizes.items())
                    },
                },
                "breaker": _breaker_demotions(),
                "meta_read": _meta_read_counts(),
                "remove": _remove_counts(),
                "drive_write": _drive_write_counts(),
                "liveness": _liveness_counts(),
                "body_read": _body_read_counts(),
                "stages": [
                    {
                        "op": op,
                        "stage": stage,
                        "streams": n,
                        "seconds": round(secs, 6),
                    }
                    for (op, stage), (n, secs) in sorted(
                        self._stages.items()
                    )
                ],
                "placement": {
                    outcome: self._placement.get(outcome, 0)
                    for outcome in ("span", "route")
                },
                "submeshes": [
                    {
                        "submesh": name,
                        "depth": self._submesh_depth.get(name, 0),
                        "depth_hwm": hwm,
                    }
                    for name, hwm in sorted(
                        self._submesh_depth_hwm.items()
                    )
                ],
                "iopool": {
                    "queues": [
                        {
                            "queue": q,
                            "jobs": jobs,
                            "bytes": nbytes,
                            "busy_seconds": round(busy, 6),
                        }
                        for q, (jobs, nbytes, busy) in sorted(
                            self._iopool.items()
                        )
                    ],
                    "depth_hwm": max(self._iopool_depth_hwm, enqueue_hwm),
                    "slowest_job_seconds": round(
                        self._iopool_slowest_s, 6
                    ),
                },
            }

    def reset(self) -> None:
        with self._mu:
            self._ops.clear()
            self._batch = [0, 0, 0, 0.0]
            self._streams.clear()
            self._heal_required = 0
            self._stages.clear()
            self._iopool.clear()
            self._iopool_depth_hwm = 0
            self._iopool_slowest_s = 0.0
            self._hedge.clear()
            self._recon = dict.fromkeys(self._recon, 0)
            self._plan = {"hit": 0, "miss": 0}
            self._d2h.clear()
            self._h2d.clear()
            self._passes.clear()
            self._pallas_passes.clear()
            self._ragged = dict.fromkeys(self._ragged, 0)
            self._widths_true.clear()
            self._staged_rows.clear()
            for row in self._stream.values():
                row.update(dict.fromkeys(row, 0))
            self._launch = dict.fromkeys(self._launch, 0)
            self._launch_sizes.clear()
            self._placement.clear()
            self._submesh_depth.clear()
            self._submesh_depth_hwm.clear()
        if self is KERNEL_STATS:
            spans.reset()
            from ..storage import xl

            xl.META_READ[:] = [0, 0, 0]
            from ..storage import diskcheck

            diskcheck.LIVENESS[:] = [0, 0, 0]
            from ..server import aio

            aio.BODY_READ[:] = [0, 0, 0]


def _parity_cache_stats() -> dict:
    """Live occupancy of the device parity-plane cache (backend.py) —
    read at snapshot time, not accumulated here, because the cache is
    its own source of truth for current occupancy."""
    from . import backend as backend_mod

    return backend_mod.parity_cache_stats()


def _patterns_seen() -> int:
    from . import backend as backend_mod

    return backend_mod.patterns_seen()


def _breaker_demotions() -> dict:
    """Demotions of a drive's breaker by cause, summed over the drives
    (storage/health.py is the source of truth, read at snapshot time)."""
    from ..storage import health

    return health.registry().demotions()


def _meta_read_counts() -> dict:
    """The drives' small-file reads (storage/xl.py counts them where
    they are made): reads, refills, error_path."""
    from ..storage import xl

    return xl.meta_read_counts()


def _remove_counts() -> dict:
    """The drives' removals that were told what they remove
    (storage/xl.py counts them where they are made): named, walked,
    calls."""
    from ..storage import xl

    return xl.remove_counts()


def _drive_write_counts() -> dict:
    """The drives' writes and shard opens (storage/xl.py counts them
    where they are made): calls, and how many of them had to ask."""
    from ..storage import xl

    return xl.drive_write_counts()


def _liveness_counts() -> dict:
    """The drives' liveness questions (storage/diskcheck.py counts them
    where they are asked): asked, looked, reset."""
    from ..storage import diskcheck

    return diskcheck.liveness_counts()


def _body_read_counts() -> dict:
    """Request bodies handed from the event loops to their handlers
    (server/aio.py counts them where they cross): handovers, bytes,
    loop_reads."""
    from ..server import aio

    return aio.body_read_counts()


# Process-wide singleton: one codec seam per process (backend.py caches
# one backend), so one registry; tests reset() it.
KERNEL_STATS = KernelStats()


def _true_bytes(arr, lengths) -> int:
    """The caller's bytes of a (B, rows, L) array: its rows at their
    true lengths where the caller staged them wider (``ops.bytes`` is
    what was asked for; ``ragged.staged_bytes`` what was launched)."""
    if lengths is None:
        return arr.nbytes
    return int(np.sum(lengths)) * arr.shape[1]


class InstrumentedBackend(CodecBackend):
    """CodecBackend decorator feeding a KernelStats registry.

    ``ops.seconds`` is the caller's clock round the whole call - staging,
    dispatch, kernel, read-back and GIL waits in one sum, not device
    time; the spans ``seam_stage`` / ``seam_launch`` /
    ``seam_kernel_wait`` / ``seam_d2h`` inside TpuBackend split it.
    ``name`` mirrors the inner backend so layers keying behavior off it
    (the batcher's power-of-two padding for ``tpu``) are unaffected.
    ``verify`` is inherited from CodecBackend on purpose: the default
    routes through ``self.digest`` and is therefore recorded.
    """

    def __init__(self, inner: CodecBackend, stats: "KernelStats | None" = None):
        self.inner = inner
        self.stats = stats if stats is not None else KERNEL_STATS
        self.name = getattr(inner, "name", "unknown")

    @property
    def fused_encode(self):  # type: ignore[override]
        # live delegation, not an __init__ snapshot: CpuBackend demotes
        # this when its native build fails mid-process
        return getattr(self.inner, "fused_encode", False)

    def _timed(self, op: str, nbytes: int, fn):
        t0 = time.monotonic()
        try:
            return fn()
        finally:
            self.stats.record_op(
                op, self.name, nbytes, time.monotonic() - t0
            )

    def stage_width(self, nbytes: int) -> int:
        return self.inner.stage_width(nbytes)

    def encode_stripes(self, stripe_bytes: int) -> "int | None":
        return self.inner.encode_stripes(stripe_bytes)

    def encode(self, data, parity_shards, lengths=None):
        return self._timed(
            "encode",
            _true_bytes(data, lengths),
            lambda: self.inner.encode(data, parity_shards, lengths),
        )

    def encode_begin(self, data, parity_shards, lengths=None):
        # async pair: dispatch time here, materialization time in
        # encode_end; recorded once, at end, as one encode call
        t0 = time.monotonic()
        handle = self.inner.encode_begin(data, parity_shards, lengths)
        return (
            "ktel", handle, time.monotonic() - t0,
            _true_bytes(data, lengths),
        )

    def encode_end(self, handle):
        if not (
            isinstance(handle, tuple)
            and len(handle) == 4
            and handle[0] == "ktel"
        ):
            return self.inner.encode_end(handle)
        _tag, inner_handle, dispatch_s, nbytes = handle
        t0 = time.monotonic()
        try:
            return self.inner.encode_end(inner_handle)
        finally:
            self.stats.record_op(
                "encode",
                self.name,
                nbytes,
                dispatch_s + (time.monotonic() - t0),
            )

    def encode_digest_begin(self, data, parity_shards, lengths=None):
        # digest-only twin of the encode pair: same one-call recording
        # at end, under the op name "encode_digest" so the readback
        # restructure shows up as its own series next to "encode"
        t0 = time.monotonic()
        handle = self.inner.encode_digest_begin(
            data, parity_shards, lengths
        )
        return (
            "ktel", handle, time.monotonic() - t0,
            _true_bytes(data, lengths),
        )

    def encode_digest_end(self, handle):
        if not (
            isinstance(handle, tuple)
            and len(handle) == 4
            and handle[0] == "ktel"
        ):
            return self.inner.encode_digest_end(handle)
        _tag, inner_handle, dispatch_s, nbytes = handle
        t0 = time.monotonic()
        try:
            return self.inner.encode_digest_end(inner_handle)
        finally:
            self.stats.record_op(
                "encode_digest",
                self.name,
                nbytes,
                dispatch_s + (time.monotonic() - t0),
            )

    def parity_cache_pressure(self) -> float:
        return self.inner.parity_cache_pressure()

    def placement_router(self):
        # explicit delegation (this wrapper has no __getattr__): the
        # batcher feature-detects the routing seam through it
        return self.inner.placement_router()

    def digest(self, shards, lengths=None):
        return self._timed(
            "digest",
            _true_bytes(shards, lengths),
            lambda: self.inner.digest(shards, lengths),
        )

    def reconstruct(self, shards, present, data_shards, parity_shards):
        return self._timed(
            "reconstruct",
            shards.nbytes,
            lambda: self.inner.reconstruct(
                shards, present, data_shards, parity_shards
            ),
        )

    def reconstruct_and_verify(
        self, shards, digests, present, data_shards, parity_shards,
        lengths=None,
    ):
        # explicit delegation: the CodecBackend default would compose
        # self.verify + self.reconstruct and silently bypass the
        # inner backend's fused single-pass implementation
        return self._timed(
            "reconstruct_and_verify",
            _true_bytes(shards, lengths),
            lambda: self.inner.reconstruct_and_verify(
                shards, digests, present, data_shards, parity_shards,
                lengths,
            ),
        )


def instrument(
    backend: CodecBackend, stats: "KernelStats | None" = None
) -> CodecBackend:
    """Wrap a concrete backend with kernel telemetry (idempotent)."""
    if isinstance(backend, InstrumentedBackend):
        return backend
    return InstrumentedBackend(backend, stats)
