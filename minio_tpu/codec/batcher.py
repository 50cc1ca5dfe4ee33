"""Cross-request codec batching (SURVEY.md section 7 stage 8).

Concurrent PutObject/GetObject requests each produce small codec calls
(a few blocks per pass).  Launched independently they serialize on the
device and pay per-launch overhead; the reference's analogue is the
per-disk goroutine fan-out feeding one disk queue
(cmd/erasure-encode.go:39-70).  Here ALL requests feed one device queue:

* client threads submit jobs (encode / digest / reconstruct) and block;
* a single dispatcher thread coalesces jobs with identical geometry
  into one batched device call, then scatters results back;
* a batch is flushed as soon as every currently-active client has
  submitted (nobody left to wait for), or when ``deadline_s`` expires -
  so a lone stream pays ~zero extra latency while 8 concurrent streams
  coalesce into one launch (the "dynamic batch deadlines" risk note in
  SURVEY.md section 7).

Correctness is trivial: the grouped call is the same math on a
concatenated batch axis, and results are split back by row counts.
"""

from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

from ..utils import spans
from .backend import CodecBackend, at_width, stripe_lengths
from .telemetry import KERNEL_STATS


class _Job:
    __slots__ = (
        "op", "key", "arrays", "lengths", "width", "result", "error",
        "done", "created_ns", "client", "ended", "ctx",
    )

    def __init__(
        self, op: str, key: tuple, arrays: tuple, lengths, width: int
    ):
        self.op = op
        self.key = key
        self.arrays = arrays  # rows at the key's staged width
        self.lengths = lengths  # int32[B]: each stripe's true bytes
        self.width = width  # of the caller's rows: results go back at it
        self.result = None
        self.error: "BaseException | None" = None
        self.done = threading.Event()
        self.created_ns = spans.now()
        # the submitter's request, restored on the thread that flushes
        self.ctx = spans.capture()
        self.client = threading.get_ident()
        # set by the first encode_end: a second end of the same handle
        # (error-path cleanup racing the normal consume) must not
        # decrement _active again — that corrupts the distinct-client
        # flush signal for every later batch
        self.ended = False


class _SlicedParityRef:
    """View of a coalesced batch's parity ref: drain pulls the PARENT
    (one shared D2H for the whole merged flush) and hands back this
    job's rows at its width.  release is a no-op — sibling jobs may
    still need the parent, which stays governed by the write-back cache
    either way."""

    __slots__ = ("_parent", "_lo", "_hi", "_width")

    def __init__(self, parent, lo: int, hi: int, width: int):
        self._parent = parent
        self._lo = lo
        self._hi = hi
        self._width = width

    @property
    def nbytes(self) -> int:
        return 0  # the parent ref carries the cache accounting

    def drain(self):
        return self._parent.drain()[self._lo : self._hi, :, : self._width]

    def release(self) -> None:
        return None


class _SubmeshWorker(threading.Thread):
    """One daemon worker per routed submesh: runs merged groups with the
    mesh scoped to that submesh's devices (parallel.rules.placed), so
    two independent batches on disjoint submeshes overlap instead of
    serializing on the dispatcher thread."""

    def __init__(self, backend: "BatchingBackend", router, sub):
        super().__init__(name=f"codec-batcher-{sub.name}", daemon=True)
        self.backend = backend
        self.router = router
        self.sub = sub
        self.q: "queue.SimpleQueue" = queue.SimpleQueue()
        self.start()

    def submit(self, item) -> None:
        self.q.put(item)

    def stop(self) -> None:
        self.q.put(None)

    def run(self) -> None:
        from ..parallel import rules as prules

        while True:
            item = self.q.get()
            if item is None:
                return
            op, key, group, flushed_ns = item
            try:
                with prules.placed(self.sub.devices), spans.adopt(
                    [j.ctx for j in group]
                ):
                    self.backend._run_group_traced(
                        op, key, group, flushed_ns
                    )
            finally:
                self.router.release(self.sub)
                KERNEL_STATS.record_submesh_depths(self.router.depths())


class BatchingBackend(CodecBackend):
    """Wrap any CodecBackend with cross-request batch coalescing."""

    name = "batched"

    # ops the "auto" placement policy may route to a submesh (the
    # PUT-side throughput plane; see _dispatch_group)
    _ROUTED_AUTO_OPS = frozenset({"encode", "encode_digest"})

    def __init__(
        self,
        inner: CodecBackend,
        deadline_s: float = 0.004,
        max_batch_blocks: int = 256,
    ):
        self.inner = inner
        self.deadline_s = deadline_s
        self.max_batch_blocks = max_batch_blocks
        self._cv = threading.Condition()
        self._jobs: list[_Job] = []
        # client threads currently inside a codec call (submitted or
        # about to): thread ident -> outstanding call/handle count.
        # Distinct CLIENTS is the flush signal — a pipelined stream
        # holding an un-ended handle while submitting its next batch is
        # still one client, not two (counting raw handles makes the
        # "everyone submitted" fast path unreachable and every flush
        # waits out the full deadline)
        self._active: "dict[int, int]" = {}
        # submesh placement: feature-detected once from the inner
        # backend (host backends return None -> pure inline dispatch)
        self._router_known = False
        self._router_obj = None
        self._workers: "dict[str, _SubmeshWorker]" = {}
        self._workers_mu = threading.Lock()
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="codec-batcher", daemon=True
        )
        self._thread.start()

    # -- client side ------------------------------------------------------

    def _enter(self, client: int) -> None:
        """cv held: one more outstanding call/handle for ``client``."""
        self._active[client] = self._active.get(client, 0) + 1

    def _exit(self, client: int) -> None:
        """cv held: drop one outstanding call/handle for ``client``."""
        left = self._active.get(client, 0) - 1
        if left <= 0:
            self._active.pop(client, None)
        else:
            self._active[client] = left

    def stage_width(self, nbytes: int) -> int:
        return self.inner.stage_width(nbytes)

    def _job(self, op: str, arr, lengths, key_of) -> _Job:
        """A job on the rung of its rows' width, so that rows of
        different true lengths coalesce: the stream stages there itself
        (stage_width); another caller's rows are copied there now, on
        the caller's thread.  ``key_of(width)`` is the coalescing key."""
        arr = np.ascontiguousarray(arr, dtype=np.uint8)
        L = arr.shape[-1]
        lens = stripe_lengths(arr, lengths)
        width = self.inner.stage_width(L)
        return _Job(op, key_of(width), (at_width(arr, width),), lens, L)

    def _submit(self, job: _Job):
        with self._cv:
            self._jobs.append(job)
            self._cv.notify_all()
        with spans.span(spans.BATCH_RESULT_WAIT):
            job.done.wait()
        if job.error is not None:
            raise job.error
        return job.result

    def encode(self, data, parity_shards, lengths=None):
        return self.encode_end(
            self.encode_begin(data, parity_shards, lengths)
        )

    def encode_begin(self, data, parity_shards, lengths=None):
        """Non-blocking submit: the job coalesces and runs on the
        dispatcher while the caller flushes its PREVIOUS batch; the
        handle resolves in encode_end (double-buffered PUT pipeline).

        The handle counts toward _active until encode_end so that
        concurrent pipelined streams still coalesce; encode_end's
        decrement NOTIFIES the dispatcher, which then flushes as soon
        as every remaining active client has submitted instead of
        sleeping out the coalesce deadline."""
        k = data.shape[1]
        job = self._job(
            "encode", data, lengths, lambda w: (k, w, parity_shards)
        )
        with self._cv:
            self._enter(job.client)
            self._jobs.append(job)
            self._cv.notify_all()
        return job

    def encode_end(self, handle):
        job = handle
        if not job.done.is_set():
            with spans.span(spans.BATCH_RESULT_WAIT):
                job.done.wait()
        with self._cv:
            # pair with the SUBMITTING thread's entry exactly once: a
            # pipelined caller may end a handle from a different
            # thread, and error-path cleanup may end it a second time
            if not job.ended:
                job.ended = True
                self._exit(job.client)
                self._cv.notify_all()
        if job.error is not None:
            raise job.error
        return job.result

    def encode_digest_begin(self, data, parity_shards, lengths=None):
        """Digest-only twin of encode_begin: coalesces across requests
        like encode, and admission BACKS OFF while the inner backend's
        parity cache is over budget — the flush policy's cache-pressure
        term, bounding device-resident parity under concurrency."""
        self._cache_backoff()
        k = data.shape[1]
        job = self._job(
            "encode_digest", data, lengths, lambda w: (k, w, parity_shards)
        )
        with self._cv:
            self._enter(job.client)
            self._jobs.append(job)
            self._cv.notify_all()
        return job

    def encode_digest_end(self, handle):
        # same handle protocol as encode_end (idempotent, _exit once);
        # the result is (digests, parity_ref) instead of (parity, digests)
        return self.encode_end(handle)

    def parity_cache_pressure(self) -> float:
        return self.inner.parity_cache_pressure()

    def _cache_backoff(self, bound_s: float = 0.25) -> None:
        """Stall new digest-encode admission briefly while the parity
        cache is at/over budget, so lazy drains catch up instead of
        every insert forcing a synchronous write-back eviction.  Time-
        bounded: a wedged drain band degrades to eviction, not a hang."""
        if self.inner.parity_cache_pressure() < 1.0:
            return
        deadline = time.monotonic() + bound_s
        while (
            self.inner.parity_cache_pressure() >= 1.0
            and time.monotonic() < deadline
        ):
            time.sleep(0.002)

    def digest(self, shards, lengths=None):
        n = shards.shape[1]
        job = self._job("digest", shards, lengths, lambda w: (n, w))
        client = threading.get_ident()
        with self._cv:
            self._enter(client)
        try:
            return self._submit(job)
        finally:
            with self._cv:
                self._exit(client)
                self._cv.notify_all()

    def reconstruct(self, shards, present, data_shards, parity_shards):
        n = shards.shape[1]
        # jobs coalesce by what they decode FROM: the first k present
        # rows (a hedged read may hold a ninth, which no decode uses)
        first_k = np.flatnonzero(np.asarray(present, dtype=bool))
        pres = np.zeros(n, dtype=bool)
        pres[first_k[:data_shards]] = True
        pat = tuple(pres.tolist())
        job = self._job(
            "reconstruct", shards, None,
            lambda w: (n, w, pat, data_shards, parity_shards),
        )
        client = threading.get_ident()
        with self._cv:
            self._enter(client)
        try:
            return self._submit(job)
        finally:
            with self._cv:
                self._exit(client)
                self._cv.notify_all()

    @property
    def fused_encode(self):  # type: ignore[override]
        return getattr(self.inner, "fused_encode", False)

    def reconstruct_and_verify(
        self, shards, digests, present, data_shards, parity_shards,
        lengths=None,
    ):
        # straight delegation, no coalescing: this op serves heal and
        # degraded reads - rare, latency-insensitive, and keyed by a
        # per-call digest array that would defeat batch merging anyway.
        # The default composition would route through self.verify/
        # self.reconstruct and lose the inner fused pass.
        return self.inner.reconstruct_and_verify(
            shards, digests, present, data_shards, parity_shards, lengths
        )

    def placement_router(self):
        return getattr(self.inner, "placement_router", lambda: None)()

    def shutdown(self) -> None:
        with self._cv:
            self._running = False
            self._cv.notify_all()
        self._thread.join(timeout=2)
        with self._workers_mu:
            workers, self._workers = dict(self._workers), {}
        for w in workers.values():
            w.stop()
        for w in workers.values():
            w.join(timeout=2)

    # -- dispatcher -------------------------------------------------------

    def _collect(self) -> "list[_Job]":
        """Take a coalescible batch off the queue (holds no deadline
        when every active client has already submitted)."""
        with self._cv:
            while self._running and not self._jobs:
                self._cv.wait(0.1)
            if not self._running and not self._jobs:
                return []
            deadline = time.monotonic() + self.deadline_s
            while True:
                # flush when nobody else could still contribute, when
                # the batch is big enough, or at the deadline.  The
                # contribution test compares DISTINCT clients: every
                # queued job's submitter is guaranteed active, so the
                # batch is complete exactly when each active client
                # has at least one job queued (a client pipelining two
                # begins is one contributor, not two)
                if (
                    len({j.client for j in self._jobs})
                    >= len(self._active)
                ):
                    break
                if (
                    sum(j.arrays[0].shape[0] for j in self._jobs)
                    >= self.max_batch_blocks
                ):
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            jobs, self._jobs = self._jobs, []
            return jobs

    def _loop(self) -> None:
        while True:
            jobs = self._collect()
            if not jobs:
                if not self._running:
                    return
                continue
            # one clock reading closes every job's queue wait, feeds the
            # old wait sum and opens the flush
            flushed_ns = spans.now()
            rows = sum(j.arrays[0].shape[0] for j in jobs)
            waited_ns = 0
            for j in jobs:
                with spans.adopt(j.ctx):
                    spans.wait(
                        spans.BATCH_QUEUE_WAIT, j.created_ns, flushed_ns
                    )
                waited_ns += flushed_ns - j.created_ns
            KERNEL_STATS.record_batch_flush(len(jobs), rows, waited_ns / 1e9)
            groups: dict[tuple, list[_Job]] = {}
            for j in jobs:
                groups.setdefault((j.op, j.key), []).append(j)
            ctxs = [j.ctx for j in jobs]
            with spans.adopt(ctxs), spans.span(
                spans.BATCH_FLUSH,
                requests=len({c[0] for c in ctxs if c is not None}),
                jobs=len(jobs), rows=rows,
            ):
                for (op, key), group in groups.items():
                    self._dispatch_group(op, key, group, flushed_ns)

    def _router(self):
        """The inner backend's submesh router, feature-detected once."""
        if not self._router_known:
            fn = getattr(self.inner, "placement_router", None)
            self._router_obj = fn() if callable(fn) else None
            self._router_known = True
        return self._router_obj

    def _dispatch_group(
        self, op: str, key: tuple, group: "list[_Job]", flushed_ns: int
    ) -> None:
        """Place one merged group: on the least-loaded submesh (its
        worker thread, overlapping with other submeshes) or inline on
        the dispatcher spanning the full mesh."""
        router = self._router()
        sub = None
        if router is not None:
            # under "auto", only the PUT-side throughput ops are
            # routed: reconstruct/digest serve degraded reads and
            # verify, where a routed submesh's cold single-device
            # compile would be charged to a latency-sensitive GET (an
            # explicit "route" policy still routes everything)
            routable = (
                router.policy == "route" or op in self._ROUTED_AUTO_OPS
            )
            if routable:
                blocks = sum(j.arrays[0].shape[0] for j in group)
                sub = router.route(blocks)
        if sub is None:
            KERNEL_STATS.record_placement("span")
            self._run_group_traced(op, key, group, flushed_ns)
            return
        KERNEL_STATS.record_placement("route")
        KERNEL_STATS.record_submesh_depths(router.depths())
        self._worker(router, sub).submit((op, key, group, flushed_ns))

    def _worker(self, router, sub) -> _SubmeshWorker:
        with self._workers_mu:
            w = self._workers.get(sub.name)
            if w is None:
                w = _SubmeshWorker(self, router, sub)
                self._workers[sub.name] = w
            return w

    def _run_group_traced(
        self, op: str, key: tuple, group: "list[_Job]", flushed_ns: int
    ) -> None:
        """Run one group on whichever thread placement chose.  The
        flush's stamp goes down with it: the seam closes
        ``flush_to_launch`` at its first jitted call, so grouping, concat,
        pad, bytes -> words and device_put all lie inside."""
        spans.hand_over(spans.FLUSH_TO_LAUNCH, flushed_ns)
        try:
            self._run_group_safe(op, key, group)
        finally:
            spans.drop_handoff()  # a host backend never launches

    def _run_group_safe(
        self, op: str, key: tuple, group: "list[_Job]"
    ) -> None:
        try:
            self._run_group(op, key, group)
        except BaseException as e:  # noqa: BLE001
            for j in group:
                if not j.done.is_set():  # an earlier piece's are served
                    j.error = e
                    j.done.set()

    def _run_group(self, op: str, key: tuple, group: "list[_Job]") -> None:
        """Fulfil the jobs of one group.  Every job lies at the key's
        staged width, whatever the true lengths of its rows: they travel
        beside it.  The seam cuts a call into launches and pads each to
        its ladders (codec.backend), so nothing is padded here.  The
        read side's jobs merge into one call; an encode's go down in
        pieces (``_encode_pieces``), each begun before the one before
        it is ended: the device works on a piece while the digests of
        the last are read back and its jobs' streams go on, and holds
        two pieces at most."""
        if op in ("encode", "encode_digest"):
            pieces = self._encode_pieces(key, group)
            begin = getattr(self.inner, op + "_begin")
            end = getattr(self.inner, op + "_end")
            ahead = None
            for piece in pieces:
                arr, lengths = self._merged(piece)
                handle = begin(arr, key[2], lengths)
                if ahead is not None:
                    self._fulfil(op, ahead[0], end(ahead[1]))
                ahead = (piece, handle)
            self._fulfil(op, ahead[0], end(ahead[1]))
            return
        arr, lengths = self._merged(group)
        if op == "digest":
            out = self.inner.digest(arr, lengths)
        elif op == "reconstruct":
            n, L, present, k, m = key
            out = self.inner.reconstruct(arr, present, k, m)
        else:
            raise ValueError(f"unknown op {op}")
        self._fulfil(op, group, out)

    def _encode_pieces(
        self, key: tuple, group: "list[_Job]"
    ) -> "list[list[_Job]]":
        """Cut an encode group into seam calls of whole jobs.  Where the
        backend launches at most so many stripes at once, no array
        larger than a launch is built: a job of that many stripes or
        more (a stream's batch of full blocks) goes down alone, as it
        lies - the seam launches views of it - and the smaller ones are
        copied together a launch at a time.  A backend without a cap
        (the host codec's one native call, a mesh) takes the group
        merged."""
        k, width, _ = key
        cap = self.inner.encode_stripes(k * width)
        if cap is None or len(group) == 1:
            return [group]
        pieces, small, held = [], [], 0
        for j in group:
            stripes = j.arrays[0].shape[0]
            if stripes >= cap:
                pieces.append([j])
                continue
            if held + stripes > cap:
                pieces.append(small)
                small, held = [], 0
            small.append(j)
            held += stripes
        if small:
            pieces.append(small)
        return pieces

    @staticmethod
    def _merged(jobs: "list[_Job]"):
        """(rows, lengths) of one seam call: a lone job's array as it
        lies, several copied together."""
        if len(jobs) == 1:
            return jobs[0].arrays[0], jobs[0].lengths
        return (
            np.concatenate([j.arrays[0] for j in jobs], axis=0),
            np.concatenate([j.lengths for j in jobs]),
        )

    def _fulfil(self, op: str, jobs: "list[_Job]", out) -> None:
        """Split one call's result along the batch axis, a job its rows."""
        lo = 0
        for j in jobs:
            arr = j.arrays[0]
            hi = lo + arr.shape[0]
            if len(jobs) == 1 and j.width == arr.shape[-1]:
                j.result = out  # staged by its caller: the call's own result
            else:
                j.result = self._rows_of(op, out, lo, hi, j)
            j.done.set()
            lo = hi

    @staticmethod
    def _rows_of(op: str, out, lo: int, hi: int, j: _Job):
        """Job ``j``'s rows [lo, hi) of a flush's result, at the width
        its caller's rows came in."""
        if op == "encode":
            parity, digests = out
            return parity[lo:hi, :, : j.width], digests[lo:hi]
        if op == "encode_digest":
            digests, pref = out
            return digests[lo:hi], _SlicedParityRef(pref, lo, hi, j.width)
        if op == "reconstruct":
            return out[lo:hi, :, : j.width]
        return out[lo:hi]


def maybe_wrap(backend: CodecBackend) -> CodecBackend:
    """Apply batching per MINIO_CODEC_BATCH (default on; "0"/"off"
    disable - the admin config seam writes on/off)."""
    if os.environ.get("MINIO_CODEC_BATCH", "on").lower() in ("0", "off"):
        return backend
    deadline_ms = 4.0
    try:
        deadline_ms = float(
            os.environ.get("MINIO_CODEC_BATCH_DEADLINE_MS") or 4.0
        )
    except ValueError:
        pass
    return BatchingBackend(backend, deadline_s=deadline_ms / 1e3)
