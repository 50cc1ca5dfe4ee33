"""Codec backend seam: the reedsolomon.Encoder-shaped boundary.

The reference hides its codec behind reedsolomon.Encoder constructed at
cmd/erasure-coding.go:54-64; everything above (Erasure.Encode/Decode/Heal)
is codec-agnostic.  This module is that seam for the new framework:

    backend = get_backend()        # MINIO_ERASURE_BACKEND=tpu|cpu|auto

* TpuBackend: batched fused Pallas/JAX device passes (ops/codec_step).
* CpuBackend: native C++ AVX2 nibble-shuffle codec (native/csrc/gf_cpu.cc)
  + vectorized numpy phash256 - the klauspost/reedsolomon-equivalent host
  path; ``auto`` picks it where JAX reports no accelerator, and says so.

Both produce byte-identical parity and digests; shard files written by one
backend verify and decode under the other.
"""

from __future__ import annotations

import itertools
import os
import queue
import subprocess
import threading

import numpy as np

from ..ops import gf, hash as phash
from ..utils import spans
from ..utils.log import kv, logger

_log = logger("codec.backend")


def _record_d2h(plane: str, nbytes: int) -> None:
    """Account one device->host transfer (plane = data|parity).

    Lazy import: telemetry imports this module at load, so the reverse
    edge must resolve at call time.
    """
    from .telemetry import KERNEL_STATS

    KERNEL_STATS.record_d2h(plane, int(nbytes))


def _host_readback(array, plane: "str | None"):
    """The seam's one device->host read-back, split where the thread
    waits for two different things: ``seam_kernel_wait`` is
    ``block_until_ready`` on the result (the kernel, and the H2D before
    it), ``seam_d2h`` the copy to the host alone.  The same thread waits
    the same time as the bare ``np.asarray`` did.  ``plane`` (data |
    parity) accounts the bytes; None where the caller accounts several
    read-backs as one transfer."""
    ready = getattr(array, "block_until_ready", None)
    if ready is not None:  # a mesh path hands host arrays through
        with spans.span(spans.SEAM_KERNEL_WAIT):
            ready()
    with spans.span(spans.SEAM_D2H):
        host = np.asarray(array)
    if plane is not None:
        _record_d2h(plane, host.nbytes)
    return host


def _launch() -> "spans.span":
    """``with _launch():`` round a jitted call (it returns at enqueue):
    the ``seam_launch`` span.  The stamp the batcher left at its flush is
    closed here, so ``flush_to_launch`` ends where the launch begins."""
    spans.picked_up()
    return spans.span(spans.SEAM_LAUNCH)


def _record_pass(kernel: str, pallas: bool = False) -> None:
    """Account one device-program launch (jitted codec pass) by entry
    point name.  ``pallas`` says the launch ran a Mosaic-compiled (or,
    under MINIO_TPU_CODEC_INTERPRET, interpreted) Pallas kernel rather
    than the XLA formulation of the same math - counted apart so a
    tile-aligned batch that silently left the kernel shows up."""
    from .telemetry import KERNEL_STATS

    KERNEL_STATS.record_pass(kernel, pallas)


def _record_h2d(plane: str, nbytes: int) -> None:
    """Account one host->device codec staging transfer (plane =
    data|parity), the H2D twin of _record_d2h."""
    from .telemetry import KERNEL_STATS

    KERNEL_STATS.record_h2d(plane, int(nbytes))


def _record_ragged(lengths: np.ndarray, width: int) -> None:
    """Account one launch of a served entry point that takes lengths
    (encode_words_fused1, digest_words): its rows' true lengths (a
    padding row's is 0 and is left out) and the width they were staged
    at (kernel-stats ``ragged``)."""
    from .telemetry import KERNEL_STATS

    KERNEL_STATS.record_ragged(lengths, width)


def _record_launches(sizes: "list[int]") -> None:
    """Account the launches one call of a served entry point
    (encode_words_fused1, digest_words, reconstruct_words_batch) went
    out as, by the bytes of each one's staged input (kernel-stats
    ``launch``)."""
    from .telemetry import KERNEL_STATS

    KERNEL_STATS.record_launches(sizes)


# ---------------------------------------------------------------------------
# The loss pattern as operands: survivors + inverse, picked on the host
# ---------------------------------------------------------------------------
#
# Which k of n rows a read decodes from is known when the read ends (a
# lost drive, a hedge that won, a block that failed its digest).  The
# device programs take it as two small arrays, so the host's part is to
# pick the survivors and look up - or, the first time, invert - their
# matrix.  One table for the process: a pattern costs one Gauss-Jordan
# elimination over GF(2^8) once (a millisecond alone, ten on a busy
# interpreter: PERF.md, PR 27), a dict hit after.

_plans: "dict[tuple, tuple[np.ndarray, np.ndarray]]" = {}  # by mask
_patterns: "dict[tuple, tuple[np.ndarray, np.ndarray]]" = {}  # by survivors


def decode_plan(present, data_shards: int, parity_shards: int):
    """bool[n] availability -> (survivors int32[k], matrix uint8[k, k])
    for the decode programs: the first k present rows and the inverse of
    their generator rows.  ``seam_matrix`` spans it; kernel-stats counts
    ``reconstruct.matrix_cache`` hit/miss and ``patterns_seen``.  Raises
    ValueError below k present rows."""
    from ..ops import codec_step
    from .telemetry import KERNEL_STATS

    with spans.span(spans.SEAM_MATRIX):
        pres = np.asarray(present, dtype=bool)
        key = (data_shards, parity_shards, pres.tobytes())
        plan = _plans.get(key)
        hit = plan is not None
        if not hit:
            survivors, matrix = codec_step.host_pattern(
                pres, data_shards, parity_shards
            )
            # masks that differ only in rows past the first k decode
            # alike: one pattern, one pair of arrays
            plan = _patterns.setdefault(
                (data_shards, parity_shards, survivors.tobytes()),
                (survivors, matrix),
            )
            _plans[key] = plan
        KERNEL_STATS.record_decode_plan(hit)
    return plan


def patterns_seen() -> int:
    """Distinct survivor sets decoded from since boot (every geometry)."""
    return len(_patterns)


# ---------------------------------------------------------------------------
# The ladder: the leading dimension of a program is one of a few
# ---------------------------------------------------------------------------
#
# A jitted program is compiled per shape, about a second each on the one
# dispatcher thread.  Coalesced flushes, settle batches and the batches
# of a many-block stream come in any size, so the seam rounds the
# leading dimension (stripes of an encode or a reconstruct, shard rows
# of a digest) up a ladder with padding rows, and above
# ``LAUNCH_BYTES`` of input launches more than once.  The
# ladder: every size up to LADDER_UNIT (a healthy read settles its k
# shards in batches of 1 to k rows, so any traffic meets those sizes
# within seconds, and padding them would copy every batch on the one
# dispatcher thread: 2-5 % of `mixed-10m`'s rate, PERF.md PR 27), then
# powers of two (sizes only a coalesced flush reaches, met late or
# never, which is where a compile lands inside somebody's request).  At
# 10 MiB blocks of EC 8+4: digest 1-8 and 16 rows, reconstruct 1-2
# stripes.  Two entry points stop short of the powers of two, so that
# the warm-up loads every program they can launch (``_family``) and no
# flush, however many requests it coalesces and however many blocks a
# stream's batch holds, meets one that was not loaded behind the first:
# an encode keeps to 1, 2 and 4 stripes (``encode_rungs``: 1-2 at a
# full block's width), a digest to sixteen rows (``digest_rungs``; the
# tails of three 64 MiB GETs in one flush are 24 rows of 512 KiB - 16 +
# 8, not a 32-row program met once a day, inside somebody's window:
# PERF.md PR 33).  Padding rows are the seam's own cost: they cross the
# bus, count in h2d, and never reach a caller.

#
# The width is the other dimension.  A shard's byte length is an OPERAND
# of every codec program (one length a row), and the width a launch is
# staged at is whole Pallas tiles off a ladder of its own: every tile
# count up to WIDTH_UNIT, then steps of an eighth of the next power of
# two (10, 12, 14, 16, 20, ... 64, 80: a full 10 MiB block of EC 8+4 is
# 80 tiles, a rung, staged as it lies).  So the programs a geometry can
# ever trace are width rungs x row rungs however many object sizes an
# application PUTs, rows of different true lengths on one rung share a
# launch, the Pallas kernels take every width, and a row is staged at
# most a quarter wider than its tiles (the words past its length are
# padding: the hash masks them, Reed-Solomon of zero columns is zero).

LAUNCH_BYTES = 32 << 20
LADDER_CAP = 256  # the batcher's max_batch_blocks: no launch is longer
LADDER_UNIT = 8  # sizes up to here are their own rung
ENCODE_STRIPES = 4  # most stripes of one encode launch (a stream's batch)
TILE_BYTES = 16384  # of one shard row: ops/rs_pallas._TW uint32 words
WIDTH_UNIT = 8  # tile counts up to here are their own rung


def width_rung(nbytes: int) -> int:
    """The staged width, in bytes, of shard rows of ``nbytes``: the
    rung of the width ladder that holds them."""
    tiles = max(1, -(-int(nbytes) // TILE_BYTES))
    if tiles > WIDTH_UNIT:
        step = (1 << (tiles - 1).bit_length()) >> 3
        tiles = -(-tiles // step) * step
    return tiles * TILE_BYTES


def width_rungs(max_bytes: int) -> "list[int]":
    """Every rung up to the one that holds ``max_bytes``, ascending: the
    widths a geometry whose full shard is ``max_bytes`` can ever stage."""
    out, w = [], 0
    while w < max_bytes:
        w = width_rung(w + 1)
        out.append(w)
    return out


def at_width(arr: np.ndarray, width: int) -> np.ndarray:
    """``arr`` with its last axis ``width`` bytes wide.  The stream
    assembles at its backend's stage_width already and passes through;
    a caller that did not is copied into fresh zeros (not a thread's
    padding buffer: an encode still reads its input when begin
    returns)."""
    L = arr.shape[-1]
    if width == L:
        return arr
    wide = np.zeros(arr.shape[:-1] + (width,), dtype=arr.dtype)
    wide[..., :L] = arr
    return wide


def stripe_lengths(arr: np.ndarray, lengths) -> np.ndarray:
    """The true shard bytes of each stripe of a (B, rows, L) array as
    int32[B]: ``lengths`` where the caller staged wider than its rows,
    else L for all."""
    B, L = arr.shape[0], arr.shape[-1]
    if lengths is None:
        return np.full(B, L, dtype=np.int32)
    lens = np.asarray(lengths, dtype=np.int32).reshape(-1)
    if lens.shape != (B,) or (lens > L).any() or (lens < 0).any():
        raise ValueError(
            f"need {B} lengths of at most {L} bytes, got {lens.tolist()}"
        )
    return lens


def launch_rows(row_bytes: int) -> int:
    """Most rows of ``row_bytes`` one launch takes: the power of two
    that fits LAUNCH_BYTES, at least 1, at most LADDER_CAP."""
    fit = max(1, min(LADDER_CAP, LAUNCH_BYTES // max(1, row_bytes)))
    return 1 << (fit.bit_length() - 1)


def ladder(rows: int) -> int:
    """The rung that holds ``rows`` (>= 1): itself up to LADDER_UNIT, the
    next power of two above."""
    return rows if rows <= LADDER_UNIT else 1 << (rows - 1).bit_length()


def encode_rungs(stripe_bytes: int) -> "tuple[int, ...]":
    """The stripe counts an encode of ``stripe_bytes`` a stripe (k rows
    at their staged width) is launched at, ascending: the powers of two
    up to ENCODE_STRIPES that fit LAUNCH_BYTES."""
    cap = min(ENCODE_STRIPES, launch_rows(stripe_bytes))
    return tuple(1 << i for i in range(cap.bit_length()))


def encode_rung(stripes: int) -> int:
    """The rung that holds ``stripes`` (>= 1) of one encode launch."""
    return 1 << (stripes - 1).bit_length()


DIGEST_RUNGS = tuple(range(1, LADDER_UNIT + 1)) + (2 * LADDER_UNIT,)


def digest_rungs(row_bytes: int) -> "tuple[int, ...]":
    """The row counts a digest of rows of ``row_bytes`` (at their staged
    width) is launched at: every count a read settles on, and sixteen
    for a coalesced flush or a stream's batch of blocks, within
    LAUNCH_BYTES."""
    cap = launch_rows(row_bytes)
    return tuple(r for r in DIGEST_RUNGS if r <= cap)


def reconstruct_rungs(stripe_bytes: int) -> "tuple[int, ...]":
    """The stripe counts a reconstruct of ``stripe_bytes`` a stripe (n
    rows at their staged width) is launched at: the ladder up to what
    fits LAUNCH_BYTES."""
    cap = launch_rows(stripe_bytes)
    return tuple(sorted({ladder(r) for r in range(1, cap + 1)}))


def _join(parts: list) -> np.ndarray:
    """The host results of one encode call's launches, in order: one
    launch (a call within the cap) hands its buffer through as it is,
    several are copied together."""
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


_pad_buffers = threading.local()


def _pad_buffer(shape: tuple, dtype) -> np.ndarray:
    """This thread's staging array of a padded launch's shape, kept from
    one seam call to the next.  A fresh 10 MiB array per call is an
    mmap, a page fault a page and a munmap that stops every thread of
    the process for its TLB flush; the ladder makes the shapes few, so
    the arrays are too.  Safe to refill once the call that staged from
    it has read its result back (a seam call does before it returns);
    what the padding rows hold does not matter, their results are
    dropped."""
    held = _pad_buffers.__dict__.setdefault("held", {})
    buf = held.get((shape, dtype))
    if buf is None:
        buf = held[(shape, dtype)] = np.empty(shape, dtype=dtype)
    return buf


def _ladder_chunks(
    arr: np.ndarray, lengths: np.ndarray, rungs: "tuple[int, ...]", own=False
):
    """Cut the leading axis into launches and pad each to the ladders:
    yields (lo, hi, host array of as many rows as the rung of ``rungs``
    that holds hi - lo, at the width's rung; its int32 lengths with 0
    for the padding rows).  ``rungs`` are the leading dimensions the
    entry point is launched at, ascending; the last is the most one
    launch takes.  Only the last launch of a call can be short of its
    row rung, so a call that came staged at its width (codec/erasure.py
    stages there) fills at most one padding buffer.  ``own``: the call
    returns while the device still reads its input (an encode's
    begin), so a padded launch gets an array of its own and never the
    thread's buffer."""
    cap = rungs[-1]
    total, L = arr.shape[0], arr.shape[-1]
    width = width_rung(L)
    for lo in range(0, total, cap):
        hi = min(lo + cap, total)
        rows = next(r for r in rungs if r >= hi - lo)
        part, lens = arr[lo:hi], lengths[lo:hi]
        if rows != hi - lo or width != L:
            shape = (rows,) + arr.shape[1:-1] + (width,)
            # the thread's buffer serves the one short launch of a call;
            # a caller that did not stage at its width is copied launch
            # by launch, each into an array of its own (the launches of
            # a call are read back together, after the last is issued)
            padded = (
                _pad_buffer(shape, arr.dtype)
                if width == L and not own
                else np.zeros(shape, dtype=arr.dtype)
            )
            padded[: hi - lo, ..., :L] = part
            part = padded
            lens = np.zeros(rows, dtype=np.int32)
            lens[: hi - lo] = lengths[lo:hi]
        yield lo, hi, part, lens


# ---------------------------------------------------------------------------
# Warming: a width's programs are loaded off the request's path
# ---------------------------------------------------------------------------
#
# The ladders make the set of programs finite, so a server need not meet
# each inside somebody's request.  Once it is serving (server/__main__
# starts this after ``ready``; nothing else does, so tests and tools
# trace only what they launch), the first launch at a staged width
# queues that width's family - the encode at every rung it can launch
# (``encode_rungs``), the digest likewise (``digest_rungs``), the
# reconstruct at 1 and 2 stripes - and one background thread runs
# each on zeros, narrowest
# width first: a load from the compile cache where the cache has it, a
# compile where not, on a thread no request waits for.  It adapts to the
# traffic: a deployment of one object size warms one width.

_WARM_RECONSTRUCT = (1, 2)


class _Warmer:
    def __init__(self, backend: "TpuBackend"):
        self._backend = backend
        self._seen: "set[tuple]" = set()
        self._queue: "queue.PriorityQueue" = queue.PriorityQueue()
        self._seq = itertools.count()
        self._stopping = threading.Event()
        self.loaded = 0
        self._thread = threading.Thread(
            target=self._run, name="codec-warmer", daemon=True
        )
        self._thread.start()

    def note(self, *families: tuple) -> None:
        """Families (kind, ..., width) a launch has just been made of."""
        for family in families:
            if family not in self._seen:
                self._seen.add(family)
                self._queue.put((family[-1], next(self._seq), family))

    def _run(self) -> None:
        while not self._stopping.is_set():
            try:
                family = self._queue.get(timeout=0.2)[2]
            except queue.Empty:
                continue
            try:
                for program in self._backend._family(family):
                    if self._stopping.is_set():
                        return
                    program()
                    self.loaded += 1
            except Exception as exc:  # noqa: BLE001 - a request will say
                _log.warning(
                    "codec warm-up failed",
                    extra=kv(family=repr(family), err=str(exc)),
                )

    def stop(self) -> None:
        """Before the interpreter goes: a compile may not be cut short."""
        self._stopping.set()
        self._thread.join(timeout=120)


def _innermost(be):
    """The concrete backend under the batching and telemetry wrappers."""
    while hasattr(be, "inner"):
        be = be.inner
    return be


def _device_backend() -> "TpuBackend | None":
    be = _innermost(get_backend())
    return be if isinstance(be, TpuBackend) else None


def start_warming() -> bool:
    """Load the programs of every width the traffic shows, behind it
    (see above).  False on a host codec, which compiles nothing."""
    be = _device_backend()
    if be is None or len(be._base_devices()) > 1:
        return False  # a mesh builds its programs a placement
    if be._warmer is None:
        be._warmer = _Warmer(be)
    return True


def stop_warming() -> None:
    be = _innermost(_backend)  # never resolves one just to stop it
    warmer = getattr(be, "_warmer", None)
    if warmer is not None:
        be._warmer = None
        warmer.stop()


# ---------------------------------------------------------------------------
# Device-resident parity plane: refs + the bounded write-back cache
# ---------------------------------------------------------------------------


class ParityPlaneCache:
    """Bounded write-back cache of device-resident parity planes.

    One entry per (encode handle, shard-size group) — a ParityRef whose
    bytes still live on the device.  ``add`` evicts FIFO once occupancy
    exceeds the byte budget, and eviction IS the write-back: the victim
    ref drains D2H (outside the cache lock — drain re-enters via
    ``forget``), so a burst of concurrent PUTs can never pin unbounded
    device memory; it just loses laziness for the oldest planes.
    """

    def __init__(self, capacity_bytes: int):
        self._mu = threading.Lock()
        # insertion-ordered (dict preserves it): FIFO eviction
        self._refs: "dict[int, object]" = {}
        self._bytes = 0
        self.capacity = max(1, int(capacity_bytes))
        self.added = 0
        self.evictions = 0

    def _account(self) -> None:
        """Report occupancy to the shared device-byte ledger this plane
        splits with the read cache (cache/allocator.py). Lock held."""
        try:
            from ..cache.allocator import device_budget

            device_budget().set_usage("parity_plane", self._bytes)
        except Exception as exc:  # noqa: BLE001 - must never fail I/O
            _log.debug("parity budget accounting failed: %s", exc)

    def add(self, ref) -> None:
        while True:
            victim = None
            with self._mu:
                if id(ref) not in self._refs:
                    self._refs[id(ref)] = ref
                    self._bytes += ref.nbytes
                    self.added += 1
                    self._account()
                if self._bytes > self.capacity:
                    for r in self._refs.values():
                        if r is not ref:
                            victim = r
                            break
                    if victim is not None:
                        self.evictions += 1
                if victim is None:
                    return  # within budget (or lone oversized plane)
            victim.drain()  # write-back outside the lock; drain forgets

    def forget(self, ref) -> None:
        """Drop a drained/released ref (called by the ref itself)."""
        with self._mu:
            if self._refs.pop(id(ref), None) is not None:
                self._bytes -= ref.nbytes
                self._account()

    def pressure(self) -> float:
        """Occupancy over budget; >= 1.0 means the batcher should back
        off admitting new encodes until drains catch up."""
        with self._mu:
            return self._bytes / self.capacity

    def stats(self) -> dict:
        with self._mu:
            return {
                "capacity_bytes": self.capacity,
                "occupancy_bytes": self._bytes,
                "entries": len(self._refs),
                "added": self.added,
                "evictions": self.evictions,
            }


class _EagerParityRef:
    """ParityRef over host-resident parity (eager/CPU backends): the
    bytes never were on a device, so drain is a handover."""

    __slots__ = ("_parity",)

    def __init__(self, parity_b: np.ndarray):
        self._parity = parity_b

    @property
    def nbytes(self) -> int:
        return 0 if self._parity is None else self._parity.nbytes

    def drain(self) -> np.ndarray:
        return self._parity

    def release(self) -> None:
        self._parity = None


class _DeviceParityRef:
    """One encode call's device-resident parity: a (rows, m, w) u32
    plane for each launch the call went out as, padding rows included.

    ``drain()`` is the single D2H seam: thread-safe and memoized, so
    the m per-disk parity writers sharing this ref pay one transfer.
    Registered with the ParityPlaneCache, which accounts the planes
    until they are drained or released.
    """

    __slots__ = ("_lk", "_cache", "_planes", "_host", "_width", "nbytes")

    def __init__(self, cache: ParityPlaneCache, planes: list, width: int):
        self._lk = threading.Lock()
        self._cache = cache
        self._planes = planes  # [(real rows, plane)], in the call's order
        self._host: "np.ndarray | None" = None
        self._width = width  # of the caller's rows, at most the planes'
        self.nbytes = sum(int(plane.nbytes) for _, plane in planes)
        cache.add(self)

    def drain(self) -> np.ndarray:
        """(B, m, L) uint8 parity bytes at the caller's width,
        materialized at most once: the one sanctioned eager readback of
        a parity plane.  One launch (a call within the cap) hands its
        buffer through as a view; several are joined on the host."""
        from ..ops import codec_step

        with self._lk:
            if self._host is None and self._planes is not None:
                self._host = _join([
                    codec_step.host_words_to_bytes(
                        _host_readback(plane, "parity")
                    )[:rows, :, : self._width]
                    for rows, plane in self._planes
                ])
                self._planes = None
                self._cache.forget(self)
            return self._host

    def release(self) -> None:
        """Drop unused planes without the transfer (error-path
        cleanup of handles whose writers were never scheduled)."""
        with self._lk:
            if self._planes is not None:
                self._planes = None
                self._cache.forget(self)


_PARITY_CACHE: "ParityPlaneCache | None" = None


def parity_plane_cache() -> ParityPlaneCache:
    """The process-wide parity cache (MINIO_TPU_PARITY_CACHE_MB,
    default 128 MiB; env read once at creation, reset_backend() drops
    it so tests can resize)."""
    global _PARITY_CACHE
    c = _PARITY_CACHE
    if c is None:
        with _lock:
            if _PARITY_CACHE is None:
                try:
                    mb = float(
                        os.environ.get("MINIO_TPU_PARITY_CACHE_MB")
                        or 128
                    )
                except ValueError:
                    mb = 128.0
                _PARITY_CACHE = ParityPlaneCache(int(mb * (1 << 20)))
            c = _PARITY_CACHE
    return c


def parity_cache_stats() -> dict:
    """Occupancy/eviction counters for telemetry (zeros before first use)."""
    c = _PARITY_CACHE
    if c is None:
        return {
            "capacity_bytes": 0,
            "occupancy_bytes": 0,
            "entries": 0,
            "added": 0,
            "evictions": 0,
        }
    return c.stats()


def parity_cache_pressure() -> float:
    """Cache pressure without forcing the singleton into existence."""
    c = _PARITY_CACHE
    return 0.0 if c is None else c.pressure()


class _AsyncHandle:
    """Mutable in-flight encode handle.

    ``consumed``/``result`` make encode_end IDEMPOTENT: error-path
    cleanup racing the normal consume gets the first call's result back
    instead of re-materializing (or corrupting wrapper bookkeeping).
    Single-threaded consumption is the contract — the erasure layer's
    _Begun records serialize end() per handle.
    """

    __slots__ = ("kind", "payload", "consumed", "result")

    def __init__(self, kind: str, payload):
        self.kind = kind
        self.payload = payload
        self.consumed = False
        self.result = None


class CodecBackend:
    """Batched erasure codec + bitrot digest interface.

    Shapes are byte-domain; implementations may view as words internally.
    """

    name = "abstract"

    # True when encode() computes parity and digests in one fused pass
    # over the bytes (TPU device pass, native single-pass CPU kernel).
    # The erasure layer keys its stage accounting on this so the fused
    # time shows up as "codec_fused" in put_stages breakdowns.
    fused_encode = False

    # Every op that hashes takes ``lengths``: None, or int32[B], the
    # true shard bytes of each stripe where the caller staged its rows
    # wider than they are (at ``stage_width``, zero past the length).
    # ``reconstruct`` takes none: Reed-Solomon is column-wise, and a
    # zero padding column decodes to zero.  Results come back at the
    # width the rows came in; what lies past a stripe's length in them
    # is padding.

    def stage_width(self, nbytes: int) -> int:
        """The width to lay shard rows of ``nbytes`` out at so that this
        backend takes them as they lie (the stream assembles its blocks
        there, so staging costs no second copy).  Host codecs work at
        the exact width."""
        return nbytes

    def encode_stripes(self, stripe_bytes: int) -> "int | None":
        """Most stripes of ``stripe_bytes`` (k rows at their staged
        width) this backend launches at once, None where it takes a
        batch of any size in one go.  The batcher copies jobs together
        no further than this."""
        return None

    def encode(self, data: np.ndarray, parity_shards: int, lengths=None):
        """(B, k, L) u8 -> (parity (B, m, L) u8, digests (B, k+m, 8) u32).

        L must be a multiple of 32.  Digest order: data rows then parity.
        """
        raise NotImplementedError

    def reconstruct(
        self,
        shards: np.ndarray,
        present: "tuple[bool, ...]",
        data_shards: int,
        parity_shards: int,
    ) -> np.ndarray:
        """(B, n, L) u8 + survivor mask -> (B, k, L) u8 data rows."""
        raise NotImplementedError

    def digest(self, shards: np.ndarray, lengths=None) -> np.ndarray:
        """(B, n, L) u8 -> (B, n, 8) u32 phash256 digests."""
        raise NotImplementedError

    def verify(
        self, shards: np.ndarray, digests: np.ndarray, lengths=None
    ) -> np.ndarray:
        """(B, n, L) u8 + (B, n, 8) digests -> (B, n) bool intact mask."""
        return (
            self.digest(shards, lengths) == np.asarray(digests)
        ).all(axis=-1)

    def reconstruct_and_verify(
        self,
        shards: np.ndarray,
        digests: np.ndarray,
        present: "tuple[bool, ...] | np.ndarray",
        data_shards: int,
        parity_shards: int,
        lengths=None,
    ) -> "tuple[np.ndarray, np.ndarray]":
        """Verify digests of present shards AND decode the data rows.

        (B, n, L) u8 + (B, n, 8) digests + present mask ->
        (data (B, k, L) u8, ok (B, n) bool).  The returned ok mask
        reflects per-shard digest checks (absent shards are False);
        decode uses only shards that verified intact.  Raises
        ValueError when fewer than k shards verify for some stripe.
        Backends may fuse the two passes; this default composes them.
        """
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        pres = np.asarray(present, dtype=bool)
        ok = self.verify(shards, digests, lengths) & pres
        return (
            self._reconstruct_from_ok(
                shards, ok, data_shards, parity_shards
            ),
            ok,
        )

    def _reconstruct_from_ok(self, shards, ok, data_shards, parity_shards):
        """Decode each stripe from its own verified-intact shard set,
        grouping stripes that share a survivor pattern into one
        reconstruct call."""
        B, n, L = shards.shape
        out = np.empty((B, data_shards, L), dtype=np.uint8)
        groups: "dict[tuple[bool, ...], list[int]]" = {}
        for b in range(B):
            if int(ok[b].sum()) < data_shards:
                raise ValueError(
                    f"stripe {b}: {int(ok[b].sum())}/{n} shards intact,"
                    f" need {data_shards}"
                )
            groups.setdefault(tuple(bool(x) for x in ok[b]), []).append(b)
        for pat, idxs in groups.items():
            out[idxs] = self.reconstruct(
                shards[idxs], pat, data_shards, parity_shards
            )
        return out

    # -- async pipeline seam (erasure-encode.go:73-109 overlap) --------
    #
    # encode_begin enqueues the H2D transfer + device pass and returns
    # an opaque handle WITHOUT synchronizing; encode_end materializes
    # the results.  The streaming encoder keeps exactly one batch in
    # flight so the device works on block-batch k while the host does
    # disk/network I/O for batch k-1 (double buffering).  Host-only
    # backends fall back to eager evaluation - the handle IS the
    # result, and end() is free.

    def encode_begin(
        self, data: np.ndarray, parity_shards: int, lengths=None
    ):
        return self.encode(data, parity_shards, lengths)

    def encode_end(self, handle):
        return handle

    # -- digest-only pipeline seam (device-resident parity plane) ------
    #
    # Same begin/end split, but _end eagerly materializes ONLY the
    # digests (all the commit path needs to build bitrot metadata and
    # ack) and returns the parity as a ParityRef whose .drain() is the
    # lazy D2H seam the parity writers pull through behind quorum.
    # Host backends compose the eager defaults below — the "ref" wraps
    # parity that is already host-resident; device backends override to
    # keep the plane on device (TpuBackend).

    def encode_digest_begin(
        self, data: np.ndarray, parity_shards: int, lengths=None
    ):
        return self.encode_begin(data, parity_shards, lengths)

    def encode_digest_end(self, handle):
        """handle -> (digests (B, k+m, 8) u32, parity ref)."""
        parity, digests = self.encode_end(handle)
        return (
            np.asarray(digests),
            _EagerParityRef(
                np.ascontiguousarray(parity, dtype=np.uint8)
            ),
        )

    def parity_cache_pressure(self) -> float:
        """Write-back cache pressure seen by this backend (0.0 when the
        backend keeps nothing device-resident)."""
        return 0.0

    def placement_router(self):
        """Submesh router for multi-chip placement, or None when the
        backend has no device set to carve (host backends, single
        device).  The batcher feature-detects this seam to route
        independent merged batches to disjoint submeshes."""
        return None


class TpuBackend(CodecBackend):
    """Device backend over the devices JAX reports (device_info() names
    the platform): fused single-device passes on the device the batcher
    routed this thread's batch to, mesh-parallel when a batch spans >1
    device (a real slice, or the tests' virtual CPU mesh).  The mesh
    path shards stripes over "stripe" and the k data shards over
    "shard" with an XOR all-reduce (parallel.mesh), mirroring the
    reference's set- and disk-level fan-out (SURVEY.md section 2.4).
    Set MINIO_MESH=0 to pin every pass to one device.
    """

    name = "tpu"
    fused_encode = True  # ops/codec_step fuses encode+hash on device

    def __init__(self, devices=None):
        # devices=None -> every visible device; an explicit tuple pins
        # the backend to a slice of the machine
        self._devices = tuple(devices) if devices is not None else None
        self._meshes: dict[tuple, object] = {}
        self._router = None
        self._router_mu = threading.Lock()
        self._warmer: "_Warmer | None" = None  # start_warming()

    def _base_devices(self) -> tuple:
        import jax

        if self._devices is not None:
            return self._devices
        return tuple(jax.devices())

    def _mesh_for(self, batch: int, k: int):
        """Pick a mesh for this call's geometry, or None for single-device.

        A submesh routed by the batcher (parallel.rules.placed) narrows
        the device set for this thread; otherwise the full base set
        spans.
        """
        if os.environ.get("MINIO_MESH", "1") == "0":
            return None
        from ..parallel import mesh as pm, rules as prules

        devices = prules.current_placement() or self._base_devices()
        if len(devices) <= 1:
            return None
        stripe, shard = pm.pick_axes(len(devices), batch, k)
        # key on device ids, not the tuple of Device objects: cheap and
        # stable across jax.devices() calls
        key = (tuple(int(d.id) for d in devices), stripe, shard)
        m = self._meshes.get(key)
        if m is None:
            m = pm.make_mesh(list(devices), stripe=stripe, shard=shard)
            self._meshes[key] = m
        return m

    def _to_device(self, host: np.ndarray):
        """Stage a host array on THE device this thread's single-device
        pass runs on: the submesh the batcher routed the batch to, else
        the first of the base set.  Never the process default - on a
        multi-chip host that would land every routed batch on chip 0."""
        import jax

        from ..parallel import rules as prules

        device = (prules.current_placement() or self._base_devices())[0]
        return jax.device_put(host, device)

    def _stage(self, host_bytes: np.ndarray):
        """``seam_stage``: bytes -> words and the device_put, up to the
        jitted call."""
        from ..ops import codec_step

        with spans.span(spans.SEAM_STAGE):
            words = self._to_device(
                codec_step.host_bytes_to_words(host_bytes)
            )
        _record_h2d("data", words.nbytes)
        return words

    def placement_router(self):
        devices = self._base_devices()
        if len(devices) <= 1 or os.environ.get("MINIO_MESH", "1") == "0":
            return None  # one device, or pinned to one: nothing to route
        with self._router_mu:
            if self._router is None:
                from ..parallel import rules as prules

                self._router = prules.PlacementRouter(devices)
            return self._router

    def stage_width(self, nbytes: int) -> int:
        return width_rung(nbytes)

    def encode_stripes(self, stripe_bytes: int) -> "int | None":
        if self.placement_router() is not None:
            return None  # a mesh takes the flush whole, over its devices
        return encode_rungs(stripe_bytes)[-1]

    def _family(self, family: tuple):
        """The programs of one family of a staged width, as thunks that
        launch each on zeros exactly as the seam launches it (the same
        statics, shapes and placement, so the program a request finds is
        this one).  Of the encode and the digest that is every program
        the width can launch; of the reconstruct the two any traffic
        meets."""
        from ..ops import codec_step

        kind, width = family[0], family[-1]
        use_pallas, interpret = codec_step.pallas_dispatch(width // 4)

        def zeros(*shape):
            return self._to_device(np.zeros(shape, dtype=np.uint32))

        if kind == "encode":
            _, k, m, _ = family
            for B in encode_rungs(k * width):
                yield lambda B=B: codec_step.encode_words_fused1(
                    zeros(B, k, width // 4), m, np.zeros(B, np.int32),
                    use_pallas=use_pallas, interpret=interpret,
                )
        elif kind == "digest":
            for rows in digest_rungs(width):
                yield lambda rows=rows: codec_step.digest_words(
                    zeros(1, rows, width // 4),
                    np.zeros((1, rows), np.int32),
                )
        elif kind == "reconstruct":
            _, k, m, _ = family
            survivors = np.arange(k, dtype=np.int32)
            matrix = np.eye(k, dtype=np.uint8)
            for B in _WARM_RECONSTRUCT:
                if B <= launch_rows((k + m) * width):
                    yield lambda B=B: codec_step.reconstruct_words_batch(
                        zeros(B, k + m, width // 4), survivors, matrix, k, m,
                        use_pallas=use_pallas, interpret=interpret,
                    )

    @staticmethod
    def _at_rung(data: np.ndarray) -> np.ndarray:
        return at_width(data, width_rung(data.shape[-1]))

    def encode(self, data, parity_shards, lengths=None):
        return self.encode_end(
            self.encode_begin(data, parity_shards, lengths)
        )

    def encode_begin(self, data, parity_shards, lengths=None):
        """Asynchronous start: JAX dispatch is async, so the returned
        device arrays are futures - the H2D copy and the fused pass
        run while the caller streams the PREVIOUS batch to disk."""
        from ..ops import codec_step

        data = np.ascontiguousarray(data, dtype=np.uint8)
        B, k, L = data.shape
        lens = stripe_lengths(data, lengths)
        width = width_rung(L)
        compiled = parity_shards > 0 and codec_step.pallas_compiled(
            width // 4
        )
        mesh = self._mesh_for(B, k)
        if mesh is not None:
            # shard_map dispatch is as async as plain jit: the mesh
            # begin/end split returns device-array futures, so the
            # encode/write overlap survives on the mesh path too
            from ..parallel import mesh as pm

            # a mesh builds its programs a placement and a batch shape:
            # a power of two of stripes keeps them few (zero stripes
            # pad, and their results are dropped at the end)
            padded = encode_rung(B)
            if padded != B:
                wide = np.zeros((padded, k, width), dtype=np.uint8)
                wide[:B, :, :L] = data
                data = wide
                lens = np.concatenate(
                    [lens, np.zeros(padded - B, np.int32)]
                )
            else:
                data = at_width(data, width)
            # on a mesh the staging happens inside the mesh call
            with _launch():
                h = pm.mesh_encode_hash_begin(
                    mesh, codec_step.host_bytes_to_words(data),
                    parity_shards, lens,
                )
            _record_h2d("data", data.nbytes)
            # k-sharded meshes run the dynamic XLA bit-walk + all-reduce
            _record_pass(
                "mesh_encode_hash",
                pallas=compiled and mesh.shape["shard"] == 1,
            )
            return _AsyncHandle("async-mesh", (h, B, L))
        launched = []
        for lo, hi, part, plens in _ladder_chunks(
            data, lens, encode_rungs(k * width), own=True
        ):
            words = self._stage(part)
            with _launch():
                parity_w, digests = codec_step.encode_and_hash_words(
                    words, parity_shards, plens
                )
            _record_pass("encode_and_hash_words", pallas=compiled)
            launched.append((hi - lo, parity_w, digests))
        return _AsyncHandle("async", (launched, B, L))

    def encode_end(self, handle):
        if not isinstance(handle, _AsyncHandle):
            return handle  # foreign/eager handle: already a result
        if handle.consumed:
            return handle.result
        from ..ops import codec_step

        payload, B, L = handle.payload
        if handle.kind == "async-mesh":
            from ..parallel import mesh as pm

            launched = [(B,) + tuple(pm.mesh_encode_hash_end(payload))]
        elif handle.kind == "async":
            launched = payload
        else:
            raise ValueError(
                f"encode_end: unknown handle kind {handle.kind!r}"
            )
        parity = _join([
            codec_step.host_words_to_bytes(
                _host_readback(parity_w, "parity")
            )[:rows, :, :L]
            for rows, parity_w, _ in launched
        ])
        digests = _join([
            _host_readback(digests_d, "data")[:rows]
            for rows, _, digests_d in launched
        ])
        result = parity, digests
        handle.result = result
        handle.consumed = True
        handle.payload = None  # drop the device refs
        return result

    def encode_digest_begin(self, data, parity_shards, lengths=None):
        """Digest-only start: the fused donated kernel keeps parity on
        device behind a ParityRef; only the 32-byte digests are
        scheduled for readback.  A call of more stripes than one launch
        holds (a stream's batch of four full blocks, a coalesced flush)
        goes out as several, each at an encode rung, as the read side's
        do: views of the caller's array where it lies at its width."""
        from ..ops import codec_step

        data = np.ascontiguousarray(data, dtype=np.uint8)
        B, k, L = data.shape
        if self._mesh_for(B, k) is not None:
            # the mesh path has no device-resident cache (planes live
            # sharded across devices): compose the eager seam, still
            # async through the mesh begin/end split
            return _AsyncHandle(
                "digest-eager",
                self.encode_begin(data, parity_shards, lengths),
            )
        lens = stripe_lengths(data, lengths)
        width = width_rung(L)
        use_pallas, interpret = codec_step.pallas_dispatch(width // 4)
        launched, sizes = [], []
        for lo, hi, part, plens in _ladder_chunks(
            data, lens, encode_rungs(k * width), own=True
        ):
            words = self._stage(part)
            with _launch():
                parity_w, digests = codec_step.encode_words_fused1(
                    words,
                    parity_shards,
                    plens,
                    use_pallas=use_pallas,
                    interpret=interpret,
                )
            _record_pass("encode_words_fused1", pallas=use_pallas)
            _record_ragged(np.repeat(plens, k), width)
            sizes.append(part.nbytes)
            launched.append((hi - lo, parity_w, digests))
        _record_launches(sizes)
        if self._warmer is not None and parity_shards > 0:
            self._warmer.note(
                ("encode", k, parity_shards, width),
                ("digest", width),
                ("reconstruct", k, parity_shards, width),
            )
        return _AsyncHandle("digest", (launched, L))

    def encode_digest_end(self, handle):
        if not isinstance(handle, _AsyncHandle) or handle.kind not in (
            "digest",
            "digest-eager",
        ):
            return super().encode_digest_end(handle)
        if handle.consumed:
            return handle.result
        if handle.kind == "digest-eager":
            parity, digests = self.encode_end(handle.payload)
            result = (
                np.asarray(digests),
                _EagerParityRef(
                    np.ascontiguousarray(parity, dtype=np.uint8)
                ),
            )
        else:
            # digests are the ONLY eager readback (MTPU107); parity
            # stays device-resident behind the ref
            launched, L = handle.payload
            digests = _join([
                _host_readback(digests_d, "data")[:rows]
                for rows, _, digests_d in launched
            ])
            result = (
                digests,
                _DeviceParityRef(
                    parity_plane_cache(),
                    [(rows, parity_w) for rows, parity_w, _ in launched],
                    L,
                ),
            )
        handle.result = result
        handle.consumed = True
        handle.payload = None
        return result

    def drain(self, parity_ref) -> np.ndarray:
        """The lazy readback seam: stream one cached parity plane D2H
        (delegates to the ref — named here so callers/tests have a
        backend surface to drive and the lint exemption a seam name)."""
        return parity_ref.drain()

    def parity_cache_pressure(self) -> float:
        return parity_cache_pressure()

    def reconstruct(self, shards, present, data_shards, parity_shards):
        from ..ops import codec_step

        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        B, n, L = shards.shape
        survivors, matrix = decode_plan(present, data_shards, parity_shards)
        width = width_rung(L)
        use_pallas, interpret = codec_step.pallas_dispatch(width // 4)
        mesh = self._mesh_for(B, data_shards)
        if mesh is not None:
            from ..parallel import mesh as pm

            with _launch():  # staging, kernel and read-back are inside
                dw = pm.mesh_reconstruct(
                    mesh,
                    codec_step.host_bytes_to_words(self._at_rung(shards)),
                    survivors,
                    matrix,
                    data_shards,
                    parity_shards,
                    use_pallas=use_pallas and mesh.shape["shard"] == 1,
                    interpret=interpret,
                )
            _record_pass(
                "mesh_reconstruct",
                pallas=use_pallas and mesh.shape["shard"] == 1,
            )
            # k compacted survivor rows go up, k data rows come back
            _record_h2d("data", dw.nbytes)
            _record_d2h("data", dw.nbytes)
            return codec_step.host_words_to_bytes(dw)[..., :L]
        launched, sizes = [], []
        # the decode takes no length (column-wise), so its launches are
        # not in ``ragged``: their rows lie at the same rungs
        no_lengths = np.zeros(B, dtype=np.int32)
        for lo, hi, part, _ in _ladder_chunks(
            shards, no_lengths, reconstruct_rungs(n * width)
        ):
            sizes.append(part.nbytes)
            words = self._stage(part)
            with _launch():
                dw = codec_step.reconstruct_words_batch(
                    words,
                    survivors,
                    matrix,
                    data_shards,
                    parity_shards,
                    use_pallas=use_pallas,
                    interpret=interpret,
                )
            _record_pass("reconstruct_words_batch", pallas=use_pallas)
            launched.append((lo, hi, dw))
        _record_launches(sizes)
        if self._warmer is not None:
            self._warmer.note(
                ("reconstruct", data_shards, parity_shards, width)
            )
        return self._gather(launched, (B, data_shards, L))

    @staticmethod
    def _gather(launched, shape):
        """Read back the launches of one seam call: (lo, hi, device
        words) each, padding rows and the staged width's padding
        dropped.  One launch (the common case) hands its buffer through
        as a view."""
        from ..ops import codec_step

        L = shape[-1]
        if len(launched) == 1:
            lo, hi, dw = launched[0]
            got = codec_step.host_words_to_bytes(_host_readback(dw, "data"))
            return got[: hi - lo, ..., :L]
        out = np.empty(shape, dtype=np.uint8)
        for lo, hi, dw in launched:
            got = codec_step.host_words_to_bytes(_host_readback(dw, "data"))
            out[lo:hi] = got[: hi - lo, ..., :L]
        return out

    def reconstruct_and_verify(
        self, shards, digests, present, data_shards, parity_shards,
        lengths=None,
    ):
        """Fused GET-side pass: digest checks + survivor decode in ONE
        device pass (codec_step.verify_and_reconstruct_words) on the
        heal path.  Optimistic like CpuBackend: decode from the first k
        present rows while hashing all of them; on the rare digest
        mismatch among the chosen survivors, re-pick survivors from the
        verified mask and re-solve just the hit stripes."""
        from ..ops import codec_step

        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        pres = np.asarray(present, dtype=bool)
        B, n, L = shards.shape
        lens = stripe_lengths(shards, lengths)
        survivors, matrix = decode_plan(pres, data_shards, parity_shards)
        words = codec_step.host_bytes_to_words(self._at_rung(shards))
        use_pallas, interpret = codec_step.pallas_dispatch(words.shape[-1])
        mesh = self._mesh_for(B, data_shards)
        if mesh is not None:
            from ..parallel import mesh as pm

            with _launch():  # staging, kernel and read-back are inside
                dw, ok = pm.mesh_verify_reconstruct(
                    mesh,
                    words,
                    np.asarray(digests),
                    pres,
                    survivors,
                    matrix,
                    data_shards,
                    parity_shards,
                    lens,
                    use_pallas=use_pallas,
                    interpret=interpret,
                )
            _record_pass("mesh_verify_reconstruct", pallas=use_pallas)
            _record_h2d("data", words.nbytes)
            _record_d2h("data", dw.nbytes)
        else:
            with spans.span(spans.SEAM_STAGE):
                words_d = self._to_device(words)
                digests_d = self._to_device(np.asarray(digests))
            _record_h2d("data", words_d.nbytes)
            with _launch():
                dw_d, ok_d = codec_step.verify_and_reconstruct_words(
                    words_d,
                    digests_d,
                    pres,
                    survivors,
                    matrix,
                    data_shards,
                    parity_shards,
                    lens,
                    use_pallas=use_pallas,
                    interpret=interpret,
                )
            _record_pass("verify_and_reconstruct_words", pallas=use_pallas)
            dw = _host_readback(dw_d, "data")
            ok = _host_readback(ok_d, None)
        data = codec_step.host_words_to_bytes(dw)[..., :L]
        bad = ~ok[:, survivors].all(axis=1)
        if bad.any():
            idxs = np.nonzero(bad)[0]
            if not data.flags.writeable:  # zero-copy view of a jax buffer
                data = data.copy()
            data[idxs] = self._reconstruct_from_ok(
                shards[idxs], ok[idxs], data_shards, parity_shards
            )
        return data, ok

    def digest(self, shards, lengths=None):
        from ..ops import codec_step

        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        B, n, L = shards.shape
        # digests are row-local: the rows of the whole batch lie flat,
        # each with its own length
        rows = shards.reshape(B * n, L)
        lens = np.repeat(stripe_lengths(shards, lengths), n)
        mesh = self._mesh_for(B * n, 1)
        if mesh is not None:
            from ..parallel import mesh as pm

            words = codec_step.host_bytes_to_words(self._at_rung(rows))
            _record_pass("mesh_digest")
            with _launch():  # staging, kernel and read-back are inside
                got = pm.mesh_digest(mesh, words, lens).reshape(B, n, 8)
            _record_h2d("data", words.nbytes)
            _record_d2h("data", got.nbytes)
            return got
        # (1, rows, w), and the row count walks the ladder - one
        # program per (row rung, width rung) whatever (B, n) a flush
        # came in and whatever lengths its rows have
        width = width_rung(L)
        out = np.empty((B * n, 8), dtype=np.uint32)
        launched, sizes = [], []
        for lo, hi, part, plens in _ladder_chunks(
            rows, lens, digest_rungs(width)
        ):
            sizes.append(part.nbytes)
            words = self._stage(part[None])
            # the healthy-read digest has no Pallas kernel: one XLA pass
            with _launch():
                got = codec_step.digest_words(words, plens[None])
            _record_pass("digest_words")
            _record_ragged(plens, width)
            launched.append((lo, hi, got))
        _record_launches(sizes)
        if self._warmer is not None:
            self._warmer.note(("digest", width))
        for lo, hi, got in launched:
            out[lo:hi] = _host_readback(got, "data")[0, : hi - lo]
        return out.reshape(B, n, 8)


class CpuBackend(CodecBackend):
    """Host backend: the whole batch goes through ONE native call per
    op (fused single-pass encode+hash, batched tiled reconstruct,
    fused reconstruct+verify), stripe-parallel inside the C layer.
    Every native entry point has a bit-identical numpy twin used when
    the toolchain/library is unavailable (warn-once, cached)."""

    name = "cpu"

    # None = untried, False = unavailable (decision cached: the
    # fallback must not re-attempt a failing g++ build per block)
    _native_ok: "bool | None" = None  # fused batch entry points
    _native_hash_ok: "bool | None" = None

    _NATIVE_ERRS = (
        OSError,
        AttributeError,  # stale .so without the symbol
        subprocess.CalledProcessError,
    )

    @property
    def fused_encode(self):  # type: ignore[override]
        return CpuBackend._native_ok is not False

    @staticmethod
    def _exact(arr: np.ndarray, lengths) -> None:
        """The host codec works at the exact width (stage_width is the
        identity here): rows staged wider than they are have no taker."""
        if lengths is not None and (
            stripe_lengths(arr, lengths) != arr.shape[-1]
        ).any():
            raise ValueError(
                "the host codec takes rows at their exact width; got "
                f"lengths below {arr.shape[-1]} bytes"
            )

    @classmethod
    def _native_fused(cls):
        """The native module, or None after a failed build (warn-once)."""
        if cls._native_ok is False:
            return None
        from ..utils import native

        if cls._native_ok is None:
            try:
                native.lib()
                cls._native_ok = True
            except cls._NATIVE_ERRS as exc:
                cls._native_ok = False
                _log.warning(
                    "native codec unavailable; numpy twin engaged"
                    " (bit-identical, slower)",
                    extra=kv(err=str(exc)),
                )
                return None
        return native

    def encode(self, data, parity_shards, lengths=None):
        """Fused single-pass batch encode: ONE native call, no Python
        per-stripe loop, no full-batch concatenate copy."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        self._exact(data, lengths)
        native = self._native_fused()
        if native is not None:
            try:
                return native.encode_and_hash_cpu(data, parity_shards)
            except self._NATIVE_ERRS as exc:
                CpuBackend._native_ok = False
                _log.warning(
                    "native fused encode failed; numpy twin engaged",
                    extra=kv(err=str(exc)),
                )
        parity = _numpy_encode(data, parity_shards)
        # digests of data and parity rows hashed separately and
        # stacked: digest arrays are (B, n, 8) - tiny - so no
        # full-batch byte concatenate on the fallback path either
        digests = np.concatenate(
            [self.digest(data), self.digest(parity)], axis=1
        )
        return parity, digests

    def encode_split(self, data, parity_shards):
        """Legacy split path: per-stripe native matmul round-trips plus
        a separate full-read digest pass over a concatenated copy.
        Kept callable as the identity baseline the fused kernel is
        asserted bit-identical against (tests/test_native.py); not used
        by the erasure layer."""
        from ..utils import native

        data = np.ascontiguousarray(data, dtype=np.uint8)
        B, k, L = data.shape
        m = parity_shards
        parity = np.empty((B, m, L), dtype=np.uint8)
        matrix = gf.parity_matrix(k, m)
        for b in range(B):
            parity[b] = native.gf_matmul_cpu(matrix, data[b])
        digests = self.digest(np.concatenate([data, parity], axis=1))
        return parity, digests

    def reconstruct(self, shards, present, data_shards, parity_shards):
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        pres = np.asarray(present, dtype=bool)
        native = self._native_fused()
        if native is not None:
            try:
                return native.reconstruct_batch_cpu(
                    shards, pres, data_shards, parity_shards
                )
            except self._NATIVE_ERRS as exc:
                CpuBackend._native_ok = False
                _log.warning(
                    "native batch reconstruct failed; numpy twin engaged",
                    extra=kv(err=str(exc)),
                )
        return _numpy_reconstruct(shards, pres, data_shards, parity_shards)

    def reconstruct_and_verify(
        self, shards, digests, present, data_shards, parity_shards,
        lengths=None,
    ):
        """Fused GET-side pass: digest checks + survivor decode in one
        native memory pass.  Optimistic: decodes from the first k
        present shards while hashing all of them; on the rare digest
        mismatch among the chosen survivors, re-picks survivors from
        the verified mask and reconstructs just the hit stripes."""
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        self._exact(shards, lengths)
        pres = np.asarray(present, dtype=bool)
        native = self._native_fused()
        if native is None:
            return super().reconstruct_and_verify(
                shards, digests, pres, data_shards, parity_shards
            )
        try:
            data, ok = native.reconstruct_and_verify_cpu(
                shards, digests, pres, data_shards, parity_shards
            )
        except self._NATIVE_ERRS as exc:
            CpuBackend._native_ok = False
            _log.warning(
                "native fused reconstruct_and_verify failed;"
                " numpy twin engaged",
                extra=kv(err=str(exc)),
            )
            return super().reconstruct_and_verify(
                shards, digests, pres, data_shards, parity_shards
            )
        surv = np.nonzero(pres)[0][:data_shards]
        bad = ~ok[:, surv].all(axis=1)
        if bad.any():
            idxs = np.nonzero(bad)[0]
            if not data.flags.writeable:  # zero-copy view of a jax buffer
                data = data.copy()
            data[idxs] = self._reconstruct_from_ok(
                shards[idxs], ok[idxs], data_shards, parity_shards
            )
        return data, ok

    def digest(self, shards, lengths=None):
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        self._exact(shards, lengths)
        L = shards.shape[-1]
        words = shards.view(np.uint32)
        if CpuBackend._native_hash_ok is not False:
            from ..utils import native

            try:
                out = native.phash256_rows(words, L)
                CpuBackend._native_hash_ok = True
                return out
            except self._NATIVE_ERRS:
                CpuBackend._native_hash_ok = False
        # no toolchain / stale lib: numpy twin (bit-identical, slower)
        return phash.phash256_host_batched(words, L)


def _numpy_encode(data: np.ndarray, parity_shards: int) -> np.ndarray:
    """Vectorized numpy parity twin: loops only over the (m, k) matrix
    cells, each multiply a batched table gather + XOR over (B, L)."""
    B, k, L = data.shape
    m = parity_shards
    matrix = gf.parity_matrix(k, m)
    table = gf.mul_table()
    parity = np.zeros((B, m, L), dtype=np.uint8)
    for r in range(m):
        for c in range(k):
            parity[:, r, :] ^= table[matrix[r, c]][data[:, c, :]]
    return parity


def _numpy_reconstruct(
    shards: np.ndarray,
    present: np.ndarray,
    data_shards: int,
    parity_shards: int,
) -> np.ndarray:
    """Vectorized numpy decode twin of reconstruct_batch_cpu."""
    B, n, L = shards.shape
    k = data_shards
    idx = tuple(int(i) for i in np.nonzero(present)[0])
    if len(idx) < k:
        raise ValueError(f"need {k} shards to reconstruct, have {len(idx)}")
    rm = gf.reconstruction_matrix(k, parity_shards, idx)
    table = gf.mul_table()
    surv = shards[:, list(idx[:k]), :]
    out = np.zeros((B, k, L), dtype=np.uint8)
    for r in range(k):
        for c in range(k):
            if rm[r, c]:
                out[:, r, :] ^= table[rm[r, c]][surv[:, c, :]]
    return out


_lock = threading.Lock()
_backend: "CodecBackend | None" = None


def get_backend(name: "str | None" = None) -> CodecBackend:
    """Resolve the codec backend (MINIO_ERASURE_BACKEND=tpu|cpu|auto)."""
    global _backend
    if name is None:
        with _lock:
            if _backend is not None:
                return _backend
            name = os.environ.get("MINIO_ERASURE_BACKEND", "auto")
            _backend = _make(name)
            return _backend
    return _make(name)


def _pinned_to(platform: str) -> bool:
    """Did the operator hold JAX to this platform (JAX_PLATFORMS=cpu, the
    tests' configuration)?  Then asking for the device backend by name
    gets it there; a platform JAX merely fell back to is not a TPU."""
    import jax

    return platform in (jax.config.jax_platforms or "").split(",")


def _make(name: str) -> CodecBackend:
    # kernel telemetry wraps the CONCRETE backend, under the batcher:
    # a coalesced flush is one recorded call with real device seconds,
    # while queue wait is the batcher's own series (codec/telemetry.py)
    from .batcher import maybe_wrap
    from .telemetry import instrument

    if name not in ("cpu", "tpu", "auto"):
        raise ValueError(f"unknown erasure backend {name!r}")
    if name != "cpu":
        import jax

        # no try: a chip that is missing or held by another process
        # makes jax.devices() raise, and that must stop the server, not
        # quietly serve from the host codec
        platform = jax.devices()[0].platform
        if platform == "tpu" or (name == "tpu" and _pinned_to(platform)):
            _log.info(
                "codec backend resolved",
                extra=kv(requested=name, backend="tpu", platform=platform),
            )
            return maybe_wrap(instrument(TpuBackend()))
        if name == "tpu":
            raise RuntimeError(
                f"MINIO_ERASURE_BACKEND=tpu but JAX found platform "
                f"{platform!r}; set MINIO_ERASURE_BACKEND=cpu (or auto) "
                "to serve from the host codec"
            )
        _log.info(
            "codec backend resolved: no accelerator, host codec",
            extra=kv(requested=name, backend="cpu", platform=platform),
        )
    return maybe_wrap(instrument(CpuBackend()))


def backend_info() -> dict:
    """The resolved backend and the devices under it, for the boot log,
    ``healthinfo`` and ``kernel-stats``: backend name and
    utils.jaxenv.device_info().  Resolves the backend if nothing has
    yet, and raises if that fails."""
    from ..parallel import rules as prules
    from ..utils import jaxenv

    be = get_backend()
    inner = _innermost(be)
    doc = {"backend": inner.name, "batched": be is not inner}
    if isinstance(inner, CpuBackend):
        native = CpuBackend._native_fused() is not None
        doc["codec"] = "native" if native else "numpy"
        return doc
    doc.update(jaxenv.device_info())
    n = doc["device_count"]
    if n > 1:
        doc["placement"] = (
            "pinned to device 0 (MINIO_MESH=0)"
            if os.environ.get("MINIO_MESH", "1") == "0"
            else f"{prules.placement_policy()} over {n} devices"
        )
    return doc


def reset_backend() -> None:
    """Testing aid: drop the cached backend (and the parity cache) so
    env changes take effect."""
    global _backend, _PARITY_CACHE
    with _lock:
        _backend = None
        _PARITY_CACHE = None
    _plans.clear()
    _patterns.clear()
    try:
        from ..cache.allocator import device_budget

        device_budget().set_usage("parity_plane", 0)
    except Exception as exc:  # noqa: BLE001
        _log.debug("parity budget reset failed: %s", exc)
