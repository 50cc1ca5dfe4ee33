"""Spans inside the program: one primitive at every boundary where a
request waits.

A served request is handed over four times (loop -> handler pool ->
iopool queue -> batcher -> device) and between hand-overs it runs, waits
for the GIL, waits for a lock or waits for a drive.  A timer wrapped
round a call from outside holds all of that in one number; the stamps
here are taken where the work is handed over, so each kind of waiting
has a name of its own.

``span(name)``
    Work on one thread.  Records name, start and end (``CLOCK_MONOTONIC``
    in ns, the clock the benchmark harness uses), the enclosing span and
    the request's identifier; the spans that bound a layer
    (``CPU_SPANS``) also record the thread's CPU time over the same
    interval.  Wall minus CPU is what the thread spent not running:
    blocked in a syscall, on a lock, on a queue, or waiting for the GIL.
    After the ``with`` block ``sp.t0`` / ``sp.wall_ns`` / ``sp.seconds``
    hold the two clock readings, so a counter that timed the same
    boundary before shares them instead of reading the clock again.

    Why not CPU time on every span: ``CLOCK_THREAD_CPUTIME_ID`` has no
    vDSO path, every reading is a system call made with the GIL held -
    0.75 us on a plain Linux host, 5.6 us on the sealed v5e host the
    benchmark runs on.  With it on all 3,100 spans a second of
    ``mixed-10m``, ``op_rate`` fell by 9 % (three seeds, parent against
    change); without any, by under 3 %.  So the leaf spans (drive calls,
    seam, stream stages: three quarters of all spans) are wall only, and
    their ``cpu_seconds`` reads null.

``wait(name, since_ns)``
    A hand-over between threads: the submitter stamps ``now()`` into the
    job, the thread that picks the job up records the wait.  No CPU
    time, no nesting.

Three sinks, no fourth:

1. *Counters, always on.*  Per thread a plain dict ``name -> [count,
   wall_ns, cpu_ns]``, touched without a lock; the thread's role (loop,
   handler, iopool, batcher, other) comes from its name.
   ``KernelStats.snapshot()`` merges them into ``kernel-stats.spans``
   and the probe below into ``kernel-stats.probe``.
2. *The profiler's trace, while one is being taken.*  A span enters
   ``jax.profiler.TraceAnnotation("mtpu/<name>", req=<id>)``, the same
   clock as the device's planes.  Building the annotation costs ten
   times the flag check (0.56 us against 0.06 us), so it is built only
   while a session runs.  This module never imports JAX: it takes
   ``jax.profiler`` only when ``jax`` is already in ``sys.modules``.
3. *``admin trace``, while someone listens.*  ``begin_request(True)``
   gives the request a record list; every span under it, on whatever
   thread, appends one record, and ``end_request`` renders them
   (offset and duration in us, parent index, thread role, CPU us where
   read) for the request's ``trace_info`` entry.

The request identifier is minted once per request (``begin_request``),
kept in the thread's state, captured where work is handed to another
thread (``capture``) and restored there (``adopt``).

The interpreter probe (``PROBE``) is one daemon thread that sleeps 20 ms
at a time and records how late each wake-up was: with one GIL, that is
how long a thread that wants to run waits for it.  Each server loop does
the same with ``call_later`` (``LoopProbe``): the loop's lag.
"""

from __future__ import annotations

import functools
import random
import sys
import threading
import time

now = time.monotonic_ns
_cpu = time.thread_time_ns

PREFIX = "mtpu/"  # every annotation of the program's carries it

# -- the names: layer by layer, request plane down to the seam -----------

AIO_QUEUE_WAIT = "aio_queue_wait"
S3_REQUEST = "s3_request"
BODY_READ_WAIT = "body_read_wait"
RESP_WRITE_WAIT = "resp_write_wait"
SIGV4_VERIFY = "sigv4_verify"
HASHREADER_READ = "hashreader_read"
OL_PUT_OBJECT = "ol_put_object"
OL_GET_OBJECT = "ol_get_object"
GET_FIRST_WRITE = "get_first_write"  # ol_get_object's start -> first body bytes
OL_GET_OBJECT_INFO = "ol_get_object_info"
OL_DELETE_OBJECT = "ol_delete_object"
NSLOCK_WAIT = "nslock_wait"
META_READ_ALL = "meta_read_all"
XL_READ_VERSION = "xl_read_version"
XL_READ_ALL = "xl_read_all"
XL_WRITE_ALL = "xl_write_all"
XL_RENAME_DATA = "xl_rename_data"
XL_DELETE_VERSION = "xl_delete_version"
XL_DELETE_FILE = "xl_delete_file"
IOPOOL_QUEUE_WAIT = "iopool_queue_wait"
IOPOOL_JOB = "iopool_job"
XL_SHARD_WRITE = "xl_shard_write"
XL_SHARD_FSYNC = "xl_shard_fsync"
XL_SHARD_READ = "xl_shard_read"
IOPOOL_RESULT_WAIT = "iopool_result_wait"
STREAM_ASSEMBLE = "stream_assemble"
STREAM_CODEC_WAIT = "stream_codec_wait"
STREAM_DISK = "stream_disk"
STREAM_READAHEAD_WAIT = "stream_readahead_wait"  # a GET's wait for its prefetch
BATCH_QUEUE_WAIT = "batch_queue_wait"
BATCH_FLUSH = "batch_flush"
FLUSH_TO_LAUNCH = "flush_to_launch"
BATCH_RESULT_WAIT = "batch_result_wait"
SEAM_MATRIX = "seam_matrix"
SEAM_STAGE = "seam_stage"
SEAM_LAUNCH = "seam_launch"
SEAM_KERNEL_WAIT = "seam_kernel_wait"
SEAM_D2H = "seam_d2h"
PROBE_NAME = "probe"  # a counter, not a span: kernel-stats.probe

# the spans that read the thread's CPU clock: one per layer on the request's
# own path, a few thousand a second at most
CPU_SPANS = frozenset({
    S3_REQUEST, SIGV4_VERIFY, OL_PUT_OBJECT, OL_GET_OBJECT, OL_GET_OBJECT_INFO,
    OL_DELETE_OBJECT, META_READ_ALL, BATCH_FLUSH,
})


def _role_of(thread_name: str) -> str:
    if thread_name.startswith("aio-loop"):
        return "loop"
    if thread_name.startswith("aio") and "-worker-" in thread_name:
        return "handler"
    if thread_name.startswith("iopool"):
        return "iopool"
    if thread_name.startswith("codec-batcher"):
        return "batcher"
    return "other"


# -- per-thread state ------------------------------------------------------


class _State:
    """One thread's counters and the request context it is working for."""

    __slots__ = (
        "thread", "role", "counters", "req", "sink", "parent", "handoff",
    )

    def __init__(self, thread: threading.Thread):
        self.thread = thread
        self.role = _role_of(thread.name)
        self.counters: "dict[str, list]" = {}
        self.req = ""
        self.sink = None  # the request's record list while admin trace listens
        self.parent = None  # the open span's record, same condition
        self.handoff = None  # (name, since_ns) waiting for its pick-up


_tls = threading.local()
_REG_LK = threading.Lock()
_STATES: "list[_State]" = []
# (role, name) -> [count, wall_ns, cpu_ns] of threads that have exited
_RETIRED: "dict[tuple[str, str], list]" = {}
_SWEEP_AT = 256  # fold dead threads' counters once this many states exist


def _state() -> _State:
    try:
        return _tls.st
    except AttributeError:
        st = _tls.st = _State(threading.current_thread())
        with _REG_LK:
            if len(_STATES) >= _SWEEP_AT:
                _fold_dead_locked()
            _STATES.append(st)
        return st


def _items(counters: dict) -> list:
    # the owner may insert a name meanwhile; list(dict.items()) runs
    # without releasing the GIL, the retry is for interpreters without one
    while True:
        try:
            return list(counters.items())
        except RuntimeError:
            continue


def _fold_dead_locked() -> None:
    live = []
    for st in _STATES:
        if st.thread.is_alive():
            live.append(st)
            continue
        for name, row in _items(st.counters):
            into = _RETIRED.setdefault((st.role, name), [0, 0, 0])
            for i in range(3):
                into[i] += row[i]
    _STATES[:] = live


def _count(st: _State, name: str, wall_ns: int, cpu_ns: int) -> None:
    row = st.counters.get(name)
    if row is None:
        st.counters[name] = [1, wall_ns, cpu_ns]
    else:
        row[0] += 1
        row[1] += wall_ns
        row[2] += cpu_ns


# -- the profiler sink -----------------------------------------------------

_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _tracing():
    """``TraceAnnotation`` while a profiler session runs, else None."""
    global _annotation
    ann = _annotation
    if ann is None:
        # None too while another thread is still half-way through importing jax
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is None:
            return None
        ann = _annotation = prof.TraceAnnotation
    return ann if ann.is_enabled() else None


# -- span / wait -----------------------------------------------------------

# a record: [name, start_ns, wall_ns (-1 while open), cpu_ns or None, parent record, role]
MAX_RECORDS = 2048  # of one request: a long-lived stream stops recording here


class span:
    """``with span(name, **args) as sp:`` - see the module docstring.
    ``args`` ride the profiler annotation beside ``req``."""

    __slots__ = ("name", "args", "t0", "wall_ns", "_st", "_c0", "_ann", "_rec")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.wall_ns = 0

    def __enter__(self) -> "span":
        st = self._st = _state()
        rec = None
        if st.sink is not None and len(st.sink) < MAX_RECORDS:
            rec = [self.name, 0, -1, 0, st.parent, st.role]
            st.sink.append(rec)
            st.parent = rec
        self._rec = rec
        ann = _tracing()
        if ann is not None:
            ann = ann(PREFIX + self.name, req=st.req, **self.args)
            ann.__enter__()
        self._ann = ann
        # the CPU readings lie inside the wall readings, so cpu <= wall
        self.t0 = now()
        self._c0 = _cpu() if self.name in CPU_SPANS else -1
        return self

    def __exit__(self, *exc) -> bool:
        cpu = None
        if self._c0 >= 0:
            cpu = _cpu() - self._c0
        wall = self.wall_ns = now() - self.t0
        if cpu is not None and cpu > wall:  # two clocks, two granularities
            cpu = wall
        if self._ann is not None:
            self._ann.__exit__(*exc)
        st = self._st
        _count(st, self.name, wall, cpu or 0)
        rec = self._rec
        if rec is not None:
            rec[1], rec[2], rec[3] = self.t0, wall, cpu
            st.parent = rec[4]
        return False

    @property
    def seconds(self) -> float:
        return self.wall_ns / 1e9


def spanned(name: str):
    """Decorator: the whole call is one span."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            with span(name):
                return fn(*a, **kw)

        return inner

    return wrap


def wait(name: str, since_ns: int, now_ns: "int | None" = None) -> int:
    """Record a hand-over that began at ``since_ns`` on another thread and
    ends now, on the thread that picked the work up.  Returns the end, so
    a caller that needs the same reading takes it from here."""
    end = now() if now_ns is None else now_ns
    st = _state()
    _count(st, name, end - since_ns, 0)
    if st.sink is not None and len(st.sink) < MAX_RECORDS:
        st.sink.append(
            [name, since_ns, end - since_ns, None, st.parent, st.role]
        )
    return end


def hand_over(name: str, since_ns: int) -> None:
    """Leave a stamp for a pick-up further down THIS thread's call (the
    batcher's flush for the seam's launch), through layers that need not
    know of it."""
    _state().handoff = (name, since_ns)


def picked_up() -> None:
    """Close the stamp ``hand_over`` left, if one is waiting."""
    st = _state()
    h = st.handoff
    if h is not None:
        st.handoff = None
        wait(h[0], h[1])


def drop_handoff() -> None:
    _state().handoff = None


# -- the request's identity, carried across threads -------------------------


def begin_request(recording: bool) -> str:
    """Mint the request's identifier on the handler's thread; with
    ``recording`` (a trace subscriber listens) its spans keep records."""
    st = _state()
    st.req = "%016X" % random.getrandbits(64)
    st.sink = [] if recording else None
    st.parent = None
    return st.req


def request_id() -> str:
    return _state().req


def end_request() -> "list[dict] | None":
    """Forget the request; its records, rendered for ``trace_info``: the
    root first, offsets from the root's start, only spans that had ended
    when the root did."""
    st = _state()
    sink, st.req, st.sink, st.parent = st.sink, "", None, None
    if not sink:
        return None
    root = sink[0]
    if root[2] < 0:
        return None
    t0, t1 = root[1], root[1] + root[2]
    kept = [r for r in list(sink) if r[2] >= 0 and r[1] + r[2] <= t1 and r[1] >= t0]
    index = {id(r): i for i, r in enumerate(kept)}
    out = []
    for i, r in enumerate(kept):
        # the parent is the nearest enclosing span that holds this one: work
        # begun asynchronously outlives the span it was submitted under, and
        # a coalesced flush hangs under another request's span - both end up
        # under the root
        p = r[4]
        while p is not None and not (
            id(p) in index and p[1] <= r[1] and r[1] + r[2] <= p[1] + p[2]
        ):
            p = p[4]
        start_us = (r[1] - t0) // 1000
        rec = {
            "name": r[0],
            "start_us": start_us,
            "dur_us": (r[1] + r[2] - t0) // 1000 - start_us,
            "parent": -1 if i == 0 else index[id(p)] if p is not None else 0,
            "role": r[5],
        }
        if r[3] is not None:  # only CPU_SPANS read the thread's CPU clock
            rec["cpu_us"] = r[3] // 1000
        out.append(rec)
    return out


class _Fan:
    """Record sink of a coalesced flush: every served request gets the
    flush's spans."""

    __slots__ = ("sinks",)

    def __init__(self, sinks: list):
        self.sinks = sinks

    def append(self, rec) -> None:
        for s in self.sinks:
            s.append(rec)

    def __len__(self) -> int:
        return max(len(s) for s in self.sinks)


def capture() -> "tuple | None":
    """The context a job takes along to another thread."""
    st = _state()
    if not st.req:
        return None
    return (st.req, st.sink, st.parent)


class adopt:
    """``with adopt(ctx):`` - work for the captured request on this
    thread, and give the thread back as it was.  A list of contexts (a
    coalesced flush) joins the identifiers and fans the records out."""

    __slots__ = ("_ctx", "_st", "_old")

    def __init__(self, ctx):
        if isinstance(ctx, list):
            ctxs = [c for c in ctx if c is not None]
            if not ctxs:
                ctx = None
            elif len(ctxs) == 1:
                ctx = ctxs[0]
            else:
                sinks = [c[1] for c in ctxs if c[1] is not None]
                ctx = (
                    ",".join(dict.fromkeys(c[0] for c in ctxs)),
                    _Fan(sinks) if sinks else None,
                    None,
                )
        self._ctx = ctx

    def __enter__(self) -> "adopt":
        ctx = self._ctx
        if ctx is not None:
            st = self._st = _state()
            self._old = (st.req, st.sink, st.parent)
            st.req, st.sink, st.parent = ctx
        return self

    def __exit__(self, *exc) -> bool:
        if self._ctx is not None:
            st = self._st
            st.req, st.sink, st.parent = self._old
        return False


# -- the interpreter probe ---------------------------------------------------

PROBE_INTERVAL_S = 0.02


class LoopProbe:
    """One server loop's lag: ``call_later(20 ms)`` against the clock.
    Written by the loop's thread only."""

    __slots__ = ("index", "samples", "late_ns", "late_max_ns", "_handle", "_due")

    def __init__(self, index: int):
        self.index = index
        self.samples = self.late_ns = self.late_max_ns = 0
        self._handle = None
        self._due = 0

    def start(self, loop) -> None:
        """On the loop's own thread."""
        self._due = now() + int(PROBE_INTERVAL_S * 1e9)
        self._handle = loop.call_later(PROBE_INTERVAL_S, self._tick, loop)

    def _tick(self, loop) -> None:
        late = max(0, now() - self._due)
        self.samples += 1
        self.late_ns += late
        if late > self.late_max_ns:
            self.late_max_ns = late
        self.start(loop)

    def stop(self) -> None:
        """On the loop's own thread."""
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None


class _Probe:
    """The daemon thread; servers of one process share it (``start`` and
    ``stop`` count)."""

    def __init__(self):
        self._lk = threading.Lock()
        self._users = 0
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None
        self.samples = self.late_ns = self.late_max_ns = 0
        self.loops: "list[LoopProbe]" = []

    def start(self) -> None:
        with self._lk:
            self._users += 1
            if self._thread is None:
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, args=(self._stop,),
                    name="interp-probe", daemon=True,
                )
                self._thread.start()

    def stop(self) -> None:
        with self._lk:
            self._users = max(0, self._users - 1)
            if self._users or self._thread is None:
                return
            t, self._thread = self._thread, None
            self._stop.set()
        t.join(timeout=2)

    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def _run(self, stop: threading.Event) -> None:
        step = int(PROBE_INTERVAL_S * 1e9)
        while True:
            t0 = now()
            if stop.wait(PROBE_INTERVAL_S):
                return
            late = max(0, now() - t0 - step)
            self.samples += 1
            self.late_ns += late
            if late > self.late_max_ns:
                self.late_max_ns = late

    def add_loop(self, index: int) -> LoopProbe:
        cell = LoopProbe(index)
        with self._lk:
            self.loops = [c for c in self.loops if c.index != index] + [cell]
        return cell

    def snapshot(self) -> dict:
        def row(c) -> dict:
            return {
                "samples": c.samples,
                "late_seconds": round(c.late_ns / 1e9, 6),
                "late_max_seconds": round(c.late_max_ns / 1e9, 6),
            }

        out = row(self)
        out["interval_seconds"] = PROBE_INTERVAL_S
        out["loops"] = [
            dict(row(c), loop=c.index)
            for c in sorted(self.loops, key=lambda c: c.index)
        ]
        return out

    def reset(self) -> None:
        self.samples = self.late_ns = self.late_max_ns = 0
        for c in self.loops:
            c.samples = c.late_ns = c.late_max_ns = 0


PROBE = _Probe()


# -- reading -------------------------------------------------------------------


def snapshot() -> dict:
    """``{"spans": [...], "probe": {...}}`` for ``KernelStats.snapshot()``:
    the live threads' dicts merged with what exited threads left."""
    with _REG_LK:
        _fold_dead_locked()
        merged = {k: list(v) for k, v in _RETIRED.items()}
        states = list(_STATES)
    for st in states:
        for name, row in _items(st.counters):
            into = merged.setdefault((st.role, name), [0, 0, 0])
            for i in range(3):
                into[i] += row[i]
    return {
        "spans": [
            {
                "role": role,
                "name": name,
                "count": n,
                "wall_seconds": round(wall / 1e9, 6),
                "cpu_seconds": (
                    round(cpu / 1e9, 6) if name in CPU_SPANS else None
                ),
            }
            for (role, name), (n, wall, cpu) in sorted(merged.items())
        ],
        PROBE_NAME: PROBE.snapshot(),
    }


def reset() -> None:
    """Tests: zero every counter (the threads keep their dicts)."""
    with _REG_LK:
        _RETIRED.clear()
        for st in _STATES:
            st.counters.clear()
    PROBE.reset()
