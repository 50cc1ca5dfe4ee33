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
   handler, iopool, batcher, warmer, crawler, probe, other) comes from
   its name.  ``KernelStats.snapshot()`` merges them into
   ``kernel-stats.spans`` and the probe below into ``kernel-stats.probe``.
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

Four tables beside ``spans`` and ``probe`` account a request's wall and the
server's CPU, each to 100 %; the first two are kept always, the last two
are made when a snapshot is asked for and cost the hot path nothing:

``kernel-stats.requests`` - *self time by verb, on the request's own thread.*
    A span's self time is its wall less the wall of the spans opened under
    it on the same thread (each span remembers the one it was opened under
    and adds its wall to that one's ``_under`` when it ends: two or three
    attribute writes).  Between ``begin_request`` and ``end_request`` the
    thread that called them keeps ``name -> [count, self_ns]`` in one small
    dict, which ``end_request(verb, queue_wait_ns)`` folds into the row of
    the S3 API call the handler resolved (``other`` if none), with the
    root's own wall and CPU readings: no clock is read for it.  A verb's
    self times add up to its wall to the nanosecond.  The root's own self
    time and the four ``ol_*`` spans' are the time inside no named child:
    the map's blind spot, reported.  Spans of other threads (an iopool job,
    the flush, a read-ahead) are not subtracted - the request's thread was
    in some span of its own meanwhile, waiting - and ``wait()`` hand-overs
    are no part of the nesting.
``kernel-stats.fanout`` - *the drive fan-outs by the job that ended them.*
    A PUT waits three times for jobs that run twelve abreast (a batch's
    shard writes to quorum, the writers' close, ``rename_data``), a GET
    once a block group; summing the jobs' queue waits counts twelve side
    by side twelve times.  ``fanout_done(phase, wall_ns, job)`` adds the
    wait's wall (its span's own reading) and, from three stamps an
    ``IOFuture`` gets for free, the queue wait and the run of the one job
    whose completion ended the wait.  A late start is the hand-off and the
    GIL; a long run is the drive's own calls.
``kernel-stats.cpu`` - *the process's CPU by thread role.*  Why not from a
    clock reading a span: that is the 5.6 us above, thousands of times a
    second, and it sees only the threads that open spans.  The scheduler
    keeps the account anyway, for every thread: ``_cpu_table`` reads each
    Python thread's CPU clock by its tid when a snapshot is taken (the
    number ``/proc/self/task/<tid>/schedstat`` prints) and adds what each
    burnt since the last snapshot to its role, beside ``process_seconds``;
    what the process burnt and no Python thread did is ``native``: XLA's
    and PJRT's pools.
``kernel-stats.loops`` - the handler threads' ``s3_request`` count and
    ``aio_queue_wait`` by the ``aio<N>`` of their names, before the merge by
    role throws it away: which loops a run's connections hashed onto.

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
PUT_CLOSE_WAIT = "put_close_wait"  # a PUT's wait for its writers' close, every drive
PUT_RENAME_WAIT = "put_rename_wait"  # ... and for rename_data, every drive
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


# the object layer's own spans: with the root's, their self time is the time
# inside no named child - kernel-stats.requests' blind spot
OL_SPANS = (OL_PUT_OBJECT, OL_GET_OBJECT, OL_GET_OBJECT_INFO, OL_DELETE_OBJECT)



class phase:
    """The rows of ``kernel-stats.fanout``: a wait for drive jobs that ran
    abreast, named by its site (not spans: nothing opens them)."""

    PUT_FLUSH = "put_flush"  # ShardFlusher.flush: a batch's shard writes, to quorum
    PUT_CLOSE = "put_close"  # the writers' close, every drive
    PUT_RENAME = "put_rename"  # rename_data, every drive
    GET_READS = "get_reads"  # a block group's shard reads, to the k it decodes from
    OF_WAIT = {PUT_CLOSE_WAIT: PUT_CLOSE, PUT_RENAME_WAIT: PUT_RENAME}


def _role_of(thread_name: str) -> str:
    if thread_name.startswith("aio-loop"):
        return "loop"
    if thread_name.startswith("aio") and "-worker-" in thread_name:
        return "handler"
    if thread_name.startswith("iopool"):
        return "iopool"
    if thread_name.startswith("codec-batcher"):
        return "batcher"
    if thread_name.startswith("codec-warmer"):
        return "warmer"
    if thread_name.startswith("data-crawler"):
        return "crawler"
    if thread_name.startswith("interp-probe"):
        return "probe"
    return "other"


def _loop_of(thread_name: str) -> "int | None":
    """The ``N`` of a handler thread's ``aio<N>-worker-<i>``."""
    head = thread_name.partition("-worker-")[0]
    return int(head[3:]) if head[3:].isdigit() else None


# -- per-thread state ------------------------------------------------------


class _State:
    """One thread's counters and the request context it is working for."""

    __slots__ = (
        "thread", "role", "loop", "counters", "req", "sink", "parent",
        "handoff", "open", "acc", "root", "verbs",
    )

    def __init__(self, thread: threading.Thread):
        self.thread = thread
        self.role = _role_of(thread.name)
        self.loop = _loop_of(thread.name) if self.role == "handler" else None
        self.counters: "dict[str, list]" = {}
        self.req = ""
        self.sink = None  # the request's record list while admin trace listens
        self.parent = None  # the open span's record, same condition
        self.handoff = None  # (name, since_ns) waiting for its pick-up
        self.open = None  # the innermost span open on THIS thread
        # between begin_request and end_request, on the thread that called
        # them: name -> [count, self_ns] of the request's spans here, and the
        # [wall_ns, cpu_ns] of those no span encloses (the root)
        self.acc: "dict[str, list] | None" = None
        self.root = None
        # verb -> [count, wall_ns, cpu_ns, queue_wait_ns, {name: [count, self_ns]}]
        self.verbs: "dict[str, list]" = {}


_tls = threading.local()
_REG_LK = threading.Lock()
_STATES: "list[_State]" = []
# (role, name) -> [count, wall_ns, cpu_ns] of threads that have exited
_RETIRED: "dict[tuple[str, str], list]" = {}
_RETIRED_VERBS: "dict[str, list]" = {}  # their verb rows
_RETIRED_LOOPS: "dict[int, list]" = {}  # loop -> [requests, queue_wait_ns] of theirs
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


def _add_verbs(into: "dict[str, list]", verbs: "dict[str, list]") -> None:
    for verb, row in _items(verbs):
        have = into.get(verb)
        if have is None:
            have = into[verb] = [0, 0, 0, 0, {}]
        for i in range(4):
            have[i] += row[i]
        for name, (n, ns) in _items(row[4]):
            cell = have[4].setdefault(name, [0, 0])
            cell[0] += n
            cell[1] += ns


_ZERO = (0, 0, 0)


def _add_loop(into: "dict[int, list]", st: _State) -> None:
    if st.loop is not None:
        cell = into.setdefault(st.loop, [0, 0])
        cell[0] += st.counters.get(S3_REQUEST, _ZERO)[0]
        cell[1] += st.counters.get(AIO_QUEUE_WAIT, _ZERO)[1]


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
        _add_verbs(_RETIRED_VERBS, st.verbs)
        _add_loop(_RETIRED_LOOPS, st)
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

    __slots__ = (
        "name", "args", "t0", "wall_ns", "_st", "_c0", "_ann", "_rec",
        "_up", "_under",
    )

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
        # self time: what the spans opened under this one on this thread
        # take is theirs, the rest is this one's own
        self._up = st.open
        self._under = 0
        st.open = self
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
        up = st.open = self._up
        acc = st.acc
        if acc is not None:
            if up is None:
                st.root[0] += wall
                st.root[1] += cpu or 0
            row = acc.get(self.name)
            if row is None:
                acc[self.name] = [1, wall - self._under]
            else:
                row[0] += 1
                row[1] += wall - self._under
        if up is not None:
            up._under += wall
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
    st.open = None
    st.acc = {}
    st.root = [0, 0]
    return st.req


def request_id() -> str:
    return _state().req


def end_request(verb: str = "", queue_wait_ns: int = 0) -> "list[dict] | None":
    """Forget the request.  Its self times on this thread are folded into
    the row of ``verb`` (the S3 API call the handler resolved; anything
    else is ``other``), with the root's own wall and CPU readings and the
    wait in the handler queue that the caller holds.  Returns its records,
    rendered for ``trace_info``: the root first, offsets from the root's
    start, only spans that had ended when the root did."""
    st = _state()
    acc, st.acc = st.acc, None
    if acc:
        row = st.verbs.get(verb or "other")
        if row is None:
            row = st.verbs[verb or "other"] = [0, 0, 0, 0, {}]
        row[0] += 1
        row[1] += st.root[0]
        row[2] += st.root[1]
        row[3] += queue_wait_ns
        into = row[4]
        for name, (n, ns) in acc.items():
            cell = into.get(name)
            if cell is None:
                into[name] = [n, ns]
            else:
                cell[0] += n
                cell[1] += ns
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


# -- the drive fan-outs ---------------------------------------------------------

_FAN_LK = threading.Lock()
# phase -> [count, wall_ns, last_queue_ns, last_run_ns]
_FANOUT: "dict[str, list]" = {}


def fanout_done(phase: str, wall_ns: int, job) -> None:
    """A wait for jobs that ran abreast has ended after ``wall_ns``: add it
    to ``kernel-stats.fanout.<phase>`` with the queue wait and the run of
    ``job``, the one whose completion ended it (anything with the stamps
    ``queued_ns`` / ``started_ns`` / ``done_ns``, as an ``IOFuture`` keeps
    them).  A
    late start is the hand-off and the GIL, a long run the drive's calls."""
    with _FAN_LK:
        row = _FANOUT.get(phase)
        if row is None:
            row = _FANOUT[phase] = [0, 0, 0, 0]
        row[0] += 1
        row[1] += wall_ns
        if job is not None:
            row[2] += job.started_ns - job.queued_ns
            row[3] += job.done_ns - job.started_ns


# -- the process's CPU by thread role --------------------------------------------

_CPU_LK = threading.Lock()
_CPU_SEEN: "dict[int, int]" = {}  # tid -> on-CPU ns when last read
_CPU_ROLE: "dict[str, int]" = {}  # role -> ns, only ever added to


def _task_cpu_ns(tid: int) -> "int | None":
    """What the scheduler has charged the thread, in ns: its CPU clock
    ``MAKE_THREAD_CPUCLOCK(tid, CPUCLOCK_SCHED)``, which reads the
    ``sum_exec_runtime`` that ``/proc/self/task/<tid>/schedstat`` prints.
    The kernel looks the tid up, so one that has exited (or is another
    process's) is EINVAL here, not a dangling ``pthread_t``: None."""
    try:
        return time.clock_gettime_ns((~tid << 3) | 6)
    except OSError:
        return None


def _cpu_table() -> dict:
    """Read at snapshot time only, from the scheduler's books: nothing on
    any request's path.  A thread's CPU since it was last read goes to the
    role its name gives it now, so every row only grows and a thread that
    has exited keeps what it was last seen with.  ``native`` is what the
    process has burnt (``process_seconds``) and no Python thread has been
    seen with: the runtime's own threads (XLA's and PJRT's pools), and what
    a thread burnt between its last reading and its exit.

    Why the clocks and not ``/proc/self/task``: ``open`` / ``read`` /
    ``close`` each hand the GIL back, as does every ``readdir`` of a
    listing, and a thread that wants it back waits behind whoever runs
    (0.3 ms for 35 threads on an idle interpreter, 700 ms beside two that
    spin; listing ~130 tasks under ``mixed-10m`` on the chip's host: 170-180
    ms).  A clock reading keeps the GIL: a snapshot makes no system call
    that could lose it."""
    with _CPU_LK:
        seen = {}
        for t in threading.enumerate():
            tid = t.native_id
            ns = None if tid is None else _task_cpu_ns(tid)
            if ns is None:
                continue
            last = _CPU_SEEN.get(tid, 0)
            role = _role_of(t.name)
            # a tid that reads less than it did belongs to a new thread
            _CPU_ROLE[role] = _CPU_ROLE.get(role, 0) + (ns - last if ns >= last else ns)
            seen[tid] = ns
        _CPU_SEEN.clear()
        _CPU_SEEN.update(seen)
        process = time.process_time_ns()
        known = sum(v for k, v in _CPU_ROLE.items() if k != "native")
        # the threads were read a moment before the process: never shrink
        _CPU_ROLE["native"] = max(_CPU_ROLE.get("native", 0), process - known)
        out = {role: round(ns / 1e9, 6) for role, ns in sorted(_CPU_ROLE.items())}
    out["process_seconds"] = round(process / 1e9, 6)
    return out


# -- reading -------------------------------------------------------------------


def snapshot() -> dict:
    """``{"spans": [...], "probe": {...}, "requests": [...], "fanout":
    {...}, "cpu": {...}, "loops": [...]}`` for ``KernelStats.snapshot()``:
    the live threads' dicts merged with what exited threads left."""
    with _REG_LK:
        _fold_dead_locked()
        merged = {k: list(v) for k, v in _RETIRED.items()}
        verbs: "dict[str, list]" = {}
        _add_verbs(verbs, _RETIRED_VERBS)
        loops = {k: list(v) for k, v in _RETIRED_LOOPS.items()}
        states = list(_STATES)
    for c in PROBE.loops:  # a loop no connection landed on is a row too
        loops.setdefault(c.index, [0, 0])
    for st in states:
        for name, row in _items(st.counters):
            into = merged.setdefault((st.role, name), [0, 0, 0])
            for i in range(3):
                into[i] += row[i]
        _add_verbs(verbs, st.verbs)
        _add_loop(loops, st)
    with _FAN_LK:
        fans = {k: list(v) for k, v in sorted(_FANOUT.items())}
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
        # nine digits: a verb's self times add up to its wall to the ns
        "requests": [
            {
                "verb": verb,
                "count": n,
                "wall_seconds": round(wall / 1e9, 9),
                "cpu_seconds": round(cpu / 1e9, 9),
                "queue_wait_seconds": round(queue / 1e9, 9),
                "self": {
                    name: [c, round(ns / 1e9, 9)]
                    for name, (c, ns) in sorted(own.items())
                },
            }
            for verb, (n, wall, cpu, queue, own) in sorted(verbs.items())
        ],
        "fanout": {
            phase: {
                "count": n,
                "wall_seconds": round(wall / 1e9, 6),
                "last_queue_seconds": round(queue / 1e9, 6),
                "last_run_seconds": round(run / 1e9, 6),
            }
            for phase, (n, wall, queue, run) in fans.items()
        },
        "cpu": _cpu_table(),
        "loops": [
            {
                "loop": loop,
                "requests": n,
                "queue_wait_seconds": round(queue / 1e9, 6),
            }
            for loop, (n, queue) in sorted(loops.items())
        ],
    }


def reset() -> None:
    """Tests: zero every counter (the threads keep their dicts).  The CPU
    table is the scheduler's and stays."""
    with _REG_LK:
        _RETIRED.clear()
        _RETIRED_VERBS.clear()
        _RETIRED_LOOPS.clear()
        for st in _STATES:
            st.counters.clear()
            st.verbs.clear()
    with _FAN_LK:
        _FANOUT.clear()
    PROBE.reset()
