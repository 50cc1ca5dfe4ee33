"""Background healing: MRF queue, heal routine, fresh-disk monitor.

The reference splits this across three pieces that we mirror:

- MRF ("most recently failed", erasure.go:40-45 + addPartial,
  erasure-object.go:999): writes that missed some disks enqueue the
  object for immediate heal instead of waiting for a crawl.  An entry
  whose missing shards all lie on drives that are gone could write
  nothing, so it is set aside until one of those drives answers again
  (healMRFRoutine waits for disksConnectEvent the same way).
- Heal routine (healRoutine.run, background-heal-ops.go:77): one
  background consumer draining heal tasks against the object layer,
  throttled so it cannot starve foreground I/O.
- Fresh-disk monitor (monitorLocalDisksAndHeal,
  background-newdisks-heal-ops.go:124 + healErasureSet,
  global-heal.go:92): detects a replaced/wiped drive (format.json gone),
  re-stamps it from the set's reference format, and sweeps the set's
  buckets + objects through the heal queue so the new drive converges
  with no operator action.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import weakref

from ..utils import spans
from ..utils.log import kv, logger

_log = logger("heal")

# kernel-stats.heal: what the object layer handed the MRF queue, by why
# (``pushed``), and what the heal routine made of each task, by outcome -
# ``healed`` (a shard was written back), ``unwritable`` (shards are
# missing, but every drive that misses one is still gone: nothing could be
# written), ``clean`` (nothing was missing), ``failed`` (it raised) - with
# the tasks' wall seconds and the shard bytes they read and wrote; and
# what the queue did with pushes that could write nothing (``parked``):
# ``set_aside`` (kept out of the FIFO until a drive it waits for
# answers), ``absorbed`` (its object was already set aside), ``requeued``
# (a drive answered: back into the FIFO).  One add a push, a few a task;
# never a byte.
PUSH_SOURCES = ("partial_write", "bitrot_read", "degraded_read", "crawl")
OUTCOMES = ("healed", "unwritable", "clean", "failed")
PARK_EVENTS = ("set_aside", "absorbed", "requeued")
PUSHES = dict.fromkeys(PUSH_SOURCES, 0)
TASKS = dict.fromkeys(OUTCOMES, 0)
PARKED = dict.fromkeys(PARK_EVENTS, 0)
TASK_TOTALS = {"seconds": 0.0, "read_bytes": 0, "written_bytes": 0}
_COUNT_LK = threading.Lock()  # pushes come from every handler thread
_QUEUES: "weakref.WeakSet[HealQueue]" = weakref.WeakSet()


def pushed(source: str) -> None:
    """One object handed to the heal queue's hook, and why."""
    with _COUNT_LK:
        PUSHES[source] += 1


def _parked(event: str, n: int = 1) -> None:
    with _COUNT_LK:
        PARKED[event] += n


def _task_done(outcome: str, seconds: float, result: dict) -> None:
    with _COUNT_LK:
        TASKS[outcome] += 1
        TASK_TOTALS["seconds"] += seconds
        TASK_TOTALS["read_bytes"] += result.get("read_bytes", 0)
        TASK_TOTALS["written_bytes"] += result.get("written_bytes", 0)


def _outcome_of(result: dict) -> str:
    """A heal result's outcome (``heal_object``'s or ``heal_bucket``'s)."""
    if result.get("healed"):
        return "healed"
    return "unwritable" if result.get("offline") else "clean"


def heal_counts() -> dict:
    """``kernel-stats.heal``; ``queue_depth`` (queued + set aside) and
    ``parked_depth`` are read now, every queue's."""
    with _COUNT_LK:
        out = {
            "pushed": dict(PUSHES),
            "tasks": dict(TASKS),
            "parked": dict(PARKED),
            **TASK_TOTALS,
        }
    out["seconds"] = round(out["seconds"], 6)
    queues = list(_QUEUES)
    out["queue_depth"] = sum(len(q) for q in queues)
    out["parked_depth"] = sum(q.parked_depth() for q in queues)
    return out


@dataclasses.dataclass(frozen=True)
class HealTask:
    """One unit of heal work; object == "" heals the bucket only."""

    bucket: str
    object: str = ""
    version_id: str = ""


class HealQueue:
    """Deduplicating FIFO feeding the heal routine (the healTask
    channel analogue, bounded by dedup rather than depth), and beside it
    the entries set aside: tasks that could write nothing, each keyed
    once and held until a drive it waits for answers again."""

    def __init__(self):
        self._mu = threading.Lock()
        self._cond = threading.Condition(self._mu)
        self._queue: collections.deque = collections.deque()
        self._pending: set = set()
        self._inflight = 0
        # task -> the drives it waits for; drive -> the tasks waiting on it
        self._parked: "dict[HealTask, tuple]" = {}
        self._waiters: "dict[object, set[HealTask]]" = {}
        _QUEUES.add(self)

    def push(self, task: HealTask, waits: tuple = ()) -> None:
        """Queue ``task``.  ``waits`` names the drives that hold every
        shard the pusher found wanting, when each of them was offline: a
        heal could write nothing now, so the task is set aside until one
        of them answers.  A push with nothing to wait for can write, and
        runs at once even where the object was set aside."""
        with self._cond:
            if task in self._pending:
                return
            if waits:
                self._park(task, waits)
                return
            if task in self._parked:
                self._unpark(task)
            self._enqueue(task)

    def push_object(
        self, bucket: str, object_name: str, version_id: str = "",
        waits: tuple = (),
    ) -> None:
        self.push(HealTask(bucket, object_name, version_id), waits)

    def _enqueue(self, task: HealTask) -> None:
        self._pending.add(task)
        self._queue.append(task)
        self._cond.notify()

    def _park(self, task: HealTask, waits: tuple) -> None:
        held = self._parked.get(task)
        if held is None:
            self._parked[task] = tuple(waits)
            new = waits
            _parked("set_aside")
        else:
            new = tuple(d for d in waits if d not in held)
            if new:
                self._parked[task] = held + new
            _parked("absorbed")
        for d in new:
            self._waiters.setdefault(d, set()).add(task)

    def _unpark(self, task: HealTask) -> None:
        for d in self._parked.pop(task):
            tasks = self._waiters.get(d)
            if tasks is not None:
                tasks.discard(task)
                if not tasks:
                    del self._waiters[d]

    def requeue_answered(self) -> int:
        """Ask each drive that set-aside tasks wait for whether it is
        online (once a drive, whatever the number of tasks on it), and
        put every task waiting on one that answers back into the FIFO.
        Returns how many went back."""
        if not self._waiters:
            return 0
        with self._mu:
            drives = list(self._waiters)
        back = [d for d in drives if _answers(d)]
        if not back:
            return 0
        moved = 0
        with self._cond:
            for d in back:
                for task in list(self._waiters.get(d, ())):
                    self._unpark(task)
                    if task not in self._pending:
                        self._enqueue(task)
                        moved += 1
        if moved:
            _parked("requeued", moved)
        return moved

    def pop(self, timeout: "float | None" = None) -> "HealTask | None":
        with self._cond:
            if not self._queue and not self._cond.wait(timeout):
                return None
            if not self._queue:
                return None
            task = self._queue.popleft()
            self._pending.discard(task)
            # in-flight until task_done(): popped-but-unprocessed tasks
            # must keep drain() waiting (no gap where the queue looks
            # empty while a heal is mid-run)
            self._inflight += 1
            return task

    def task_done(self) -> None:
        with self._mu:
            self._inflight -= 1

    def idle(self) -> bool:
        """Nothing queued, nothing running, and no set-aside task waits
        on a drive that answers again (those go back into the FIFO)."""
        self.requeue_answered()
        with self._mu:
            return not self._queue and self._inflight == 0

    def parked_depth(self) -> int:
        with self._mu:
            return len(self._parked)

    def __len__(self) -> int:
        """Objects awaiting a heal: queued and set aside."""
        with self._mu:
            return len(self._queue) + len(self._parked)


def _answers(drive) -> bool:
    """Whether a drive a set-aside task waits for is online again
    (``DiskIDCheck`` answers from its last look, once an interval)."""
    try:
        return bool(drive.is_online())
    except Exception as exc:  # noqa: BLE001 - the heal routine keeps running
        _log.debug("liveness probe failed", extra=kv(err=str(exc)))
        return False


class HealRoutine:
    """Daemon consumer draining the queue against the object layer."""

    def __init__(
        self,
        object_layer,
        queue: HealQueue,
        throttle_s: float = 0.0,
    ):
        self._ol = object_layer
        self.queue = queue
        self._throttle = throttle_s
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None
        self.healed = 0  # counters for admin/metrics
        self.failed = 0

    def start(self) -> "HealRoutine":
        self._thread = threading.Thread(
            target=self._run, name="heal-routine", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Wait for the queue to empty (tests / admin heal barrier)."""
        import time

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.queue.idle():
                return True
            time.sleep(0.05)
        return False

    def _run(self) -> None:
        while not self._stop.is_set():
            # once a wake: set-aside tasks whose drive answered go back
            self.queue.requeue_answered()
            task = self.queue.pop(timeout=0.25)
            if task is None:
                continue
            sp = spans.span(spans.HEAL_OBJECT)
            try:
                with sp:
                    if task.object:
                        result = self._ol.heal_object(
                            task.bucket, task.object, task.version_id
                        )
                    else:
                        result = self._ol.heal_bucket(task.bucket)
                self.healed += 1
                result = result if isinstance(result, dict) else {}
                _task_done(_outcome_of(result), sp.seconds, result)
            except Exception as e:  # noqa: BLE001 - retried by later triggers
                self.failed += 1
                _task_done("failed", sp.seconds, {})
                from ..utils import log

                log.logger("heal").warning(
                    "heal task failed",
                    extra=log.kv(
                        bucket=task.bucket,
                        object=task.object,
                        error=f"{type(e).__name__}: {e}",
                    ),
                )
            finally:
                self.queue.task_done()
            import os

            # config seam: runtime-editable via admin set-config-kv;
            # a malformed value must never kill this thread
            try:
                throttle = float(
                    os.environ.get("MINIO_TPU_HEAL_THROTTLE_S")
                    or self._throttle
                )
            except ValueError:
                throttle = self._throttle
            if throttle:
                self._stop.wait(throttle)


class FreshDiskMonitor:
    """Detects wiped/replaced local drives and heals them back in.

    Every interval, each LOCAL disk of every set is probed for its
    format document.  A reachable disk without one is a replacement:
    it is re-stamped with the uuid its slot records in the set's
    reference format (HealFormat semantics, erasure-sets.go:1328), and
    the set's namespace is swept into the heal queue (healErasureSet).
    """

    def __init__(
        self,
        zones_layer,
        queue: HealQueue,
        interval_s: float = 10.0,
    ):
        self._zones = zones_layer
        self._queue = queue
        self._interval = interval_s
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None
        self.stamped = 0

    def start(self) -> "FreshDiskMonitor":
        self._thread = threading.Thread(
            target=self._run, name="fresh-disk-monitor", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _effective_interval(self) -> float:
        import os

        try:
            v = float(
                os.environ.get("MINIO_TPU_FRESH_DISK_INTERVAL_S")
                or self._interval
            )
        except ValueError:
            return self._interval
        return v if v >= 1.0 else max(self._interval, 1.0)

    def _run(self) -> None:
        while not self._stop.wait(self._effective_interval()):
            try:
                self.scan_once()
            except Exception as exc:
                _log.warning("background heal scan failed", extra=kv(err=str(exc)))

    def scan_once(self) -> int:
        """One probe pass; returns how many fresh disks were stamped."""
        from ..objectlayer.format import (
            FormatErasure,
            read_format,
            write_format,
        )

        stamped = 0
        for zone in getattr(self._zones, "zones", [self._zones]):
            ref = getattr(zone, "format_ref", None)
            if ref is None:
                continue
            for s_idx, eset in enumerate(zone.sets):
                fresh: list[int] = []
                for d_idx, disk in enumerate(eset.disks):
                    if disk is None:
                        continue
                    # probe THROUGH the decorator chain (DiskIDCheck,
                    # MeteredDisk - in either stacking order) to the
                    # raw disk: the ID check (rightly) fails every op
                    # on an unformatted drive, but this monitor's
                    # whole job is resurrecting exactly those drives
                    raw = disk
                    while True:
                        inner = (
                            raw.__dict__.get("unwrapped")
                            if hasattr(raw, "__dict__")
                            else None
                        )
                        if inner is None:
                            break
                        raw = inner
                    # stamped at boot (load_or_init_format hole fill):
                    # still needs its set swept
                    if getattr(raw, "_freshly_stamped", False):
                        raw._freshly_stamped = False
                        fresh.append(d_idx)
                        continue
                    if not raw.is_local() or not raw.is_online():
                        continue
                    try:
                        fmt = read_format(raw)
                    except Exception:  # noqa: BLE001
                        continue  # corrupt format: operator decision
                    if fmt is not None:
                        continue
                    # replaced drive: restore staging vol + identity
                    # (write_format recreates .sys itself)
                    try:
                        write_format(
                            raw,
                            FormatErasure(
                                id=ref.id,
                                this=ref.sets[s_idx][d_idx],
                                sets=ref.sets,
                            ),
                        )
                        fresh.append(d_idx)
                        stamped += 1
                    except Exception:  # noqa: BLE001
                        continue
                if fresh:
                    self._sweep_set(eset)
        self.stamped += stamped
        return stamped

    def _sweep_set(self, eset) -> None:
        """Enqueue every bucket + object of the set for heal
        (healErasureSet, global-heal.go:92)."""
        buckets: dict[str, None] = {}
        for disk in eset.disks:
            if disk is None or not disk.is_online():
                continue
            try:
                for v in disk.list_vols():
                    buckets[v.name] = None
            except Exception:  # noqa: BLE001
                continue
        for bucket in buckets:
            self._queue.push(HealTask(bucket))
            names: dict[str, None] = {}
            for disk in eset.disks:
                if disk is None or not disk.is_online():
                    continue
                try:
                    for name in disk.walk(bucket):
                        names[name] = None
                except Exception:  # noqa: BLE001
                    continue
            for name in names:
                self._queue.push(HealTask(bucket, name))
