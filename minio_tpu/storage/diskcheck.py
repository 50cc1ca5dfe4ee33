"""Per-operation disk-ID validation (cmd/xl-storage-disk-id-check.go).

Wraps any StorageAPI so every I/O first confirms the drive still holds
the format document this slot was admitted with:

- format.json unreadable -> the drive was wiped/replaced with an empty
  one: ops fail DiskNotFound until the fresh-disk monitor re-stamps and
  heals it (heal/background.py FreshDiskMonitor);
- disk uuid mismatch -> a DIFFERENT formatted drive was mounted into
  this slot (cabling/mount mixups): ops fail immediately instead of
  scribbling one cluster's shards onto another's drive.

The on-disk read is rate-limited (default 1s); in between, ops pass
straight through.  Reconnect notes: remote disks already lazily
re-probe (storage/rest_client.py is_online backoff), and local disks
report offline while their root dir is missing - together with the
fresh-disk monitor this covers the reference's connectDisks loop
(erasure-sets.go:200-295) without a dedicated thread.

Whether a LOCAL drive is there is what that look last found
(``is_online``): format.json cannot be read under a root that is gone,
so the look already answers it, and between looks the answer is a field
read - no ``stat`` of the root a drive every time the object layer
takes its snapshot of live drives, three to five times a request.  A
call that fails in a way that blames the drive forgets the last look,
so staleness is bounded by one failed call as well as by the interval.
Liveness is a hint for masking, never a vote: quorums are counted from
what each drive answered.
"""

from __future__ import annotations

import errno
import threading
import time

from . import errors
from .xl import SYS_DIR

# is_online() over every DiskIDCheck of the process: [asked, looked
# (looks at the drive: format.json read, from is_online or from a
# wrapped call's check), reset (failed calls that blamed the drive and
# forced the next look)].  Plain adds under the GIL like xl.META_READ;
# kernel-stats carries them as ``liveness``.  looked / asked is the
# share of the liveness questions that still cost system calls.
LIVENESS = [0, 0, 0]

_NEVER = float("-inf")


def liveness_counts() -> dict:
    asked, looked, reset = LIVENESS
    return {"asked": asked, "looked": looked, "reset": reset}


class DiskIDCheck:
    """StorageAPI decorator validating the slot's disk identity."""

    # every method that touches the drive contents
    _CHECKED = frozenset(
        {
            "make_vol", "list_vols", "stat_vol", "delete_vol",
            "list_dir", "read_all", "write_all", "delete_file",
            "rename_file", "stat_file", "create_file", "append_file",
            "walk", "walk_sorted", "read_file_stream", "read_version",
            "read_xl", "write_metadata", "update_metadata",
            "delete_version", "rename_data", "verify_file",
        }
    )

    def __init__(self, disk, expected_id: str, check_interval_s: float = 1.0):
        self.unwrapped = disk
        self._expected = expected_id
        self._interval = check_interval_s
        self._mu = threading.Lock()
        self._last_check = _NEVER
        self._last_err: "Exception | None" = None
        # a remote drive's client keeps its own flag and back-off
        self._local = bool(disk.is_local())

    def _check(self) -> None:
        now = time.monotonic()
        with self._mu:
            if now - self._last_check < self._interval:
                if self._last_err is not None:
                    raise self._last_err
                return
            self._last_check = now
            LIVENESS[1] += 1
            err: "Exception | None" = None
            try:
                from ..objectlayer.format import read_format

                fmt = read_format(self.unwrapped)
            except Exception:  # noqa: BLE001
                err = errors.DiskNotFound(
                    "unformatted or unreadable disk (awaiting heal)"
                )
            else:
                if fmt is None:
                    err = errors.DiskNotFound(
                        "unformatted disk (awaiting heal)"
                    )
                elif fmt.this != self._expected:
                    err = errors.DiskNotFound(
                        f"disk ID mismatch: expected {self._expected}, "
                        f"found {fmt.this} - wrong drive mounted?"
                    )
            self._last_err = err
            if err is not None:
                raise err

    def is_online(self) -> bool:
        LIVENESS[0] += 1
        if not self._local and not self.unwrapped.is_online():
            return False
        try:
            self._check()
        except Exception:  # noqa: BLE001
            return False
        return True

    def _blames_drive(self, exc: Exception) -> bool:
        """Whether a failed call says the drive, not the object, is at
        fault.  A volume or a path that is not there may just not
        exist: the root is asked then, on this error path only."""
        if isinstance(exc, errors.DiskNotFound):
            return True
        if isinstance(exc, errors.VolumeNotFound):
            # every formatted drive has its system volume
            return exc.args[:1] == (SYS_DIR,) or not self.unwrapped.is_online()
        if isinstance(exc, OSError):
            return exc.errno == errno.EIO or (
                exc.errno in (errno.ENOENT, errno.ENOTDIR)
                and not self.unwrapped.is_online()
            )
        return False

    def _forget_last_look(self) -> None:
        with self._mu:
            self._last_check = _NEVER
        LIVENESS[2] += 1

    def __getattr__(self, name: str):
        attr = getattr(self.unwrapped, name)
        if name in self._CHECKED and callable(attr):
            def wrapped(*a, **k):
                self._check()
                try:
                    return attr(*a, **k)
                except (errors.StorageError, OSError) as e:
                    if self._blames_drive(e):
                        self._forget_last_look()
                    raise

            wrapped.__name__ = name
            return wrapped
        return attr
