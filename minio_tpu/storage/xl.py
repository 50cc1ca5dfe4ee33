"""XLStorage: local POSIX StorageAPI backend (cmd/xl-storage.go).

Disk layout (xl-storage-format-v2.go:71-83):

    <root>/.sys/tmp/<uuid>...            staging area (atomic renames)
    <root>/.sys/format.json              disk identity + set layout
    <root>/<bucket>/<object>/xl.meta     version journal (meta.XLMeta)
    <root>/<bucket>/<object>/<dataDir>/part.N   framed shard files

Crash consistency is by construction, like the reference: shard files and
metadata are staged under .sys/tmp and committed with a single directory
rename (rename_data, the analogue of xl-storage.go:2000 RenameData); a
crash leaves only garbage in tmp, never a torn object.
"""

from __future__ import annotations

import errno
import os
import shutil
import time
import uuid

from . import errors
from .api import DiskInfo, ShardReader, ShardWriter, StatInfo, StorageAPI, VolInfo
from ..utils import spans
from .meta import FileInfo, XLMeta

SYS_DIR = ".sys"
TMP_DIR = f"{SYS_DIR}/tmp"
XL_META = "xl.meta"

# One os.read of read_all: well above any xl.meta (336 bytes for a 10 MiB
# object at EC 8+4); a longer file takes more reads of the same size.
READ_CHUNK = 64 << 10

# read_all over every XLStorage of the process: [reads, refills (reads
# that needed more than one os.read), error_path (opens that failed and
# went to look at the volume)].  Plain adds under the GIL, no lock and no
# clock; system calls per read = 3 + refills / reads.  kernel-stats
# carries them as ``meta_read`` (codec/telemetry.py).
META_READ = [0, 0, 0]


def meta_read_counts() -> dict:
    reads, refills, error_path = META_READ
    return {"reads": reads, "refills": refills, "error_path": error_path}


# Removals that were told the names of what they remove, over every
# XLStorage of the process: [named (went by name), walked (found
# something else and fell back to the tree walk), calls (the os calls
# made by name, a fall-back's attempts included; what its walk then
# makes is not counted)].  Plain adds like META_READ; kernel-stats
# carries them as ``remove``.  named / (named + walked) is the share of
# removals that walked no tree.
REMOVE = [0, 0, 0]


def remove_counts() -> dict:
    named, walked, calls = REMOVE
    return {"named": named, "walked": walked, "calls": calls}


# The calls that write or open a shard - create_file, read_file_stream,
# rename_data, write_all - over every XLStorage of the process: [calls,
# asked (times one of them, its system call refused, went to look at a
# volume or ran makedirs to find out why)].  Plain adds like META_READ;
# kernel-stats carries them as ``drive_write``.  asked / calls is the
# share that left the path on which nothing is asked before the work.
DRIVE_WRITE = [0, 0]

# the errnos by which a system call says that a component of its path is
# not there: what sends a drive call to look at the volume and the parents
_NO_PARENT = (FileNotFoundError, NotADirectoryError)


def drive_write_counts() -> dict:
    calls, asked = DRIVE_WRITE
    return {"calls": calls, "asked": asked}


def _check_name(name: str) -> None:
    if not name or name.startswith("/") or ".." in name.split("/"):
        raise errors.FileAccessDenied(name)


class _FileShardWriter(ShardWriter):
    def __init__(self, path: str):
        self._f = open(path, "wb")

    @spans.spanned(spans.XL_SHARD_WRITE)
    def write(self, data: bytes) -> None:
        self._f.write(data)

    @spans.spanned(spans.XL_SHARD_FSYNC)
    def close(self) -> None:
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()


class _FileShardReader(ShardReader):
    def __init__(self, path: str):
        try:
            self._f = open(path, "rb")
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except IsADirectoryError:
            raise errors.IsNotRegular(path) from None

    @spans.spanned(spans.XL_SHARD_READ)
    def read_at(self, offset: int, length: int) -> bytes:
        self._f.seek(offset)
        return self._f.read(length)

    def close(self) -> None:
        self._f.close()


class XLStorage(StorageAPI):
    """One local disk rooted at ``root``."""

    def __init__(self, root: str, endpoint: str = ""):
        self.root = os.path.abspath(root)
        self._endpoint = endpoint or self.root
        self._tmp_root = os.path.join(self.root, TMP_DIR)
        os.makedirs(self._tmp_root, exist_ok=True)
        self._disk_id = ""

    # ---- identity / health ----------------------------------------------

    def is_online(self) -> bool:
        return os.path.isdir(self.root)

    def endpoint(self) -> str:
        return self._endpoint

    def is_local(self) -> bool:
        return True

    def disk_info(self) -> DiskInfo:
        st = os.statvfs(self.root)
        total = st.f_blocks * st.f_frsize
        free = st.f_bavail * st.f_frsize
        return DiskInfo(
            total=total,
            free=free,
            used=total - free,
            root_disk=False,
            endpoint=self._endpoint,
            mount_path=self.root,
            disk_id=self._disk_id,
        )

    def get_disk_id(self) -> str:
        return self._disk_id

    def set_disk_id(self, disk_id: str) -> None:
        self._disk_id = disk_id

    # ---- path helpers ---------------------------------------------------

    def _vol_path(self, volume: str) -> str:
        _check_name(volume)
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        vp = self._vol_path(volume)
        _check_name(path or "x")
        return os.path.join(vp, *path.split("/")) if path else vp

    def _require_vol(self, volume: str) -> str:
        vp = self._vol_path(volume)
        if not os.path.isdir(vp):
            raise errors.VolumeNotFound(volume)
        return vp

    def _ask(self, volume: str, directory: str) -> None:
        """What a write or an open does once its system call has said
        that a component of its path is missing: the volume first (a
        missing one, a file in its place and a lost root are
        VolumeNotFound, and nothing is made under them), then the
        parents, made on demand."""
        DRIVE_WRITE[1] += 1
        self._require_vol(volume)
        os.makedirs(directory, exist_ok=True)

    # ---- volumes --------------------------------------------------------

    def make_vol(self, volume: str) -> None:
        vp = self._vol_path(volume)
        try:
            os.makedirs(vp)
        except FileExistsError:
            # atomic exists-check: a concurrent MakeVol racing this
            # one must surface VolumeExists, not an OS error that the
            # quorum reducer would count as a disk failure
            raise errors.VolumeExists(volume) from None

    def list_vols(self) -> list[VolInfo]:
        out = []
        for name in sorted(os.listdir(self.root)):
            if name == SYS_DIR or name.startswith("."):
                continue
            full = os.path.join(self.root, name)
            if os.path.isdir(full):
                out.append(
                    VolInfo(name, int(os.stat(full).st_ctime_ns))
                )
        return out

    def stat_vol(self, volume: str) -> VolInfo:
        vp = self._require_vol(volume)
        try:
            return VolInfo(volume, int(os.stat(vp).st_ctime_ns))
        except FileNotFoundError:
            # a concurrent DeleteVol won between the isdir check and
            # the stat: a bucket-level outcome, never a raw errno
            raise errors.VolumeNotFound(volume) from None

    def delete_vol(self, volume: str, force: bool = False) -> None:
        vp = self._require_vol(volume)
        if force:
            # rmtree racing a concurrent deleter (root vanishes) or a
            # concurrent writer (an entry vanishes mid-walk) surfaces
            # ENOENT; both are linearizable outcomes, not disk faults
            # (storage-errors.go errno mapping)
            for _ in range(8):
                try:
                    shutil.rmtree(vp)
                    return
                except FileNotFoundError:
                    if not os.path.lexists(vp):
                        raise errors.VolumeNotFound(volume) from None
                    continue  # entry vanished mid-walk; retry
                except OSError as e:
                    if e.errno in (errno.ENOTEMPTY, errno.EEXIST):
                        continue  # writer re-filled a dir mid-walk
                    raise
            shutil.rmtree(vp, ignore_errors=True)
            if os.path.lexists(vp):
                raise errors.VolumeNotEmpty(volume)
            return
        try:
            os.rmdir(vp)
        except FileNotFoundError:
            raise errors.VolumeNotFound(volume) from None
        except OSError:
            raise errors.VolumeNotEmpty(volume) from None

    # ---- raw files ------------------------------------------------------

    def list_dir(self, volume: str, dir_path: str, count: int = -1) -> list[str]:
        self._require_vol(volume)
        full = self._file_path(volume, dir_path) if dir_path else self._vol_path(volume)
        try:
            names = sorted(os.listdir(full))
        except FileNotFoundError:
            raise errors.FileNotFound(dir_path) from None
        except NotADirectoryError:
            raise errors.IsNotRegular(dir_path) from None
        out = []
        for nm in names:
            if os.path.isdir(os.path.join(full, nm)):
                nm += "/"
            out.append(nm)
            if 0 <= count <= len(out):
                break
        return out

    @spans.spanned(spans.XL_READ_ALL)
    def read_all(self, volume: str, path: str) -> bytes:
        # open, read, close: three system calls for a file under
        # READ_CHUNK and none before them.  Each one gives the GIL away
        # and has to win it back from every other thread of the server,
        # so their number is the wall time of a metadata round; the
        # volume is looked at only once the open has failed
        full = self._file_path(volume, path)
        try:
            return self._read_whole(full)
        except _NO_PARENT as e:
            META_READ[2] += 1
            self._require_vol(volume)
            if isinstance(e, NotADirectoryError):
                raise  # a parent component is a regular file
            raise errors.FileNotFound(path) from None
        except IsADirectoryError:
            raise errors.IsNotRegular(path) from None

    @staticmethod
    def _read_whole(full: str) -> bytes:
        """The file's bytes by open, read, close; what the open or the
        read raises is the caller's to name."""
        META_READ[0] += 1
        fd = os.open(full, os.O_RDONLY)
        try:
            data = os.read(fd, READ_CHUNK)
            if len(data) == READ_CHUNK:
                # came back full: a long version journal, a large
                # format.json or bucket document; read on to the end
                META_READ[1] += 1
                chunks = [data]
                while len(chunks[-1]) == READ_CHUNK:
                    chunks.append(os.read(fd, READ_CHUNK))
                data = b"".join(chunks)
            return data
        finally:
            os.close(fd)

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        DRIVE_WRITE[0] += 1
        self._commit(volume, self._file_path(volume, path), data)

    @spans.spanned(spans.XL_WRITE_ALL)
    def _commit(self, volume: str, full: str, data: bytes) -> None:
        """``data`` becomes the file ``full`` of ``volume`` or the old
        file stays: written to a temp file in the staging area, fsynced,
        closed, then ``replace`` - five system calls and none in front.
        Whether the volume, the target's parents and the staging area
        are there is asked only once one of the five has said no."""
        tmp = os.path.join(self._tmp_root, f"wa-{uuid.uuid4().hex}")
        flags = os.O_WRONLY | os.O_CREAT | os.O_EXCL
        try:
            fd = os.open(tmp, flags, 0o666)
        except _NO_PARENT:
            # delete_file's parent cleanup can take the tmp area with the
            # last staging dir; a lost root is the volume's to report
            self._ask(volume, self._tmp_root)
            fd = os.open(tmp, flags, 0o666)
        try:
            try:
                left = memoryview(data)
                while left:
                    left = left[os.write(fd, left):]
                os.fsync(fd)
            finally:
                os.close(fd)
            try:
                os.replace(tmp, full)
            except _NO_PARENT:
                self._ask(volume, os.path.dirname(full))
                os.replace(tmp, full)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    @spans.spanned(spans.XL_DELETE_FILE)
    def delete_file(
        self,
        volume: str,
        path: str,
        recursive: bool = False,
        fi: "FileInfo | None" = None,
    ) -> None:
        if recursive and fi is not None:
            # the caller holds the names: no stat in front and no walk.
            # Whatever is not as named goes to the walk below, which
            # also finds the error class a caller is to see
            full = self._file_path(volume, path)
            data_only = (
                bool(fi.data_dir) and os.path.basename(full) == fi.data_dir
            )
            if self._remove_named(full, fi, data_only):
                if not data_only:
                    # a data dir's parent is its object, which lives on
                    REMOVE[2] += self._prune_parents(volume, full)
                return
        self._require_vol(volume)
        full = self._file_path(volume, path)
        try:
            if os.path.isdir(full):
                if recursive:
                    shutil.rmtree(full)
                else:
                    os.rmdir(full)
            else:
                os.remove(full)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        self._prune_parents(volume, full)

    def _remove_named(self, full: str, fi: FileInfo, data_only: bool) -> bool:
        """Remove the object directory ``full`` - or, with ``data_only``,
        the data dir of a living object that ``full`` is - by the names
        ``fi`` gives its files: the journal first (a crash leaves data
        without a journal, never a journal that names missing data),
        the parts, the data dir, the directory itself.  One system call
        a name, each of which gives the GIL away once; the walk makes
        seven for every one of these.  False once anything is not as
        named (another version's data dir, a stray file, no journal, no
        such object or volume): what is left is the walk's."""
        data_dir = full if data_only else (
            os.path.join(full, fi.data_dir) if fi.data_dir else ""
        )
        calls = 0
        try:
            if not data_only:
                calls += 1
                os.unlink(os.path.join(full, XL_META))
            if data_dir:
                for part in fi.parts:
                    calls += 1
                    try:
                        os.unlink(
                            os.path.join(data_dir, f"part.{part.number}")
                        )
                    except FileNotFoundError:
                        pass  # already gone: a heal may be in flight
                if not data_only:
                    calls += 1
                    os.rmdir(data_dir)
            calls += 1
            os.rmdir(full)
        except OSError:
            went = False
        else:
            went = True
        REMOVE[0 if went else 1] += 1
        REMOVE[2] += calls
        return went

    def _prune_parents(self, volume: str, full: str) -> int:
        """Remove the now-empty parents of ``full`` up to the volume
        root (deleteFile, xl-storage.go parent cleanup); the number of
        rmdir calls it took."""
        parent = os.path.dirname(full)
        vol = self._vol_path(volume)
        calls = 0
        while parent != vol:
            calls += 1
            try:
                os.rmdir(parent)
            except OSError:
                break
            parent = os.path.dirname(parent)
        return calls

    def rename_file(
        self, src_volume: str, src_path: str, dst_volume: str, dst_path: str
    ) -> None:
        self._require_vol(src_volume)
        self._require_vol(dst_volume)
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        if not os.path.exists(src):
            raise errors.FileNotFound(src_path)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        os.replace(src, dst)

    def stat_file(self, volume: str, path: str) -> StatInfo:
        self._require_vol(volume)
        try:
            st = os.stat(self._file_path(volume, path))
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        return StatInfo(
            size=st.st_size,
            mod_time_ns=st.st_mtime_ns,
            is_dir=os.path.isdir(self._file_path(volume, path)),
        )

    # ---- shard streams --------------------------------------------------

    def create_file(self, volume: str, path: str) -> ShardWriter:
        # a staged shard (tmp/<id>/<data_dir>/part.N, tmp/<id>/part.N):
        # the staging area is this drive's own and there since boot, so
        # the directories under it are made top down, one mkdir each, and
        # the file opened: no stat.  The first that is there already (a
        # heal's second part) says the rest is too.  Any other path: the
        # open first.  A call that says a parent is missing sends the
        # drive to look at the volume and make the parents, once
        full = self._file_path(volume, path)
        DRIVE_WRITE[0] += 1
        try:
            made = self._tmp_root
            if full.startswith(made + os.sep):
                for name in full[len(made) + 1:].split(os.sep)[:-1]:
                    made = os.path.join(made, name)
                    try:
                        os.mkdir(made)
                    except FileExistsError:
                        break
            return _FileShardWriter(full)
        except _NO_PARENT:
            self._ask(volume, os.path.dirname(full))
            return _FileShardWriter(full)

    def read_file_stream(self, volume: str, path: str) -> ShardReader:
        DRIVE_WRITE[0] += 1
        try:
            return _FileShardReader(self._file_path(volume, path))
        except (errors.FileNotFound, NotADirectoryError):
            # no such file, or no such volume: the volume is asked now
            DRIVE_WRITE[1] += 1
            self._require_vol(volume)
            raise

    # ---- object metadata ------------------------------------------------

    def read_xl(self, volume: str, path: str) -> XLMeta:
        raw = self.read_all(volume, f"{path}/{XL_META}")
        return XLMeta.from_bytes(raw, volume, path)

    @spans.spanned(spans.XL_READ_VERSION)
    def read_version(
        self, volume: str, path: str, version_id: str = ""
    ) -> FileInfo:
        xl = self.read_xl(volume, path)
        fi = xl.find(version_id)
        fi.volume, fi.name = volume, path
        return fi

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        try:
            xl = self.read_xl(volume, path)
        except errors.FileNotFound:
            xl = XLMeta()
        xl.add_version(fi)
        self.write_all(volume, f"{path}/{XL_META}", xl.to_bytes())

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        xl = self.read_xl(volume, path)  # must exist
        xl.add_version(fi)
        self.write_all(volume, f"{path}/{XL_META}", xl.to_bytes())

    @spans.spanned(spans.XL_DELETE_VERSION)
    def delete_version(self, volume: str, path: str, fi: FileInfo) -> None:
        xl = self.read_xl(volume, path)
        victim = xl.delete_version(fi.version_id)
        if victim.data_dir:
            try:
                self.delete_file(
                    volume, f"{path}/{victim.data_dir}", recursive=True
                )
            except errors.FileNotFound:
                pass
        if xl.versions:
            self.write_all(volume, f"{path}/{XL_META}", xl.to_bytes())
        else:
            self.delete_file(volume, f"{path}/{XL_META}")

    @spans.spanned(spans.XL_RENAME_DATA)
    def rename_data(
        self,
        src_volume: str,
        src_path: str,
        fi: FileInfo,
        dst_volume: str,
        dst_path: str,
    ) -> None:
        # mkdir the object's directory, rename the data dir into it, read
        # the journal, commit the new one: the calls that do the work and
        # no stat in front of them.  What stood in front (the volumes, the
        # staging dir, the staged data dir, the parents) is asked in that
        # order once a call has said no, so a caller sees the same error
        src_dir = self._file_path(src_volume, src_path)
        dst_obj = self._file_path(dst_volume, dst_path)
        DRIVE_WRITE[0] += 1
        try:
            os.mkdir(dst_obj)
            made = True
        except FileExistsError:
            made = False  # an overwrite, another version, a heal
        except _NO_PARENT:
            self._ask(dst_volume, dst_obj)  # a key with parents
            made = True
        try:
            if fi.data_dir:
                self._move_data_dir(
                    src_volume, src_path, dst_volume, dst_obj, fi.data_dir
                )
            elif not os.path.isdir(src_dir):
                # nothing to move, so nothing to fail: the one question
                DRIVE_WRITE[1] += 1
                self._require_vol(src_volume)
                raise errors.FileNotFound(src_path)
            # merge + commit version journal.  The object's directory was
            # made or found above: no journal in it is a new key
            meta_path = os.path.join(dst_obj, XL_META)
            try:
                xl = XLMeta.from_bytes(
                    self._read_whole(meta_path), dst_volume, dst_path
                )
            except FileNotFoundError:
                xl = XLMeta()
            except IsADirectoryError:
                raise errors.IsNotRegular(f"{dst_path}/{XL_META}") from None
            xl.add_version(fi)
            self._commit(dst_volume, meta_path, xl.to_bytes())
        except BaseException:
            if made:
                # nothing of the object came to be: no empty directory
                # is left for a listing to show as a prefix
                try:
                    os.rmdir(dst_obj)
                    self._prune_parents(dst_volume, dst_obj)
                except OSError:
                    pass
            raise
        # the staging dir's one entry was moved out above: one rmdir, and
        # the walk only where something else is still in it
        REMOVE[2] += 1
        try:
            os.rmdir(src_dir)
            REMOVE[0] += 1
        except OSError:
            REMOVE[1] += 1
            shutil.rmtree(src_dir, ignore_errors=True)

    def _move_data_dir(
        self,
        src_volume: str,
        src_path: str,
        dst_volume: str,
        dst_obj: str,
        data_dir: str,
    ) -> None:
        """rename_data's one rename, ``<staging dir>/<data_dir>`` into
        the object's directory, with nothing in front of it."""
        src_dir = self._file_path(src_volume, src_path)
        staged = os.path.join(src_dir, data_dir)
        dst_data = os.path.join(dst_obj, data_dir)
        try:
            os.replace(staged, dst_data)
        except _NO_PARENT:
            DRIVE_WRITE[1] += 1
            self._require_vol(src_volume)
            if not os.path.isdir(src_dir):
                raise errors.FileNotFound(src_path) from None
            if not os.path.isdir(staged):
                raise errors.FileNotFound(f"{src_path}/{data_dir}") from None
            # the source is whole: the object's directory went meanwhile
            self._require_vol(dst_volume)
            os.makedirs(dst_obj, exist_ok=True)
            os.replace(staged, dst_data)
        except OSError as e:
            if e.errno not in (errno.ENOTEMPTY, errno.EEXIST):
                raise
            # the same data dir once more (a heal, a retried PUT)
            shutil.rmtree(dst_data)
            os.replace(staged, dst_data)

    # ---- maintenance ----------------------------------------------------

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        """Deep scan every part's framed blocks against its digests."""
        from ..codec import bitrot
        from ..codec.erasure import Erasure

        er = Erasure(
            fi.erasure.data_blocks,
            fi.erasure.parity_blocks,
            fi.erasure.block_size,
        )
        for part in fi.parts:
            rel = f"{path}/{fi.data_dir}/part.{part.number}"
            rd = self.read_file_stream(volume, rel)
            try:
                nblocks = er.block_count(part.size)
                for b in range(nblocks):
                    block_len = min(
                        er.block_size, part.size - b * er.block_size
                    )
                    shard_len = er.shard_size_padded(block_len)
                    frame = bitrot.DIGEST_SIZE + shard_len
                    buf = rd.read_at(er.shard_block_offset(b), frame)
                    if len(buf) != frame:
                        raise errors.FileCorrupt(
                            f"{rel}: truncated block {b}"
                        )
                    if not bitrot.verify_block(
                        buf[bitrot.DIGEST_SIZE :],
                        buf[: bitrot.DIGEST_SIZE],
                    ):
                        raise errors.FileCorrupt(f"{rel}: bitrot block {b}")
            finally:
                rd.close()

    def append_file(
        self,
        volume: str,
        path: str,
        data: bytes,
        truncate: bool = False,
        offset: "int | None" = None,
    ) -> None:
        """Append shard bytes; with ``offset``, idempotently.

        A remote writer whose response was lost retries the same append;
        writing at the *declared* offset (truncating any bytes past it)
        makes the retry converge instead of duplicating shard data
        (advisor finding r2).  Only one writer ever owns a staging file,
        so the truncate cannot race another append.
        """
        self._require_vol(volume)
        fp = self._file_path(volume, path)
        os.makedirs(os.path.dirname(fp), exist_ok=True)
        if offset is not None:
            try:
                size = os.path.getsize(fp)
            except OSError:
                size = 0
            if truncate:
                offset = 0
            if size < offset:
                raise errors.FileCorrupt(
                    f"{path}: append at {offset} but file has {size}"
                )
            with open(fp, "r+b" if size else "wb") as f:
                f.truncate(offset)
                f.seek(offset)
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            return
        with open(fp, "wb" if truncate else "ab") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())

    def walk(self, volume: str, prefix: str = ""):
        """Yield object paths (dirs containing xl.meta) under prefix."""
        vol = self._require_vol(volume)
        base = (
            os.path.join(vol, *prefix.split("/")) if prefix else vol
        )
        if not os.path.isdir(base):
            return
        for dirpath, dirnames, filenames in os.walk(base):
            if XL_META in filenames:
                rel = os.path.relpath(dirpath, vol).replace(os.sep, "/")
                dirnames[:] = []  # don't descend into data dirs
                yield rel

    # ---- ordered bounded walk (tree-walk.go analogue) -------------------

    def walk_sorted(
        self,
        volume: str,
        prefix: str = "",
        marker: str = "",
        recursive: bool = True,
        inclusive: bool = False,
    ):
        """Yield ``(name, is_prefix)`` in lexical order, lazily.

        The scalable-listing primitive (cmd/tree-walk.go doTreeWalk):
        directories are read in sorted order and subtrees that cannot
        contain a name matching ``prefix`` and > ``marker`` are pruned,
        so one page of results touches only the directories it needs.
        ``recursive=False`` lists a single level (delimiter="/" mode):
        plain directories come back once as ("dir/", True) without
        descending.  ``inclusive`` keeps names equal to the marker
        (version listings re-visit the marker key).
        """
        vol = self._require_vol(volume)
        if recursive:
            yield from self._walk_rec(vol, "", prefix, marker, inclusive)
            return
        base, _, leaf = prefix.rpartition("/")
        base_fs = (
            os.path.join(vol, *base.split("/")) if base else vol
        )
        if base and os.path.isfile(os.path.join(base_fs, XL_META)):
            # the prefix points INSIDE an object's directory: its
            # children are erasure data dirs, not namespace entries -
            # leaking them as CommonPrefixes exposes internal layout
            return
        try:
            entries = sorted(os.listdir(base_fs))
        except (FileNotFoundError, NotADirectoryError):
            return
        basep = base + "/" if base else ""
        for e in entries:
            if leaf and not e.startswith(leaf):
                continue
            full = os.path.join(base_fs, e)
            if not os.path.isdir(full):
                continue
            if os.path.isfile(os.path.join(full, XL_META)):
                name = basep + e
                if name > marker or (inclusive and name == marker):
                    yield (name, False)
            else:
                cp = basep + e + "/"
                if cp > marker:
                    yield (cp, True)

    def _walk_rec(self, vol, rel, prefix, marker, inclusive):
        base = os.path.join(vol, *rel.split("/")) if rel else vol
        try:
            entries = sorted(os.listdir(base))
        except (FileNotFoundError, NotADirectoryError):
            return
        for e in entries:
            name = f"{rel}/{e}" if rel else e
            full = os.path.join(base, e)
            if not os.path.isdir(full):
                continue
            if os.path.isfile(os.path.join(full, XL_META)):
                if prefix and not name.startswith(prefix):
                    continue
                if name > marker or (inclusive and name == marker):
                    yield (name, False)
                continue  # object dirs hold data dirs, not children
            sub = name + "/"
            # prefix prune: the subtree's names all start with `sub`
            if prefix and not (
                sub.startswith(prefix) or prefix.startswith(sub)
            ):
                continue
            # marker prune: every name under `sub` is < marker exactly
            # when marker doesn't extend `sub` and sorts after it
            if (
                marker
                and not marker.startswith(sub)
                and sub < marker
            ):
                continue
            yield from self._walk_rec(vol, name, prefix, marker, inclusive)

    # ---- staging helpers (object-layer use) -----------------------------

    def new_tmp_dir(self) -> str:
        """Unique staging path inside this disk's tmp area."""
        return f"{TMP_DIR}/{uuid.uuid4().hex}"

    def clean_tmp(self, tmp_path: str) -> None:
        full = os.path.join(self.root, *tmp_path.split("/"))
        shutil.rmtree(full, ignore_errors=True)
