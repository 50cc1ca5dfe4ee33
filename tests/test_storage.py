"""XLStorage local-disk backend tests (cmd/xl-storage_test.go intent).

Real temp-dir disks, no mocks - the reference's test style
(newErasureTestSetup, cmd/erasure_test.go).
"""

import builtins
import os
import shutil

import pytest

from minio_tpu.storage import errors
from minio_tpu.storage import xl as xl_mod
from minio_tpu.storage.meta import (
    ErasureInfo,
    FileInfo,
    ObjectPartInfo,
    XLMeta,
    new_version_id,
    now_ns,
)
from minio_tpu.storage.xl import XLStorage


@pytest.fixture
def disk(tmp_path):
    return XLStorage(str(tmp_path / "disk1"))


def test_volume_lifecycle(disk):
    disk.make_vol("bucket")
    with pytest.raises(errors.VolumeExists):
        disk.make_vol("bucket")
    assert [v.name for v in disk.list_vols()] == ["bucket"]
    disk.stat_vol("bucket")
    disk.delete_vol("bucket")
    with pytest.raises(errors.VolumeNotFound):
        disk.stat_vol("bucket")
    with pytest.raises(errors.VolumeNotFound):
        disk.delete_vol("nope")


def test_volume_not_empty(disk):
    disk.make_vol("b")
    disk.write_all("b", "x/y", b"data")
    with pytest.raises(errors.VolumeNotEmpty):
        disk.delete_vol("b")
    disk.delete_vol("b", force=True)


def test_path_traversal_rejected(disk):
    disk.make_vol("b")
    with pytest.raises(errors.FileAccessDenied):
        disk.read_all("b", "../escape")
    with pytest.raises(errors.FileAccessDenied):
        disk.read_all("..", "x")


def test_read_write_all(disk):
    disk.make_vol("b")
    disk.write_all("b", "a/b/c.bin", b"hello")
    assert disk.read_all("b", "a/b/c.bin") == b"hello"
    with pytest.raises(errors.FileNotFound):
        disk.read_all("b", "missing")
    st = disk.stat_file("b", "a/b/c.bin")
    assert st.size == 5


def test_delete_prunes_empty_parents(disk):
    disk.make_vol("b")
    disk.write_all("b", "deep/nested/file", b"x")
    disk.delete_file("b", "deep/nested/file")
    # parents pruned up to volume root
    assert disk.list_dir("b", "") == []


def test_shard_stream_roundtrip(disk):
    disk.make_vol("b")
    w = disk.create_file("b", "obj/uuid/part.1")
    w.write(b"abc")
    w.write(b"defgh")
    w.close()
    r = disk.read_file_stream("b", "obj/uuid/part.1")
    assert r.read_at(0, 3) == b"abc"
    assert r.read_at(3, 100) == b"defgh"
    r.close()


def _fi(version_id="", data_dir="dd1", size=100):
    return FileInfo(
        version_id=version_id,
        data_dir=data_dir,
        size=size,
        mod_time_ns=now_ns(),
        metadata={"content-type": "text/plain"},
        parts=[ObjectPartInfo(1, size, size)],
        erasure=ErasureInfo(
            data_blocks=2, parity_blocks=1, block_size=1024, index=1,
            distribution=[1, 2, 3],
        ),
    )


def test_xlmeta_roundtrip():
    xl = XLMeta()
    v1 = _fi(new_version_id())
    xl.add_version(v1)
    raw = xl.to_bytes()
    back = XLMeta.from_bytes(raw)
    assert back.latest().version_id == v1.version_id
    assert back.latest().erasure.data_blocks == 2
    assert back.latest().parts[0].number == 1
    with pytest.raises(errors.FileCorrupt):
        XLMeta.from_bytes(b"garbage!")


def test_metadata_journal(disk):
    disk.make_vol("b")
    fi1 = _fi("v1")
    fi1.mod_time_ns = 1000
    fi2 = _fi("v2", data_dir="dd2")
    fi2.mod_time_ns = 2000
    disk.write_metadata("b", "obj", fi1)
    disk.write_metadata("b", "obj", fi2)
    latest = disk.read_version("b", "obj")
    assert latest.version_id == "v2"
    assert disk.read_version("b", "obj", "v1").version_id == "v1"
    with pytest.raises(errors.VersionNotFound):
        disk.read_version("b", "obj", "v9")


def test_rename_data_commit(disk):
    disk.make_vol("b")
    tmp = disk.new_tmp_dir()
    w = disk.create_file(".sys", f"{tmp.split('/', 1)[1]}/dd1/part.1")
    w.write(b"shard-bytes")
    w.close()
    fi = _fi("v1")
    disk.rename_data(".sys", tmp.split("/", 1)[1], fi, "b", "obj")
    assert disk.read_version("b", "obj").version_id == "v1"
    r = disk.read_file_stream("b", "obj/dd1/part.1")
    assert r.read_at(0, 100) == b"shard-bytes"
    r.close()
    # staging dir gone
    assert not os.path.exists(
        os.path.join(disk.root, ".sys", tmp.split("/", 1)[1])
    )


def test_delete_version_removes_data(disk):
    disk.make_vol("b")
    disk.write_metadata("b", "obj", _fi("v1", data_dir="dd1"))
    disk.write_all("b", "obj/dd1/part.1", b"x")
    disk.delete_version("b", "obj", _fi("v1", data_dir="dd1"))
    with pytest.raises(errors.FileNotFound):
        disk.read_xl("b", "obj")


def test_walk(disk):
    disk.make_vol("b")
    for name in ("a/obj1", "a/obj2", "c/d/obj3"):
        disk.write_metadata("b", name, _fi("v1"))
    found = sorted(disk.walk("b"))
    assert found == ["a/obj1", "a/obj2", "c/d/obj3"]
    assert sorted(disk.walk("b", "a")) == ["a/obj1", "a/obj2"]


def test_disk_info(disk):
    info = disk.disk_info()
    assert info.total > 0
    assert 0 <= info.free <= info.total


def test_append_file_offset_idempotent(disk):
    """A retried append at the same declared offset must converge, not
    duplicate shard bytes (advisor finding r2: lost-response retry)."""
    disk.make_vol("av")
    disk.append_file("av", "f", b"aaaa", truncate=True, offset=0)
    disk.append_file("av", "f", b"bbbb", offset=4)
    # lost response: the same flush is retried verbatim
    disk.append_file("av", "f", b"bbbb", offset=4)
    disk.append_file("av", "f", b"cc", offset=8)
    assert disk.read_all("av", "f") == b"aaaabbbbcc"
    # a gap (offset past EOF) is corruption, not a retry
    import pytest as _pytest

    from minio_tpu.storage import errors as _errors

    with _pytest.raises(_errors.FileCorrupt):
        disk.append_file("av", "f", b"dd", offset=99)


# ---- read_all: three system calls, the volume looked at on the error path


def _pattern(size: int) -> bytes:
    return bytes(i % 251 for i in range(size))


def _setup_missing_file(disk):
    return "b", "nope/xl.meta"


def _setup_missing_volume(disk):
    return "nob", "o/xl.meta"


def _setup_directory(disk):
    disk.write_all("b", "o/xl.meta", b"m")
    return "b", "o"


def _setup_parent_is_file(disk):
    disk.write_all("b", "o/xl.meta", b"m")
    return "b", "o/xl.meta/xl.meta"


def _setup_volume_is_file(disk):
    open(os.path.join(disk.root, "volfile"), "w").close()
    return "volfile", "o/xl.meta"


def _lose_root(leave_file):
    # the degraded cell's case: the drive's path stops being a directory
    def setup(disk):
        disk.write_all("b", "o/xl.meta", b"m")
        shutil.rmtree(disk.root)
        if leave_file:
            open(disk.root, "w").close()
        return "b", "o/xl.meta"

    return setup


N = xl_mod.READ_CHUNK

# what read_all answers (the file's content, or a setup and the parent
# commit's error class), refills, error_path
READ_ALL_CASES = {
    "present": (_pattern(336), 0, 0),
    "empty": (b"", 0, 0),
    "one-under-chunk": (_pattern(N - 1), 0, 0),
    "exactly-chunk": (_pattern(N), 1, 0),
    "chunk-plus-one": (_pattern(N + 1), 1, 0),
    "three-chunks-plus-seven": (_pattern(3 * N + 7), 1, 0),
    "missing-file": ((_setup_missing_file, errors.FileNotFound), 0, 1),
    "missing-volume": ((_setup_missing_volume, errors.VolumeNotFound), 0, 1),
    "directory": ((_setup_directory, errors.IsNotRegular), 0, 0),
    "parent-is-a-file": ((_setup_parent_is_file, NotADirectoryError), 0, 1),
    "volume-is-a-file": ((_setup_volume_is_file, errors.VolumeNotFound), 0, 1),
    "root-became-a-file": ((_lose_root(True), errors.VolumeNotFound), 0, 1),
    "root-gone": ((_lose_root(False), errors.VolumeNotFound), 0, 1),
}


@pytest.mark.parametrize("case", READ_ALL_CASES)
def test_read_all_answers(disk, case):
    want, refills, error_path = READ_ALL_CASES[case]
    disk.make_vol("b")
    if isinstance(want, bytes):
        disk.write_all("b", "o/xl.meta", want)
        before = xl_mod.meta_read_counts()
        assert disk.read_all("b", "o/xl.meta") == want
    else:
        setup, error = want
        volume, path = setup(disk)
        before = xl_mod.meta_read_counts()
        with pytest.raises(error) as caught:
            disk.read_all(volume, path)
        assert type(caught.value) is error
    after = xl_mod.meta_read_counts()
    assert {k: after[k] - before[k] for k in after} == {
        "reads": 1, "refills": refills, "error_path": error_path,
    }


COUNTED = ("os.open", "os.read", "os.close", "os.stat", "os.path.isdir",
           "builtins.open")


@pytest.fixture
def syscalls(monkeypatch):
    """Counts of the calls a drive's read could make, while ``on``."""
    calls = dict.fromkeys(COUNTED, 0)
    state = {"on": False}

    def counted(name, fn):
        def wrapper(*a, **kw):
            if state["on"]:
                calls[name] += 1
            return fn(*a, **kw)

        return wrapper

    for name in COUNTED:
        mod, _, attr = name.rpartition(".")
        owner = {"os": os, "os.path": os.path, "builtins": builtins}[mod]
        monkeypatch.setattr(owner, attr, counted(name, getattr(owner, attr)))

    class Counter:
        def __enter__(self):
            state["on"] = True
            return calls

        def __exit__(self, *exc):
            state["on"] = False

    return Counter()


def _cell_fileinfo(pad: int = 0) -> FileInfo:
    # the shape a 10 MiB object of the benchmark's cells leaves: EC 8+4,
    # one part; the etag padded so that xl.meta is the cells' 336 bytes
    return FileInfo(
        version_id="",
        data_dir=new_version_id(),
        size=10 << 20,
        mod_time_ns=now_ns(),
        metadata={"etag": "0" * pad},
        parts=[ObjectPartInfo(number=1, size=10 << 20, actual_size=10 << 20)],
        erasure=ErasureInfo(
            data_blocks=8, parity_blocks=4, block_size=10 << 20, index=1,
            distribution=list(range(1, 13)),
        ),
    )


def _write_336(disks, name="obj"):
    fi = _cell_fileinfo()
    xl = XLMeta()
    xl.add_version(fi)
    fi = _cell_fileinfo(pad=336 - len(xl.to_bytes()))
    for d in disks:
        d.write_metadata("b", name, fi)
        assert os.path.getsize(
            os.path.join(d.root, "b", name, "xl.meta")) == 336


def test_read_version_is_three_system_calls(disk, syscalls):
    disk.make_vol("b")
    _write_336([disk])
    with syscalls as calls:
        fi = disk.read_version("b", "obj")
    assert fi.size == 10 << 20
    assert calls == {"os.open": 1, "os.read": 1, "os.close": 1,
                     "os.stat": 0, "os.path.isdir": 0, "builtins.open": 0}


@pytest.mark.parametrize("lost", [(), (2, 7)], ids=["healthy", "two-offline"])
def test_metadata_round_system_calls(tmp_path, syscalls, lost):
    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.objectlayer.metadata import (
        find_fileinfo_in_quorum,
        read_all_fileinfo,
    )

    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(12)]
    for d in disks:
        d.make_vol("b")
    _write_336(disks)
    online = [None if i in lost else d for i, d in enumerate(disks)]
    before = KERNEL_STATS.snapshot()["meta_read"]
    with syscalls as calls:
        fis, errs = read_all_fileinfo(online, "b", "obj")
    after = KERNEL_STATS.snapshot()["meta_read"]
    answered = 12 - len(lost)
    assert calls == {"os.open": answered, "os.read": answered,
                     "os.close": answered, "os.stat": 0,
                     "os.path.isdir": 0, "builtins.open": 0}
    assert sum(calls.values()) == 3 * answered  # 36 a round; 30 degraded
    assert {k: after[k] - before[k] for k in after} == {
        "reads": answered, "refills": 0, "error_path": 0,
    }
    assert [e is None for e in errs] == [i not in lost for i in range(12)]
    assert find_fileinfo_in_quorum(fis, 8).size == 10 << 20
