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
           "builtins.open", "os.unlink", "os.rmdir", "os.lstat", "os.scandir",
           "os.fstat", "os.mkdir", "os.makedirs", "os.replace", "os.rename",
           "os.fsync", "os.write")


def _calls(**made):
    # every counted name at 0 but those given, as os_open=1 for "os.open"
    want = dict.fromkeys(COUNTED, 0)
    for name, n in made.items():
        want[name.replace("_", ".", 1)] = n
    return want


@pytest.fixture
def syscalls(monkeypatch):
    """Counts of the calls a drive's read, write or removal could make,
    while ``on`` (shutil.rmtree and os.makedirs look their own up in ``os``,
    so a walk and a parent's mkdir show); ``order`` is the names as made.
    A file object's own write, flush and close are not ``os`` calls and do
    not show: ``builtins.open`` stands for a shard file's."""
    calls = dict.fromkeys(COUNTED, 0)
    state = {"on": False}
    names = []

    def counted(name, fn):
        def wrapper(*a, **kw):
            if state["on"]:
                calls[name] += 1
                names.append(name)
            return fn(*a, **kw)

        return wrapper

    for name in COUNTED:
        mod, _, attr = name.rpartition(".")
        owner = {"os": os, "os.path": os.path, "builtins": builtins}[mod]
        monkeypatch.setattr(owner, attr, counted(name, getattr(owner, attr)))

    class Counter:
        order = names

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
    assert calls == _calls(os_open=1, os_read=1, os_close=1)


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
    assert calls == _calls(os_open=answered, os_read=answered,
                           os_close=answered)
    assert sum(calls.values()) == 3 * answered  # 36 a round; 30 degraded
    assert {k: after[k] - before[k] for k in after} == {
        "reads": answered, "refills": 0, "error_path": 0,
    }
    assert [e is None for e in errs] == [i not in lost for i in range(12)]
    assert find_fileinfo_in_quorum(fis, 8).size == 10 << 20


# ---- removal by name (PR 32) ---------------------------------------------
# A drive that is told which files an object has removes them one system
# call a name and walks the tree only where it finds something else.  Every
# case runs twice, by name and by the parent commit's walk on a twin drive,
# and the two drives have to end alike, error class included.


def _parent_delete_file(disk, volume, path):
    # XLStorage.delete_file(recursive=True) as the parent commit had it
    disk._require_vol(volume)
    full = disk._file_path(volume, path)
    try:
        if os.path.isdir(full):
            shutil.rmtree(full)
        else:
            os.remove(full)
    except FileNotFoundError:
        raise errors.FileNotFound(path) from None
    except OSError as e:
        raise errors.FaultyDisk(str(e)) from e
    parent = os.path.dirname(full)
    vol = disk._vol_path(volume)
    while parent != vol:
        try:
            os.rmdir(parent)
        except OSError:
            break
        parent = os.path.dirname(parent)


def _tree(root):
    """{relative path: content, or None for a directory} of a drive."""
    if not os.path.isdir(root):
        with open(root, "rb") as f:
            return {"": f.read()}
    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        rel = os.path.relpath(dirpath, root)
        for nm in dirnames:
            out[os.path.normpath(os.path.join(rel, nm))] = None
        for nm in filenames:
            with open(os.path.join(dirpath, nm), "rb") as f:
                out[os.path.normpath(os.path.join(rel, nm))] = f.read()
    return out


def _parts_fileinfo(nparts=1, data_dir=None, version_id=""):
    fi = _cell_fileinfo()
    fi.version_id = version_id
    fi.data_dir = new_version_id() if data_dir is None else data_dir
    fi.parts = [
        ObjectPartInfo(number=n, size=64, actual_size=64)
        for n in range(1, nparts + 1)
    ]
    return fi


def _lay(disk, name, fi, journal=True):
    """The object as a PUT leaves it on one drive: the journal's entry
    and ``<data_dir>/part.<n>``."""
    obj = os.path.join(disk.root, "b", *name.split("/"))
    if fi.data_dir:
        os.makedirs(os.path.join(obj, fi.data_dir))
        for part in fi.parts:
            with open(os.path.join(obj, fi.data_dir, f"part.{part.number}"), "wb") as f:
                f.write(_pattern(part.size))
    if journal:
        disk.write_metadata("b", name, fi)


def _stray(disk, *rel):
    with open(os.path.join(disk.root, "b", *rel), "wb") as f:
        f.write(b"stray")


# name -> (build(disk, fis) -> (path, fi), os calls by name or None where the
# count is the walk's, REMOVE's [named, walked], error class)
def _case_one_part(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj-00012", fi)
    return "obj-00012", fi


def _case_three_parts(disk, fis):
    fi = fis["three"]
    _lay(disk, "obj", fi)
    return "obj", fi


def _case_no_data_dir(disk, fis):
    fi = fis["marker"]
    _lay(disk, "obj", fi)
    return "obj", fi


def _case_two_versions(disk, fis):
    old, fi = fis["v1"], fis["v2"]
    _lay(disk, "obj", old)
    _lay(disk, "obj", fi)
    return "obj", fi


def _case_stray_in_object(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj", fi)
    _stray(disk, "obj", "leftover")
    return "obj", fi


def _case_stray_in_data_dir(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj", fi)
    _stray(disk, "obj", fi.data_dir, "part.7")
    return "obj", fi


def _case_part_is_a_directory(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj", fi)
    part = os.path.join(disk.root, "b", "obj", fi.data_dir, "part.1")
    os.remove(part)
    os.makedirs(os.path.join(part, "deeper"))
    return "obj", fi


def _case_part_gone(disk, fis):
    fi = fis["three"]
    _lay(disk, "obj", fi)
    os.remove(os.path.join(disk.root, "b", "obj", fi.data_dir, "part.2"))
    return "obj", fi


def _case_data_dir_gone(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj", fi)
    shutil.rmtree(os.path.join(disk.root, "b", "obj", fi.data_dir))
    return "obj", fi


def _case_no_journal(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj", fi, journal=False)
    return "obj", fi


def _case_object_missing(disk, fis):
    _lay(disk, "other", fis["three"])
    return "obj", fis["one"]


def _case_volume_missing(disk, fis):
    os.rmdir(os.path.join(disk.root, "b"))
    return "obj", fis["one"]


def _case_root_a_file(disk, fis):
    fi = fis["one"]
    _lay(disk, "obj", fi)
    shutil.rmtree(disk.root)
    with open(disk.root, "w") as f:
        f.write("not a drive")
    return "obj", fi


def _case_nested_sibling_stays(disk, fis):
    fi = fis["one"]
    _lay(disk, "a/b/obj", fi)
    _lay(disk, "a/c/other", fis["three"])
    return "a/b/obj", fi


def _case_nested_all_empty(disk, fis):
    fi = fis["one"]
    _lay(disk, "a/b/obj", fi)
    return "a/b/obj", fi


def _replaced(disk, fis, name="obj"):
    # after an overwriting PUT's commit: the journal names the new data
    # dir, the old one is still beside it
    old, new = fis["one"], fis["three"]
    _lay(disk, name, old, journal=False)
    _lay(disk, name, new)
    return f"{name}/{old.data_dir}", old


def _case_replaced_data_dir(disk, fis):
    return _replaced(disk, fis)


def _case_replaced_nested(disk, fis):
    return _replaced(disk, fis, "a/b/obj")


def _case_replaced_stray(disk, fis):
    path, old = _replaced(disk, fis)
    _stray(disk, *path.split("/"), "part.9")
    return path, old


def _case_replaced_part_gone(disk, fis):
    path, old = _replaced(disk, fis)
    os.remove(os.path.join(disk.root, "b", *path.split("/"), "part.1"))
    return path, old


def _case_replaced_gone(disk, fis):
    path, old = _replaced(disk, fis)
    shutil.rmtree(os.path.join(disk.root, "b", *path.split("/")))
    return path, old


def _case_replaced_no_journal(disk, fis):
    # a drive that holds the old data dir and nothing else of the object:
    # the walk prunes the object's directory, by name it would stay.  The
    # object layer names the files only to a drive whose commit went
    # through (_reap_data_dir), so this drive is asked without them
    old = fis["one"]
    _lay(disk, "obj", old, journal=False)
    return f"obj/{old.data_dir}", None


REMOVE_CASES = {
    "one-part": (_case_one_part, {"os.unlink": 2, "os.rmdir": 2}, [1, 0], None),
    "three-parts": (_case_three_parts, {"os.unlink": 4, "os.rmdir": 2}, [1, 0], None),
    "no-data-dir": (_case_no_data_dir, {"os.unlink": 1, "os.rmdir": 1}, [1, 0], None),
    "two-versions": (_case_two_versions, None, [0, 1], None),
    "stray-in-object": (_case_stray_in_object, None, [0, 1], None),
    "stray-in-data-dir": (_case_stray_in_data_dir, None, [0, 1], None),
    "part-is-a-directory": (_case_part_is_a_directory, None, [0, 1], None),
    "part-already-gone": (_case_part_gone, {"os.unlink": 4, "os.rmdir": 2}, [1, 0], None),
    "data-dir-already-gone": (_case_data_dir_gone, None, [0, 1], None),
    "no-journal": (_case_no_journal, None, [0, 1], None),
    "object-missing": (_case_object_missing, None, [0, 1], errors.FileNotFound),
    "volume-missing": (_case_volume_missing, None, [0, 1], errors.VolumeNotFound),
    "root-became-a-file": (_case_root_a_file, None, [0, 1], errors.VolumeNotFound),
    "nested-sibling-stays": (_case_nested_sibling_stays, {"os.unlink": 2, "os.rmdir": 4}, [1, 0], None),
    "nested-all-empty": (_case_nested_all_empty, {"os.unlink": 2, "os.rmdir": 4}, [1, 0], None),
    "replaced-data-dir": (_case_replaced_data_dir, {"os.unlink": 1, "os.rmdir": 1}, [1, 0], None),
    "replaced-nested": (_case_replaced_nested, {"os.unlink": 1, "os.rmdir": 1}, [1, 0], None),
    "replaced-stray": (_case_replaced_stray, None, [0, 1], None),
    "replaced-part-gone": (_case_replaced_part_gone, {"os.unlink": 1, "os.rmdir": 1}, [1, 0], None),
    "replaced-already-gone": (_case_replaced_gone, None, [0, 1], errors.FileNotFound),
    "replaced-no-journal-unnamed": (_case_replaced_no_journal, None, [0, 0], None),
}


def _outcome(fn):
    try:
        fn()
    except Exception as e:  # noqa: BLE001 - the class is what is compared
        return type(e)
    return None


@pytest.mark.parametrize("case", REMOVE_CASES)
def test_remove_by_name_leaves_what_the_walk_leaves(tmp_path, syscalls, case):
    build, by_name, (named, walked), error = REMOVE_CASES[case]
    fis = dict(
        one=_parts_fileinfo(1), three=_parts_fileinfo(3),
        marker=_parts_fileinfo(0, data_dir=""),
        v1=_parts_fileinfo(1, version_id=new_version_id()),
        v2=_parts_fileinfo(1, version_id=new_version_id()),
    )
    fis["marker"].deleted = True
    drive, twin = XLStorage(str(tmp_path / "d")), XLStorage(str(tmp_path / "t"))
    for d in (drive, twin):
        d.make_vol("b")
    path, fi = build(drive, fis)
    assert build(twin, fis) == (path, fi)
    assert _tree(drive.root) == _tree(twin.root)

    before = xl_mod.remove_counts()
    with syscalls as calls:
        got = _outcome(lambda: drive.delete_file("b", path, recursive=True, fi=fi))
    made = {k: v for k, v in calls.items() if v}
    after = xl_mod.remove_counts()
    want = _outcome(lambda: _parent_delete_file(twin, "b", path))

    assert got is want is error
    assert _tree(drive.root) == _tree(twin.root)
    moved = {k: after[k] - before[k] for k in after}
    assert [moved["named"], moved["walked"]] == [named, walked]
    if by_name is not None:
        # 4 calls for the cells' object, 2 for a replaced data dir, and
        # one more for every parent of a nested key it tries to prune
        assert made == by_name
        assert moved["calls"] == sum(by_name.values())
    elif walked:
        # the attempt by name and then the walk, which stats first
        assert made.get("os.stat", 0) >= 1
        assert 1 <= moved["calls"] <= 4
    if path.startswith("a/b/") and error is None:
        kept = os.path.isdir(os.path.join(drive.root, "b", "a"))
        assert kept == (case in ("nested-sibling-stays", "replaced-nested"))
    assert os.path.isdir(os.path.join(drive.root, "b")) or error is not None


# staging dir's leftovers -> (REMOVE's [named, walked], os.rmdir calls)
STAGING_CASES = {
    "emptied": ((), [1, 0], 1),
    "leftover-file": (("junk",), [0, 1], None),
    "leftover-dir": (("more/junk",), [0, 1], None),
    "no-data-dir": (None, [1, 0], 1),
}


@pytest.mark.parametrize("case", STAGING_CASES)
def test_rename_data_removes_its_staging_dir_with_one_rmdir(disk, syscalls, case):
    left, (named, walked), rmdirs = STAGING_CASES[case]
    disk.make_vol("b")
    fi = _parts_fileinfo(1) if left is not None else _parts_fileinfo(0, data_dir="")
    tmp = disk.new_tmp_dir()
    staged = os.path.join(disk.root, *tmp.split("/"))
    os.makedirs(os.path.join(staged, fi.data_dir))
    if fi.data_dir:
        with open(os.path.join(staged, fi.data_dir, "part.1"), "wb") as f:
            f.write(_pattern(64))
    for rel in left or ():
        os.makedirs(os.path.dirname(os.path.join(staged, rel)), exist_ok=True)
        with open(os.path.join(staged, rel), "wb") as f:
            f.write(b"stray")
    before = xl_mod.remove_counts()
    with syscalls as calls:
        disk.rename_data(".sys", tmp[len(".sys/"):], fi, "b", "obj")
    after = xl_mod.remove_counts()
    # the parent's rmtree left no staging dir behind in any case
    assert os.listdir(os.path.join(disk.root, ".sys", "tmp")) == []
    got = _tree(os.path.join(disk.root, "b"))
    assert sorted(got) == sorted(
        ["obj", "obj/xl.meta"]
        + ([f"obj/{fi.data_dir}", f"obj/{fi.data_dir}/part.1"] if fi.data_dir else [])
    )
    moved = {k: after[k] - before[k] for k in after}
    assert [moved["named"], moved["walked"], moved["calls"]] == [named, walked, 1]
    if rmdirs is not None:
        assert calls["os.rmdir"] == rmdirs
        assert calls["os.scandir"] == calls["os.lstat"] == calls["os.unlink"] == 0
    else:
        assert calls["os.scandir"] >= 1  # the walk, for what was left


# ---- the same through the object layer, on twelve drives ------------------


class _WalkingDrive(XLStorage):
    """A drive that takes no notice of the names, as the StorageAPI allows
    and as every drive of the parent commit did."""

    def delete_file(self, volume, path, recursive=False, fi=None):
        return super().delete_file(volume, path, recursive)


_UUID = "[0-9a-f]{32}|[0-9a-f]{8}(-[0-9a-f]{4}){3}-[0-9a-f]{12}"


def _bucket_shape(drive):
    """What a drive holds of the bucket, with the data dirs' names (fresh
    UUIDs) and the journals' stamps taken out."""
    import re

    shape = []
    for rel, content in _tree(os.path.join(drive.root, "bkt")).items():
        rel = re.sub(_UUID, "<data_dir>", rel)
        if content is not None and rel.endswith("xl.meta"):
            content = [
                (v.deleted, v.size, bool(v.data_dir), [p.number for p in v.parts])
                for v in XLMeta.from_bytes(content, "bkt", rel).versions
            ]
        shape.append((rel, content))
    return sorted(shape, key=repr)


def _put(ol, key, seed, size=20000, **kw):
    import io

    body = bytes((seed + i) % 251 for i in range(size))
    return ol.put_object("bkt", key, io.BytesIO(body), size, **kw)


def _scene_delete(ol, drives):
    _put(ol, "obj-00012", 1)
    return lambda: ol.delete_object("bkt", "obj-00012")


def _scene_delete_nested(ol, drives):
    _put(ol, "a/b/obj", 1)
    _put(ol, "a/c/obj", 2)
    return lambda: ol.delete_object("bkt", "a/b/obj")


def _scene_overwrite(ol, drives):
    _put(ol, "obj", 1)
    return lambda: _put(ol, "obj", 2)


def _scene_versioned_delete(ol, drives):
    v1 = _put(ol, "obj", 1, versioned=True).version_id
    _put(ol, "obj", 2, versioned=True)
    return lambda: ol.delete_object("bkt", "obj", version_id=v1)


def _scene_delete_all_versions(ol, drives):
    # an unversioned DELETE of a key with two versions removes the whole
    # directory; the quorum FileInfo names one data dir of the two
    _put(ol, "obj", 1, versioned=True)
    _put(ol, "obj", 2, versioned=True)
    return lambda: ol.delete_object("bkt", "obj")


def _scene_suspended_marker(ol, drives):
    _put(ol, "obj", 1)
    return lambda: ol.delete_object("bkt", "obj", version_suspended=True)


def _scene_drive_offline(ol, drives):
    _put(ol, "obj", 1)
    ol.disks[3] = None
    return lambda: ol.delete_object("bkt", "obj")


def _scene_copy_gone(ol, drives):
    _put(ol, "obj", 1)
    shutil.rmtree(os.path.join(drives[5].root, "bkt", "obj"))
    return lambda: ol.delete_object("bkt", "obj")


def _scene_overwrite_copy_gone(ol, drives):
    _put(ol, "obj", 1)
    shutil.rmtree(os.path.join(drives[5].root, "bkt", "obj"))
    return lambda: _put(ol, "obj", 2)


# scene -> (setup, REMOVE's [named, walked, calls] over the act, the key
# that is a 404 afterwards)
SCENES = {
    "delete": (_scene_delete, [12, 0, 48], "obj-00012"),
    "delete-nested-key": (_scene_delete_nested, [12, 0, 72], "a/b/obj"),
    # a PUT's 12 staging dirs, one rmdir each, then 12 old data dirs, two
    "overwrite-put": (_scene_overwrite, [24, 0, 36], None),
    "versioned-delete": (_scene_versioned_delete, [0, 0, 0], None),
    "delete-of-two-versions": (_scene_delete_all_versions, [0, 12, 48], "obj"),
    "suspended-delete-marker": (_scene_suspended_marker, [12, 0, 24], None),
    "delete-drive-offline": (_scene_drive_offline, [11, 0, 44], "obj"),
    "delete-one-copy-gone": (_scene_copy_gone, [11, 1, 45], "obj"),
    "overwrite-one-copy-gone": (_scene_overwrite_copy_gone, [23, 1, 36], None),
}


@pytest.mark.parametrize("scene", SCENES)
def test_object_layer_removes_by_name_what_the_parent_walked(tmp_path, scene):
    from minio_tpu.objectlayer.erasure_object import ErasureObjects

    setup, want, gone = SCENES[scene]
    sets = []
    for cls in (XLStorage, _WalkingDrive):
        drives = [cls(str(tmp_path / cls.__name__ / f"d{i}")) for i in range(12)]
        ol = ErasureObjects(drives, parity_blocks=4, block_size=4096)
        ol.make_bucket("bkt")
        sets.append((ol, drives, setup(ol, drives)))
    (ol, drives, act), (_, twins, twin_act) = sets
    twin_act()
    before = xl_mod.remove_counts()
    act()
    after = xl_mod.remove_counts()
    assert [after[k] - before[k] for k in ("named", "walked", "calls")] == want
    for drive, twin in zip(drives, twins):
        assert _bucket_shape(drive) == _bucket_shape(twin)
        assert os.listdir(os.path.join(drive.root, ".sys", "tmp")) == []
    if gone is not None:
        from minio_tpu.objectlayer import api

        with pytest.raises(api.ObjectNotFound):
            ol.get_object_info("bkt", gone)
        for d, online in zip(drives, ol.disks):
            # a 404 from every drive that was asked, not from a quorum
            assert online is None or not os.path.exists(
                os.path.join(d.root, "bkt", gone))


def test_names_reach_the_drive_through_its_wrappers(tmp_path, syscalls):
    """DiskIDCheck(MeteredDisk(XLStorage)), the stack every erasure set
    runs: the names pass, the disk-id check still runs first, the meter
    still counts the call."""
    from minio_tpu.objectlayer.format import wait_for_format
    from minio_tpu.storage import metered
    from minio_tpu.storage.diskcheck import DiskIDCheck

    drives = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    ref, ordered = wait_for_format(drives, 1, 4, timeout_s=5)
    raw = ordered[0]
    raw.make_vol("b")
    fi = _parts_fileinfo(1)
    _lay(raw, "obj", fi)
    laid = _tree(raw.root)

    intruder = DiskIDCheck(metered.wrap(raw), "another-drive", check_interval_s=0.0)
    with pytest.raises(errors.DiskNotFound, match="mismatch"):
        intruder.delete_file("b", "obj", recursive=True, fi=fi)
    assert _tree(raw.root) == laid

    chain = DiskIDCheck(metered.wrap(raw), ref.sets[0][0], check_interval_s=0.0)
    before = xl_mod.remove_counts()
    with syscalls as calls:
        chain.delete_file("b", "obj", recursive=True, fi=fi)
    after = xl_mod.remove_counts()
    assert {k: after[k] - before[k] for k in after} == {
        "named": 1, "walked": 0, "calls": 4}
    # the check's read of format.json and the four removals, nothing else
    assert calls == _calls(os_open=1, os_read=1, os_close=1, os_unlink=2, os_rmdir=2)
    assert chain.api_stats()["delete_file"]["calls"] == 1
    assert chain.api_stats()["delete_file"]["errors"] == 0
    assert "obj" not in os.listdir(os.path.join(raw.root, "b"))


# ---- the liveness question (PR 34) ----------------------------------------
# Whether a drive is there is what its DiskIDCheck last found, not a `stat`
# of its root a drive every time the object layer takes its snapshot of live
# drives.  `os.stat` a request at the object layer of the server's own stack
# (`build_object_layer`, 12 drives, EC 8+4, DiskIDCheck(MeteredDisk(XLStorage))),
# as the parent commit 85d9f77 made them:
#
# | request        | `stat` | of them before and round the metadata round | the round | now (PR 36) |
# |----------------|--------|----------------------------------------------|-----------|-------------|
# | STAT           |  40    | 40: three snapshots x 12 `isdir`, 2 `stat_vol` x 2 | 12 x open/read/close | 2 |
# | GET            |  64    | 52: four snapshots, 2 `stat_vol` (+ 12 `_require_vol` at the shard opens) | 36 | 2 |
# | PUT, new key   | 244    | 64 (+ 15 a drive x 12, the drive's own writes) | - | 14: 2 + the round's error path |
# | PUT, overwrite | 232    | 52 (+ 15 a drive x 12)                       | 36        | 2 |
# | DELETE         |  52    | 52: four snapshots, 2 `stat_vol`             | 36        | 2 |
#
# PR 34: a snapshot makes none and the bucket question is asked once (one
# `stat_vol` = 2 `stat`, on the first live drive, never from a cache).  PR 36:
# a drive's writes and shard opens ask nothing before the call that does the
# work (DRIVE_WRITE_CALLS below).  What is left, by the XLStorage call that
# made it, is the look at the volume when a new key's round finds no xl.meta.


def _stats_by_drive_call(monkeypatch):
    """{XLStorage method: os.stat calls made under it}, on every thread,
    while ``on``; ``"root"`` counts those that looked at a drive's root."""
    import collections
    import sys

    made = collections.Counter()
    state = {"on": False, "roots": set()}
    real = os.stat

    def stat(path, *a, **kw):
        if state["on"]:
            frame, api = sys._getframe(1), "object layer"
            while frame is not None:
                if isinstance(frame.f_locals.get("self"), XLStorage):
                    api = frame.f_code.co_name  # the outermost wins
                frame = frame.f_back
            made[api] += 1
            if path in state["roots"]:
                made["root"] += 1
        return real(path, *a, **kw)

    monkeypatch.setattr(os, "stat", stat)
    return made, state


# request -> os.stat by the drive call that made them, at 1 MiB on 12 drives
LIVENESS_CALLS = {
    "STAT": dict(stat_vol=2),
    "GET": dict(stat_vol=2),
    # read_version: the round finds no xl.meta and looks at the volume (PR
    # 28's error path, ROADMAP queue 1 item 3b)
    "PUT-new": dict(stat_vol=2, read_version=12),
    "PUT-overwrite": dict(stat_vol=2),
    "DELETE": dict(stat_vol=2),
}


@pytest.mark.parametrize("request_kind", LIVENESS_CALLS)
def test_a_request_asks_no_drive_whether_it_is_there(tmp_path, monkeypatch, request_kind):
    import io
    import time

    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.server.__main__ import build_object_layer

    ol = build_object_layer([str(tmp_path / "d{1...12}")], parity=4)
    ol.make_bucket("bkt")
    body = os.urandom(1 << 20)
    for key in ("obj", "old"):
        ol.put_object("bkt", key, io.BytesIO(body), len(body))
    got = io.BytesIO()
    act = {
        "STAT": lambda: ol.get_object_info("bkt", "obj"),
        "GET": lambda: ol.get_object("bkt", "obj", got),
        "PUT-new": lambda: ol.put_object("bkt", "new", io.BytesIO(body), len(body)),
        "PUT-overwrite": lambda: ol.put_object("bkt", "old", io.BytesIO(body), len(body)),
        "DELETE": lambda: ol.delete_object("bkt", "old"),
    }[request_kind]
    ol.get_object_info("bkt", "obj")  # every drive looked at within the second

    made, state = _stats_by_drive_call(monkeypatch)
    state["roots"] = {str(tmp_path / f"d{i}") for i in range(1, 13)}
    before = KERNEL_STATS.snapshot()["liveness"]
    state["on"], t0 = True, time.monotonic()
    try:
        act()
    finally:
        state["on"], took = False, time.monotonic() - t0
    after = KERNEL_STATS.snapshot()["liveness"]

    assert made.pop("root", 0) == 0
    assert dict(made) == LIVENESS_CALLS[request_kind]
    # outside the round and the drives' own calls: STAT <= 4, GET <= 4,
    # PUT <= 6, DELETE <= 6 is what the issue asked; 2 is what is left
    assert made["stat_vol"] + made["object layer"] + made["is_online"] == 2
    if request_kind == "GET":
        assert got.getvalue() == body
    moved = {k: after[k] - before[k] for k in after}
    snapshots = 2 if request_kind == "STAT" else 3
    assert (moved["asked"], moved["reset"]) == (12 * snapshots, 0)
    # a look a drive a second (format.json: open, read, close; no stat)
    assert moved["looked"] <= 12 * int(took + 1)


# ---- a drive writes first and asks only when the write fails (PR 36) -------
# create_file, read_file_stream, rename_data and write_all make the system
# calls that do the work; the volume, the parents and the staging area are
# looked at once one of those has said no.  Every case: the exact counted
# calls (a file object's own write / close are not `os` calls: the fixture's
# docstring), DRIVE_WRITE's [calls, asked], the error class, the tree left.
#
# | drive call, one drive      | parent f8e0389                     | now                         |
# |----------------------------|------------------------------------|-----------------------------|
# | create_file, staged shard  | 3 `stat`, 2 `mkdir`, `open`        | 2 `mkdir`, `open`           |
# | read_file_stream           | 1 `stat`, `open`                   | `open`                      |
# | rename_data, new key       | 12 `stat`, 3 `mkdir` (2 failing), 9 | `mkdir`, `replace`, `open` (fails), `open`, `write`, `fsync`, `close`, `replace`, `rmdir`: 9 |
# | rename_data, overwrite     | 12 `stat`, 3 failing `mkdir`, 11   | the same with `open`, `read`, `close` of the journal and the `mkdir` failing: 11 |

_SHARD = b"shard-bytes-of-part-%d"


def _stage(disk, tmp_id="stg", data_dir="dd", parts=(1,), volume=".sys", base="tmp/"):
    # a staged data dir as a PUT's writers leave it
    for n in parts:
        w = disk.create_file(volume, f"{base}{tmp_id}/{data_dir}/part.{n}")
        w.write(_SHARD % n)
        w.close()


def _dw_fi(data_dir="dd", parts=(1,)):
    fi = _parts_fileinfo(len(parts), data_dir=data_dir)
    for part, n in zip(fi.parts, parts):
        part.number = n
    return fi


def _rename(disk, fi=None, src=(".sys", "tmp/stg"), dst=("b", "obj")):
    fi = _dw_fi() if fi is None else fi
    return lambda: disk.rename_data(src[0], src[1], fi, dst[0], dst[1])


def _write_and_close(disk, volume, path):
    def act():
        w = disk.create_file(volume, path)
        w.write(_SHARD % int(path.rsplit(".", 1)[1]))
        w.close()

    return act


def _open_and_close(disk, volume, path):
    def act():
        rd = disk.read_file_stream(volume, path)
        assert rd.read_at(0, 100) == _SHARD % int(path.rsplit(".", 1)[1])
        rd.close()

    return act


_SYS = {".sys": None, ".sys/tmp": None, "b": None}
_PART = {"obj": None, "obj/dd": None, "obj/dd/part.1": _SHARD % 1}


def _under(prefix, tree):
    return {f"{prefix}/{k}": v for k, v in tree.items()}


# the calls of a journal's commit and of a whole rename_data, in their order
_COMMIT = ["os.open", "os.write", "os.fsync", "os.close", "os.replace"]
_NEW_KEY = ["os.mkdir", "os.replace", "os.open"] + _COMMIT + ["os.rmdir"]
_OVERWRITE = (["os.mkdir", "os.replace", "os.open", "os.read", "os.close"]
              + _COMMIT + ["os.rmdir"])


def _dw_staged(disk):
    return _write_and_close(disk, ".sys", "tmp/stg/dd/part.1")


def _dw_staged_multipart(disk):
    return _write_and_close(disk, ".sys", "tmp/stg/part.3")


def _dw_staged_second_part(disk):
    _stage(disk)
    return _write_and_close(disk, ".sys", "tmp/stg/dd/part.2")


def _dw_staged_id_there(disk):
    # tmp/<id> is there and <data_dir> is not: the open says so
    os.mkdir(os.path.join(disk.root, ".sys", "tmp", "stg"))
    return _write_and_close(disk, ".sys", "tmp/stg/dd/part.1")


def _dw_create_dir_there(disk):
    os.makedirs(os.path.join(disk.root, "b", "obj", "dd"))
    return _write_and_close(disk, "b", "obj/dd/part.1")


def _dw_create_parents_missing(disk):
    return _write_and_close(disk, "b", "obj/dd/part.1")


def _dw_create_no_volume(disk):
    return _write_and_close(disk, "nob", "obj/dd/part.1")


def _dw_create_volume_is_file(disk):
    open(os.path.join(disk.root, "volfile"), "w").close()
    return _write_and_close(disk, "volfile", "obj/dd/part.1")


def _dw_create_parent_is_file(disk):
    disk.write_all("b", "obj", b"a file")
    return _write_and_close(disk, "b", "obj/dd/part.1")


def _dw_create_lost_root(disk):
    shutil.rmtree(disk.root)
    return _write_and_close(disk, ".sys", "tmp/stg/dd/part.1")


def _dw_create_tmp_pruned(disk):
    os.rmdir(os.path.join(disk.root, ".sys", "tmp"))
    return _write_and_close(disk, ".sys", "tmp/stg/dd/part.1")


def _laid(disk):
    _stage(disk)
    _rename(disk)()


def _dw_read_hit(disk):
    _laid(disk)
    return _open_and_close(disk, "b", "obj/dd/part.1")


def _dw_read_missing(disk):
    _laid(disk)
    return _open_and_close(disk, "b", "obj/dd/part.2")


def _dw_read_no_volume(disk):
    return _open_and_close(disk, "nob", "obj/dd/part.1")


def _dw_read_volume_is_file(disk):
    open(os.path.join(disk.root, "volfile"), "w").close()
    return _open_and_close(disk, "volfile", "obj/dd/part.1")


def _dw_read_parent_is_file(disk):
    _laid(disk)
    return _open_and_close(disk, "b", "obj/dd/part.1/part.1")


def _dw_read_directory(disk):
    _laid(disk)
    return lambda: disk.read_file_stream("b", "obj/dd")


def _dw_read_lost_root(disk):
    _laid(disk)
    shutil.rmtree(disk.root)
    open(disk.root, "w").close()
    return _open_and_close(disk, "b", "obj/dd/part.1")


def _dw_rename_new(disk):
    _stage(disk)
    return _rename(disk)


def _dw_rename_overwrite(disk):
    _laid(disk)
    _stage(disk, data_dir="d2")
    return _rename(disk, _dw_fi("d2"))


def _dw_rename_parents(disk):
    _stage(disk)
    return _rename(disk, dst=("b", "a/b/c"))


def _dw_rename_same_data_dir(disk):
    _laid(disk)
    _stage(disk)
    return _rename(disk)


def _dw_rename_two_parts(disk):
    _stage(disk, parts=(1, 2))
    return _rename(disk, _dw_fi(parts=(1, 2)))


def _dw_rename_no_staging(disk):
    return _rename(disk)


def _dw_rename_no_staged_data(disk):
    _stage(disk, data_dir="other")
    return _rename(disk)


def _dw_rename_no_staged_data_overwrite(disk):
    _laid(disk)
    _stage(disk, data_dir="other")
    return _rename(disk, _dw_fi("d2"))


def _dw_rename_no_src_volume(disk):
    return _rename(disk, src=("nosrc", "stg"))


def _dw_rename_no_dst_volume(disk):
    _stage(disk)
    return _rename(disk, dst=("nob", "obj"))


def _dw_rename_lost_root(disk):
    _stage(disk)
    shutil.rmtree(disk.root)
    return _rename(disk)


def _dw_rename_tmp_pruned(disk):
    # staged outside the tmp area, which parent cleanup has taken
    _stage(disk, volume="b", base="staging/")
    os.rmdir(os.path.join(disk.root, ".sys", "tmp"))
    return _rename(disk, src=("b", "staging/stg"))


def _dw_rename_no_data_dir(disk):
    os.mkdir(os.path.join(disk.root, ".sys", "tmp", "stg"))
    return _rename(disk, _dw_fi("", parts=()))


def _dw_rename_no_data_dir_no_staging(disk):
    return _rename(disk, _dw_fi("", parts=()))


def _dw_rename_journal_is_dir(disk):
    _stage(disk)
    os.makedirs(os.path.join(disk.root, "b", "obj", "xl.meta"))
    return _rename(disk)


def _dw_write_all(disk):
    disk.write_all("b", "cfg/old.json", b"old")
    return lambda: disk.write_all("b", "cfg/doc.json", b"document")


def _dw_write_all_replaces(disk):
    disk.write_all("b", "cfg/doc.json", b"old")
    return lambda: disk.write_all("b", "cfg/doc.json", b"document")


def _dw_write_all_no_parent(disk):
    return lambda: disk.write_all("b", "cfg/sub/doc.json", b"document")


def _dw_write_all_no_volume(disk):
    return lambda: disk.write_all("nob", "cfg/doc.json", b"document")


def _dw_write_all_lost_root(disk):
    shutil.rmtree(disk.root)
    return lambda: disk.write_all("b", "cfg/doc.json", b"document")


def _dw_write_all_tmp_pruned(disk):
    os.makedirs(os.path.join(disk.root, "b", "cfg"))
    os.rmdir(os.path.join(disk.root, ".sys", "tmp"))
    return lambda: disk.write_all("b", "cfg/doc.json", b"document")


def _dw_write_all_onto_dir(disk):
    os.makedirs(os.path.join(disk.root, "b", "cfg", "doc.json"))
    return lambda: disk.write_all("b", "cfg/doc.json", b"document")


_STG = {".sys/tmp/stg": None, ".sys/tmp/stg/dd": None,
        ".sys/tmp/stg/dd/part.1": _SHARD % 1}
_CFG = {"b/cfg": None, "b/cfg/doc.json": b"document"}
# a journal is given as the list of its versions' data dirs
_OBJ = {**_under("b", _PART), "b/obj/xl.meta": ["dd"]}

# case -> (build, the counted calls in their order - or as a dict where a
# rmtree's or a makedirs' own order is not the point -, DRIVE_WRITE's
# [calls, asked], the error class, the drive's tree afterwards)
DRIVE_WRITE_CALLS = {
    "create-staged": (
        _dw_staged, ["os.mkdir", "os.mkdir", "builtins.open", "os.fsync"],
        [1, 0], None, {**_SYS, **_STG}),
    "create-staged-multipart-part": (
        _dw_staged_multipart, ["os.mkdir", "builtins.open", "os.fsync"],
        [1, 0], None,
        {**_SYS, ".sys/tmp/stg": None, ".sys/tmp/stg/part.3": _SHARD % 3}),
    "create-staged-second-part": (
        _dw_staged_second_part, ["os.mkdir", "builtins.open", "os.fsync"],
        [1, 0], None, {**_SYS, **_STG, ".sys/tmp/stg/dd/part.2": _SHARD % 2}),
    "create-staged-id-there-data-dir-not": (
        _dw_staged_id_there,
        {"os.mkdir": 2, "builtins.open": 2, "os.path.isdir": 1, "os.stat": 2,
         "os.makedirs": 1, "os.fsync": 1},
        [1, 1], None, {**_SYS, **_STG}),
    "create-directory-there": (
        _dw_create_dir_there, ["builtins.open", "os.fsync"],
        [1, 0], None, {**_SYS, **_under("b", _PART)}),
    "create-parents-missing": (
        _dw_create_parents_missing,
        {"builtins.open": 2, "os.path.isdir": 1, "os.stat": 3, "os.makedirs": 2,
         "os.mkdir": 2, "os.fsync": 1},
        [1, 1], None, {**_SYS, **_under("b", _PART)}),
    "create-volume-missing": (
        _dw_create_no_volume, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, _SYS),
    "create-volume-is-a-file": (
        _dw_create_volume_is_file, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, {**_SYS, "volfile": b""}),
    "create-parent-is-a-file": (
        _dw_create_parent_is_file,
        {"builtins.open": 1, "os.path.isdir": 2, "os.stat": 3, "os.makedirs": 1,
         "os.mkdir": 1},
        [1, 1], NotADirectoryError, {**_SYS, "b/obj": b"a file"}),
    "create-root-gone": (
        _dw_create_lost_root, ["os.mkdir", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, None),
    "create-tmp-area-pruned": (
        _dw_create_tmp_pruned,
        {"os.mkdir": 4, "os.path.isdir": 1, "os.stat": 4, "os.makedirs": 3,
         "builtins.open": 1, "os.fsync": 1},
        [1, 1], None, {**_SYS, **_STG}),
    "read-hit": (
        _dw_read_hit, ["builtins.open"], [1, 0], None, {**_SYS, **_OBJ}),
    "read-missing-file": (
        _dw_read_missing, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.FileNotFound, {**_SYS, **_OBJ}),
    "read-volume-missing": (
        _dw_read_no_volume, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, _SYS),
    "read-volume-is-a-file": (
        _dw_read_volume_is_file, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, {**_SYS, "volfile": b""}),
    "read-parent-is-a-file": (
        _dw_read_parent_is_file, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], NotADirectoryError, {**_SYS, **_OBJ}),
    "read-a-directory": (
        _dw_read_directory, ["builtins.open"],
        [1, 0], errors.IsNotRegular, {**_SYS, **_OBJ}),
    "read-root-became-a-file": (
        _dw_read_lost_root, ["builtins.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, {"": b""}),
    # the data dir renamed before the journal is replaced; the journal's temp
    # file fsynced before its replace; nine calls, none of them a question
    "rename-new-key": (
        _dw_rename_new, _NEW_KEY, [1, 0], None, {**_SYS, **_OBJ}),
    "rename-overwrite": (
        _dw_rename_overwrite, _OVERWRITE, [1, 0], None,
        {**_SYS, **_OBJ, "b/obj/d2": None, "b/obj/d2/part.1": _SHARD % 1,
         "b/obj/xl.meta": ["d2"]}),
    "rename-two-parts": (
        _dw_rename_two_parts, _NEW_KEY, [1, 0], None,
        {**_SYS, **_OBJ, "b/obj/dd/part.2": _SHARD % 2}),
    "rename-key-with-parents": (
        _dw_rename_parents,
        {"os.mkdir": 4, "os.path.isdir": 1, "os.stat": 4, "os.makedirs": 3,
         "os.replace": 2, "os.open": 2, "os.write": 1, "os.fsync": 1,
         "os.close": 1, "os.rmdir": 1},
        [1, 1], None,
        {**_SYS, "b/a": None, "b/a/b": None, **_under("b/a/b", {
            "c": None, "c/dd": None, "c/dd/part.1": _SHARD % 1,
            "c/xl.meta": ["dd"]})}),
    "rename-same-data-dir-twice": (
        _dw_rename_same_data_dir,
        {"os.mkdir": 1, "os.replace": 3, "os.open": 3, "os.read": 1,
         "os.close": 3, "os.write": 1, "os.fsync": 1, "os.rmdir": 2,
         "os.unlink": 1, "os.lstat": 1, "os.scandir": 1, "os.fstat": 1},
        [1, 0], None, {**_SYS, **_OBJ}),
    "rename-staging-dir-missing": (
        _dw_rename_no_staging,
        ["os.mkdir", "os.replace", "os.path.isdir", "os.stat", "os.path.isdir",
         "os.stat", "os.rmdir"],
        [1, 1], errors.FileNotFound, _SYS),
    "rename-staged-data-dir-missing": (
        _dw_rename_no_staged_data,
        ["os.mkdir", "os.replace"] + ["os.path.isdir", "os.stat"] * 3 + ["os.rmdir"],
        [1, 1], errors.FileNotFound,
        {**_SYS, ".sys/tmp/stg": None, ".sys/tmp/stg/other": None,
         ".sys/tmp/stg/other/part.1": _SHARD % 1}),
    "rename-staged-data-dir-missing-overwrite": (
        _dw_rename_no_staged_data_overwrite,
        ["os.mkdir", "os.replace"] + ["os.path.isdir", "os.stat"] * 3,
        [1, 1], errors.FileNotFound,
        {**_SYS, **_OBJ, ".sys/tmp/stg": None, ".sys/tmp/stg/other": None,
         ".sys/tmp/stg/other/part.1": _SHARD % 1}),
    "rename-source-volume-missing": (
        _dw_rename_no_src_volume,
        ["os.mkdir", "os.replace", "os.path.isdir", "os.stat", "os.rmdir"],
        [1, 1], errors.VolumeNotFound, _SYS),
    "rename-destination-volume-missing": (
        _dw_rename_no_dst_volume, ["os.mkdir", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, {**_SYS, **_STG}),
    "rename-root-gone": (
        _dw_rename_lost_root, ["os.mkdir", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, None),
    "rename-tmp-area-pruned-before-the-commit": (
        _dw_rename_tmp_pruned,
        {"os.mkdir": 2, "os.replace": 2, "os.open": 3, "os.path.isdir": 1,
         "os.stat": 2, "os.makedirs": 1, "os.write": 1, "os.fsync": 1,
         "os.close": 1, "os.rmdir": 1},
        [1, 1], None, {**_SYS, **_OBJ, "b/staging": None}),
    "rename-no-data-dir": (
        _dw_rename_no_data_dir,
        ["os.mkdir", "os.path.isdir", "os.stat", "os.open"] + _COMMIT + ["os.rmdir"],
        [1, 0], None, {**_SYS, "b/obj": None, "b/obj/xl.meta": [""]}),
    "rename-no-data-dir-staging-dir-missing": (
        _dw_rename_no_data_dir_no_staging,
        ["os.mkdir"] + ["os.path.isdir", "os.stat"] * 2 + ["os.rmdir"],
        [1, 1], errors.FileNotFound, _SYS),
    "rename-journal-is-a-directory": (
        _dw_rename_journal_is_dir,
        ["os.mkdir", "os.replace", "os.open", "os.read", "os.close"],
        [1, 0], errors.IsNotRegular,
        {**_SYS, **_under("b", _PART), "b/obj/xl.meta": None, ".sys/tmp/stg": None}),
    "write-all-parent-there": (
        _dw_write_all, _COMMIT, [1, 0], None,
        {**_SYS, **_CFG, "b/cfg/old.json": b"old"}),
    "write-all-replaces": (
        _dw_write_all_replaces, _COMMIT, [1, 0], None, {**_SYS, **_CFG}),
    "write-all-parent-missing": (
        _dw_write_all_no_parent,
        {"os.open": 1, "os.write": 1, "os.fsync": 1, "os.close": 1,
         "os.replace": 2, "os.path.isdir": 1, "os.stat": 3, "os.makedirs": 2,
         "os.mkdir": 2},
        [1, 1], None,
        {**_SYS, "b/cfg": None, "b/cfg/sub": None, "b/cfg/sub/doc.json": b"document"}),
    "write-all-volume-missing": (
        _dw_write_all_no_volume,
        _COMMIT + ["os.path.isdir", "os.stat", "os.unlink"],
        [1, 1], errors.VolumeNotFound, _SYS),
    "write-all-root-gone": (
        _dw_write_all_lost_root, ["os.open", "os.path.isdir", "os.stat"],
        [1, 1], errors.VolumeNotFound, None),
    "write-all-tmp-area-pruned": (
        _dw_write_all_tmp_pruned,
        {"os.open": 2, "os.path.isdir": 1, "os.stat": 2, "os.makedirs": 1,
         "os.mkdir": 1, "os.write": 1, "os.fsync": 1, "os.close": 1,
         "os.replace": 1},
        [1, 1], None, {**_SYS, **_CFG}),
    "write-all-onto-a-directory": (
        _dw_write_all_onto_dir, _COMMIT + ["os.unlink"],
        [1, 0], IsADirectoryError,
        {**_SYS, "b/cfg": None, "b/cfg/doc.json": None}),
}


def _drive_tree(root):
    """_tree with a journal as the list of its versions' data dirs, or None
    where the drive's root is gone."""
    if not os.path.lexists(root):
        return None
    tree = _tree(root)
    for rel, content in tree.items():
        if rel.endswith("xl.meta") and content is not None:
            tree[rel] = [v.data_dir for v in XLMeta.from_bytes(content).versions]
    return tree


@pytest.mark.parametrize("case", DRIVE_WRITE_CALLS)
def test_a_drive_writes_first_and_asks_only_when_the_write_fails(disk, syscalls, case):
    build, want_calls, want_counts, error, leaves = DRIVE_WRITE_CALLS[case]
    disk.make_vol("b")
    act = build(disk)
    before = xl_mod.drive_write_counts()
    with syscalls as calls:
        got = _outcome(act)
    after = xl_mod.drive_write_counts()
    assert got is error
    if isinstance(want_calls, list):
        assert syscalls.order == want_calls
    else:
        assert {k: v for k, v in calls.items() if v} == want_calls
    assert [after[k] - before[k] for k in ("calls", "asked")] == want_counts
    assert _drive_tree(disk.root) == leaves
    if not want_counts[1] and case not in (
        "rename-no-data-dir",  # nothing to move: the staging dir is asked
        "rename-same-data-dir-twice",  # the walk that removes the old copy
    ):
        # the path that asks nothing: no stat of any kind and no makedirs
        asking = ("os.stat", "os.path.isdir", "os.lstat", "os.fstat", "os.makedirs")
        assert not any(calls[name] for name in asking)


def test_the_journal_is_synced_before_it_is_replaced_and_after_the_data_dir(disk, syscalls):
    """The order that makes a PUT durable and atomic on one drive: the data
    dir is in place before the journal names it, and the journal's temp file
    is on the medium (fsync) before the replace that publishes it; the shard
    file was fsynced when its writer closed."""
    disk.make_vol("b")
    with syscalls:
        _stage(disk)
    assert syscalls.order == ["os.mkdir", "os.mkdir", "builtins.open", "os.fsync"]
    seen = []
    real_replace, real_fsync = os.replace, os.fsync

    def replace(src, dst):
        seen.append(("replace", os.path.basename(dst),
                     os.path.exists(os.path.join(disk.root, "b", "obj", "dd", "part.1"))))
        return real_replace(src, dst)

    def fsync(fd):
        seen.append(("fsync", os.path.basename(os.readlink(f"/proc/self/fd/{fd}"))[:3], None))
        return real_fsync(fd)

    os.replace, os.fsync = replace, fsync
    try:
        _rename(disk)()
    finally:
        os.replace, os.fsync = real_replace, real_fsync
    assert seen == [("replace", "dd", False), ("fsync", "wa-", None),
                    ("replace", "xl.meta", True)]


def test_kernel_stats_drive_write_counts_a_put_and_a_get_that_ask_nothing(tmp_path):
    """``kernel-stats.drive_write`` through the server's own stack on 12
    drives: a PUT is 12 ``create_file`` + 12 ``rename_data``, a GET 12
    ``read_file_stream``, and none of them asks; the first PUT under a new
    prefix asks once a drive (the parents).  What was PUT reads back."""
    import io

    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.server.__main__ import build_object_layer

    ol = build_object_layer([str(tmp_path / "d{1...12}")], parity=4)
    ol.make_bucket("bkt")

    def moved(act):
        before = KERNEL_STATS.snapshot()["drive_write"]
        act()
        after = KERNEL_STATS.snapshot()["drive_write"]
        assert set(after) == {"calls", "asked"}
        return [after[k] - before[k] for k in ("calls", "asked")]

    def put(key, body):
        return lambda: ol.put_object("bkt", key, io.BytesIO(body), len(body))

    def get(key):
        got = io.BytesIO()
        ol.get_object("bkt", key, got)
        return got.getvalue()

    first, second, third = (os.urandom(1 << 20) for _ in range(3))
    assert moved(put("obj", first)) == [24, 0]
    assert moved(lambda: get("obj")) == [12, 0]
    assert moved(put("obj", second)) == [24, 0]  # an overwrite
    assert get("obj") == second
    ol.delete_object("bkt", "obj")
    assert moved(put("obj", third)) == [24, 0]  # a PUT that follows a DELETE
    assert get("obj") == third
    assert moved(put("new/prefix/obj", first)) == [24, 12]
    assert moved(put("new/prefix/other", second)) == [24, 0]
    assert get("new/prefix/obj") == first and get("new/prefix/other") == second
