"""An object of many erasure blocks is one stream of several batches.

A 64 MiB object at 10 MiB blocks is six full blocks and a 4 MiB tail: the
PUT is two batches through the double-buffered encode, the GET two through
the read-ahead decode, and a batch of four full blocks is more than one
launch holds.  Here the same shape at an eightieth of the size (64 KiB
blocks, one-tile shards at EC 8+4, a launch of 256 KiB), so:

* through the object layer a 6.4-block object lands on the drives as the
  plain reference (``benchmark/reference.py``) decodes it from 8 of 12 shard
  files, every parity shard among them; the whole GET and range GETs that
  start and end inside, on and across block and batch boundaries equal the
  body's slices, with two drives gone too;
* at the seam an encode call of 1-12 stripes goes out as launches of at most
  ``encode_rungs`` stripes each, parity and digests equal to the host codec's
  one call, and 200 seeded coalesced flushes trace no more encode programs
  than the ladder has rungs;
* the warm-up's encode family is exactly the programs the seam can launch;
* the stream's and the seam's new counters and the two new spans move by
  what a 7-block PUT and GET are made of.
"""

import io
import os
import shutil
import sys

import numpy as np
import pytest

from minio_tpu.codec import backend as backend_mod
from minio_tpu.codec.backend import CpuBackend, TpuBackend, reset_backend
from minio_tpu.codec.batcher import BatchingBackend
from minio_tpu.codec.telemetry import KERNEL_STATS, instrument
from minio_tpu.ops import codec_step
from minio_tpu.utils import spans

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference  # noqa: E402  (the harness's plain Reed-Solomon; imports no program code)

TILE = backend_mod.TILE_BYTES
K, M = 8, 4
BLOCK = 65536  # shards of 8192 bytes: half a tile, staged at one
SIZE = 6 * BLOCK + 26214  # six full blocks and a 0.4-block tail
LAUNCH = 2 * K * TILE  # a launch holds two stripes, as 32 MiB holds two of 10 MiB
BATCH = 4 * BLOCK  # DEFAULT_BATCH_BLOCKS blocks
BUCKET, KEY = "big", "seven-blocks"
# (offset, length): inside a block, on its edges, across blocks, across the batch
RANGES = [
    (100, 1000),  # inside the first block
    (BLOCK, BLOCK),  # exactly the second block
    (BLOCK - 1, 2),  # across a block boundary
    (3 * BLOCK + 5, BLOCK),  # from the last block of the first batch into the second
    (BATCH, 10),  # starts on the batch boundary
    (BATCH - 7, 2 * BLOCK),  # across the batch boundary
    (6 * BLOCK - 3, 26214 + 3),  # across the last full block's end to the object's end
    (1, SIZE - 2),  # nearly all: both batches, through the read-ahead
]


def stat(table: str) -> dict:
    return KERNEL_STATS.snapshot()[table]


def span_count(name: str) -> int:
    return sum(r["count"] for r in spans.snapshot()["spans"] if r["name"] == name)


@pytest.fixture(scope="module")
def one_chip():
    """The one-chip seam behind the process's backend, a launch cut to
    ``LAUNCH`` bytes; the suite's eight virtual devices would take the mesh."""
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_MESH", "0")
    mp.setenv("MINIO_ERASURE_BACKEND", "tpu")
    mp.delenv("MINIO_TPU_CODEC_INTERPRET", raising=False)
    mp.setattr(backend_mod, "LAUNCH_BYTES", LAUNCH)
    reset_backend()
    assert backend_mod.encode_rungs(K * TILE) == (1, 2)
    yield
    mp.undo()
    reset_backend()


@pytest.fixture(scope="module")
def stored(one_chip, tmp_path_factory):
    """(object layer, drive directories, body, what the PUT and one whole GET
    moved): the object PUT once, then read whole once."""
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage.xl import XLStorage

    root = tmp_path_factory.mktemp("multiblock")
    drives = [str(root / f"d{i}") for i in range(K + M)]
    ol = ErasureObjects([XLStorage(d) for d in drives], parity_blocks=M, block_size=BLOCK)
    ol.make_bucket(BUCKET)
    body = np.random.default_rng(64).integers(0, 256, SIZE, dtype=np.uint8).tobytes()
    before = KERNEL_STATS.snapshot()
    waits = (span_count("stream_readahead_wait"), span_count("get_first_write"))
    ol.put_object(BUCKET, KEY, io.BytesIO(body), len(body))
    buf = io.BytesIO()
    ol.get_object(BUCKET, KEY, buf)
    assert buf.getvalue() == body
    moved = {"before": before, "after": KERNEL_STATS.snapshot(),
             "waits": (span_count("stream_readahead_wait") - waits[0],
                       span_count("get_first_write") - waits[1])}
    # off the drive directories, before any test loses a drive: the shard files
    # and what the reference makes of 8 of them, every parity shard among the 8
    parts = reference.shards_on_drives(drives, BUCKET, KEY)
    use = [1, 3, 4, 6] + list(range(K, K + M))
    moved["shard_files"] = {i: os.path.getsize(p) for i, p in parts.items()}
    moved["decoded"] = reference.decode_object(parts, use, SIZE, K, M, BLOCK)
    moved["drive_of"] = {i: p[:p.index(os.sep + BUCKET + os.sep)] for i, p in parts.items()}
    return ol, drives, body, moved


def test_the_reference_decodes_all_seven_blocks_from_8_of_12_shard_files(stored):
    _, _, body, moved = stored
    # a shard file: six framed full blocks and the tail's shorter frame
    assert moved["shard_files"] == dict.fromkeys(range(K + M), 6 * (32 + 8192) + 32 + 3296)
    assert moved["decoded"] == body


@pytest.fixture(scope="module", params=[0, 2], ids=["all-drives", "two-drives-gone"])
def layer(request, stored):
    """The stored object, whole or after the drives of two of its data shards
    stopped being directories (as the benchmark's degraded cell loses drives)."""
    ol, _, body, moved = stored
    if request.param:
        for shard in (0, 5):
            shutil.rmtree(moved["drive_of"][shard])
            open(moved["drive_of"][shard], "w").close()
    return ol, body, request.param


def test_whole_get_is_the_body(layer):
    ol, body, lost = layer
    before = stat("reconstruct")["calls"]
    buf = io.BytesIO()
    ol.get_object(BUCKET, KEY, buf)
    assert buf.getvalue() == body
    assert (stat("reconstruct")["calls"] > before) == bool(lost)


@pytest.mark.parametrize("offset,length", RANGES)
def test_range_get_is_the_bodys_slice(layer, offset, length):
    ol, body, _ = layer
    buf = io.BytesIO()
    ol.get_object(BUCKET, KEY, buf, offset, length)
    assert buf.getvalue() == body[offset:offset + length]


# -- the seam -----------------------------------------------------------------


def host_encode(data: np.ndarray, lens: np.ndarray):
    """The host codec's one call at the exact width: (parity, digests)."""
    L = int(lens[0])
    return CpuBackend().encode(np.ascontiguousarray(data[:, :, :L]), M)


@pytest.mark.parametrize("stripes", range(1, 13))
def test_an_encode_call_goes_out_as_launches_within_the_cap(one_chip, stripes):
    import jax

    be = TpuBackend(devices=jax.devices()[:1])
    cap = backend_mod.launch_rows(K * TILE)
    assert cap == 2 == backend_mod.encode_rungs(K * TILE)[-1]
    rng = np.random.default_rng(stripes)
    L = 8192
    data = np.zeros((stripes, K, TILE), dtype=np.uint8)
    data[:, :, :L] = rng.integers(0, 256, (stripes, K, L), dtype=np.uint8)
    lens = np.full(stripes, L, dtype=np.int32)
    before = stat("launch")
    digests, ref = be.encode_digest_end(be.encode_digest_begin(data, M, lens))
    after = stat("launch")
    want_parity, want_digests = host_encode(data, lens)
    assert np.array_equal(digests, want_digests)
    assert np.array_equal(ref.drain()[:, :, :L], want_parity)
    launches = -(-stripes // cap)
    assert after["count"] - before["count"] == launches
    assert after["split_calls"] - before["split_calls"] == (launches > 1)
    moved = {int(s) // (K * TILE): n - before["sizes"].get(s, 0)
             for s, n in after["sizes"].items() if n != before["sizes"].get(s, 0)}
    assert moved == {n: c for n, c in ((2, stripes // 2), (1, stripes % 2)) if c}
    assert max(moved) <= cap  # (``max_bytes`` is since boot: other tests launch wider)
    # the eager pair cuts the same way
    parity, digests = be.encode(data, M, lens)
    assert np.array_equal(parity[:, :, :L], want_parity)
    assert np.array_equal(digests, want_digests)


def test_200_coalesced_flushes_trace_no_more_programs_than_the_ladder(monkeypatch):
    """Flushes of 1-6 jobs of 1-4 stripes (a stream's batches and tails from
    several PUTs at once), at a width no other test of this file uses: the
    batcher hands the seam pieces of whole jobs, the seam launches them at its
    rungs, every job gets its own rows back, and the programs are the rungs."""
    import jax

    monkeypatch.setattr(backend_mod, "LAUNCH_BYTES", 5 * K * 3 * TILE)  # cap 4
    width = 3 * TILE
    rungs = backend_mod.encode_rungs(K * width)
    assert rungs == (1, 2, 4)
    inner = TpuBackend(devices=jax.devices()[:1])
    b = BatchingBackend(instrument(inner))
    enc = codec_step.encode_words_fused1
    traced, L = enc._cache_size(), width - 64
    rng = np.random.default_rng(200)
    before = stat("launch")
    try:
        for _ in range(200):
            jobs = []
            for stripes in rng.integers(1, 5, rng.integers(1, 7)):
                data = np.zeros((stripes, K, width), dtype=np.uint8)
                data[:, :, :L] = rng.integers(0, 256, (stripes, K, 1), dtype=np.uint8)
                jobs.append(b._job("encode_digest", data,
                                   np.full(stripes, L, np.int32), lambda w: (K, w, M)))
            pieces = b._encode_pieces(jobs[0].key, jobs)
            assert sorted(map(id, (j for p in pieces for j in p))) == sorted(map(id, jobs))
            for piece in pieces:  # copied together no further than a launch
                assert len(piece) == 1 or sum(j.arrays[0].shape[0] for j in piece) <= 4
            b._run_group("encode_digest", jobs[0].key, jobs)
            for j in jobs:
                digests, ref = j.result
                want_parity, want_digests = host_encode(j.arrays[0], j.lengths)
                assert np.array_equal(digests, want_digests)
                assert np.array_equal(ref.drain()[:, :, :L], want_parity)
    finally:
        b.shutdown()
    after = stat("launch")
    assert enc._cache_size() - traced <= len(rungs)
    launched = {int(s) // (K * width) for s, n in after["sizes"].items()
                if n != before["sizes"].get(s, 0)}
    assert launched == set(rungs)


def test_the_warm_up_loads_every_encode_program_the_seam_can_launch(monkeypatch):
    import jax

    monkeypatch.setattr(backend_mod, "LAUNCH_BYTES", 5 * K * 5 * TILE)
    be = TpuBackend(devices=jax.devices()[:1])
    width = 5 * TILE  # a width of its own: what is traced here was not before
    rungs = backend_mod.encode_rungs(K * width)
    enc = codec_step.encode_words_fused1
    traced = enc._cache_size()
    thunks = list(be._family(("encode", K, M, width)))
    assert len(thunks) == len(rungs) == 3
    for thunk in thunks:
        thunk()
    warmed = enc._cache_size()
    # (an earlier test of this process may have traced one of them already)
    assert warmed - traced <= len(rungs)
    for stripes in range(1, 13):  # whatever coalesces: nothing new is traced
        data = np.zeros((stripes, K, width), dtype=np.uint8)
        _, ref = be.encode_digest_end(be.encode_digest_begin(data, M))
        ref.release()
    assert enc._cache_size() == warmed
    # at a full 10 MiB block of EC 8+4 that is one or two stripes, the tail's 1, 2, 4
    monkeypatch.setattr(backend_mod, "LAUNCH_BYTES", 32 << 20)  # as the server runs
    assert backend_mod.encode_rungs(8 * 1310720) == (1, 2)
    assert backend_mod.encode_rungs(8 * 524288) == (1, 2, 4)
    assert backend_mod.encode_rungs(16 * 655360) == (1, 2)
    assert backend_mod.encode_rungs(64 << 20) == (1,)


@pytest.mark.parametrize("rows", [9, 16, 24, 41, 64])
def test_a_digest_goes_out_at_the_rungs_the_warm_up_loads(rows, monkeypatch):
    """The tails of three 64 MiB GETs in one flush are 24 rows of 32 tiles: 16 + 8,
    not a 32-row program of its own (the chip met it inside a window: PERF.md
    PR 33).  Whatever coalesces, a digest is launched at a rung of its family."""
    import jax

    monkeypatch.setattr(backend_mod, "LAUNCH_BYTES", 32 << 20)
    be = TpuBackend(devices=jax.devices()[:1])
    width = 7 * TILE  # a width of its own
    rungs = backend_mod.digest_rungs(width)
    assert rungs == (1, 2, 3, 4, 5, 6, 7, 8, 16)
    assert backend_mod.digest_rungs(524288) == rungs  # the 64 MiB object's tail
    assert backend_mod.digest_rungs(1310720) == rungs  # and its full blocks
    assert len(list(be._family(("digest", width)))) == len(rungs)
    shards = np.random.default_rng(rows).integers(0, 256, (1, rows, width), dtype=np.uint8)
    before = stat("launch")
    got = be.digest(shards)
    after = stat("launch")
    assert np.array_equal(got, CpuBackend().digest(shards))
    launched = {int(s) // width: n - before["sizes"].get(s, 0)
                for s, n in after["sizes"].items() if n != before["sizes"].get(s, 0)}
    last = rows % 16
    want = {16: rows // 16}
    if last:
        want[next(r for r in rungs if r >= last)] = want.get(
            next(r for r in rungs if r >= last), 0) + 1
    assert launched == {r: n for r, n in want.items() if n} and set(launched) <= set(rungs)


# -- what a 7-block PUT and GET are made of -----------------------------------


@pytest.mark.parametrize("direction", ["encode", "decode"])
def test_stream_counters_of_a_seven_block_put_and_get(stored, direction):
    _, _, _, moved = stored
    a, b = moved["before"]["stream"][direction], moved["after"]["stream"][direction]
    assert {f: b[f] - a[f] for f in b} == {
        "streams": 1, "blocks": 7, "batches": 2, "tail_groups": 1}


def test_launch_counters_of_a_seven_block_put_and_get(stored):
    _, _, _, moved = stored
    a, b = moved["before"]["launch"], moved["after"]["launch"]
    passes = {t: moved["after"]["device_passes"].get(t, 0)
              - moved["before"]["device_passes"].get(t, 0)
              for t in ("encode_words_fused1", "digest_words")}
    # PUT: four full blocks as 2 + 2, then two full blocks and the tail.  GET: a
    # batch's shard rows are verified as their reads settle, in calls of any
    # size: 32 rows at once are 16 + 16, and no launch is ever longer
    assert passes["encode_words_fused1"] == 4 and passes["digest_words"] >= 4
    assert b["count"] - a["count"] == sum(passes.values())
    assert b["split_calls"] - a["split_calls"] >= 1  # the PUT's first batch
    sizes = {int(s): n - a["sizes"].get(s, 0) for s, n in b["sizes"].items()
             if n != a["sizes"].get(s, 0)}
    assert sum(sizes.values()) == b["count"] - a["count"]
    assert sum(s * n for s, n in sizes.items()) == b["bytes"] - a["bytes"]
    assert max(sizes) == LAUNCH and sizes[LAUNCH] >= 3 and sizes[K * TILE] >= 1
    assert b["max_bytes"] >= LAUNCH


def test_the_two_spans_of_a_seven_block_get(stored):
    _, _, _, moved = stored
    assert moved["waits"] == (2, 1)  # a wait a batch; one first write a GET
