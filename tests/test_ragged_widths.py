"""A shard's byte length is a runtime operand of every codec program.

The seam stages a launch at a rung of its width ladder (whole kernel
tiles, ``codec.backend.width_rung``) and passes each row's true length
beside it, so:

* digests and parity of the operand form equal the plain numpy
  reference of the exact-width form, bit for bit, at every length round
  a tile boundary, through the portable XLA form and the interpreted
  Pallas kernels: encode, the healthy read's digest, verify+reconstruct
  with two rows lost;
* the programs a geometry can trace are bounded by the ladder, however
  many object sizes are PUT (200 seeded lengths);
* rows of different true lengths on one rung share one launch;
* the drive's bytes do not change: objects the host codec wrote at their
  exact widths read back through the device seam, and the seam writes
  the same shard files byte for byte.
"""

import io
import os
import re
import threading

import numpy as np
import pytest

from minio_tpu.codec import backend as backend_mod, bitrot
from minio_tpu.codec.backend import CpuBackend, TpuBackend, reset_backend
from minio_tpu.codec.batcher import BatchingBackend
from minio_tpu.codec.erasure import Erasure
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.ops import codec_step, hash as ph, rs_pallas

TILE = backend_mod.TILE_BYTES
K, M = 4, 2
DELTAS = (0, 1, 31, 32, 33)
# the issue's 16 object sizes (benchmark/traffic/mixed-randsize.json)
SIXTEEN = [round(40960 * 256 ** ((j + 0.5) / 16)) for j in range(16)]


@pytest.fixture(autouse=True)
def _fresh_backend_state():
    reset_backend()
    yield
    reset_backend()


def _seam(interpret: bool, monkeypatch) -> TpuBackend:
    """The one-chip seam: the portable XLA form, or the Pallas kernels
    under the interpreter (the CI kernel-regression mode)."""
    import jax

    if interpret:
        monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    else:
        monkeypatch.delenv("MINIO_TPU_CODEC_INTERPRET", raising=False)
    return TpuBackend(devices=jax.devices()[:1])


def _reference(data: np.ndarray, m: int):
    """The plain numpy reference at the exact width: (parity bytes,
    digests of data rows then parity rows)."""
    parity = backend_mod._numpy_encode(data, m)
    rows = np.concatenate([data, parity], axis=1)
    return parity, ph.phash256_host_batched(
        np.ascontiguousarray(rows).view(np.uint32), data.shape[-1]
    )


def _lengths_round(tiles: int) -> "list[int]":
    """Shard lengths, as stored (padded to 32 bytes), of shards of
    ``tiles`` tiles -/+ 0, 1, 31, 32, 33 bytes."""
    raw = {tiles * TILE + s * d for d in DELTAS for s in (-1, 1)}
    return sorted({bitrot.padded_len(n) for n in raw if n > 0})


def test_the_tile_is_the_kernels_tile_and_the_ladder_is_as_documented():
    assert TILE == 4 * rs_pallas._TW
    tiles = [w // TILE for w in backend_mod.width_rungs(80 * TILE)]
    assert tiles == [1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16, 20, 24, 28, 32,
                     40, 48, 56, 64, 80]
    # a full 10 MiB block of EC 8+4 is a rung: staged as it lies
    assert backend_mod.width_rung(10485760 // 8) == 10485760 // 8
    for nbytes in (1, 32, TILE - 32, TILE, TILE + 32, 9 * TILE, 17 * TILE + 5):
        w = backend_mod.width_rung(nbytes)
        assert w % TILE == 0 and nbytes <= w
        assert w <= max(TILE, 1.25 * TILE * -(-nbytes // TILE))


@pytest.mark.parametrize("interpret", [False, True], ids=["portable", "pallas"])
@pytest.mark.parametrize("tiles", [1, 2, 3, 4])
def test_operand_form_equals_the_plain_reference(tiles, interpret, monkeypatch):
    """Encode, healthy digest and verify+reconstruct with two rows lost,
    at every length round a tile boundary: the bits of the exact-width
    numpy reference."""
    be = _seam(interpret, monkeypatch)
    rng = np.random.default_rng(tiles)
    for L in _lengths_round(tiles):
        data = rng.integers(0, 256, (1, K, L), dtype=np.uint8)
        want_par, want_dig = _reference(data, M)
        # PUT: the fused encode+hash pass, parity behind its ref
        dig, ref = be.encode_digest_end(be.encode_digest_begin(data, M))
        par = ref.drain()
        assert par.shape == (1, M, L)
        assert np.array_equal(par, want_par), L
        assert np.array_equal(dig, want_dig), L
        # the healthy read's digest, and the same rows staged wider by
        # their caller with the length beside them
        shards = np.concatenate([data, want_par], axis=1)
        assert np.array_equal(be.digest(shards), want_dig), L
        wide = np.zeros((1, K + M, be.stage_width(L)), dtype=np.uint8)
        wide[..., :L] = shards
        lens = np.array([L], dtype=np.int32)
        assert np.array_equal(be.digest(wide, lens), want_dig), L
        # heal: verify + reconstruct in one pass, rows 0 and 2 lost
        present = np.ones(K + M, dtype=bool)
        present[[0, 2]] = False
        held = wide.copy()
        held[:, [0, 2]] = 0xA5
        got, ok = be.reconstruct_and_verify(
            held, want_dig, present, K, M, lens
        )
        assert np.array_equal(got[..., :L], data), L
        assert ok.tolist() == [present.tolist()], L
        # a degraded read's reconstruct
        assert np.array_equal(
            be.reconstruct(held, present, K, M)[..., :L], data
        ), L


@pytest.mark.parametrize("interpret", [False, True], ids=["portable", "pallas"])
def test_a_flipped_byte_inside_the_length_fails_its_digest_and_padding_cannot(
    interpret, monkeypatch
):
    be = _seam(interpret, monkeypatch)
    L = bitrot.padded_len(TILE + 33)
    data = np.random.default_rng(9).integers(0, 256, (1, K, L), dtype=np.uint8)
    parity, dig = _reference(data, M)
    wide = np.zeros((1, K + M, be.stage_width(L)), dtype=np.uint8)
    wide[..., :L] = np.concatenate([data, parity], axis=1)
    lens = np.array([L], dtype=np.int32)
    assert be.verify(wide, dig, lens).all()
    rotten = wide.copy()
    rotten[0, 1, L - 1] ^= 1
    assert be.verify(rotten, dig, lens).tolist() == [
        [True, False, True, True, True, True]
    ]
    junk = wide.copy()
    junk[0, :, L:] = 0x5A  # past the length: padding, nobody's bytes
    assert be.verify(junk, dig, lens).all()


def test_200_lengths_trace_no_more_programs_than_the_ladder_has_rungs(
    monkeypatch,
):
    """The continuum the benchmark's 16 sizes stand for: object lengths
    drawn log-uniform over [128 B, 10 MiB] at EC 8+4 are hundreds of
    distinct shard widths and at most one program a rung."""
    be = _seam(False, monkeypatch)
    k, m = 8, 4
    er = Erasure(k, m)
    rungs = backend_mod.width_rungs(er.shard_size_padded())
    rng = np.random.default_rng(31)
    sizes = np.exp(rng.uniform(np.log(128), np.log(10485760), 200)).astype(int)
    widths = {er.shard_size_padded(int(s)) for s in sizes}
    assert len(widths) > 150
    enc, dig = codec_step.encode_words_fused1, codec_step.digest_words
    enc0, dig0 = enc._cache_size(), dig._cache_size()
    ks0 = KERNEL_STATS.snapshot()["ragged"]
    for s in sizes:
        L = er.shard_size_padded(int(s))
        data = np.zeros((1, k, be.stage_width(L)), dtype=np.uint8)
        data[0, :, :L] = rng.integers(0, 256, (k, 1), dtype=np.uint8)
        lens = np.array([L], dtype=np.int32)
        d, ref = be.encode_digest_end(be.encode_digest_begin(data, m, lens))
        ref.release()
        assert np.array_equal(be.digest(data, lens), d[:, :k])
    staged = {backend_mod.width_rung(w) for w in widths}
    # one program a staged width at most (an earlier test of this process
    # may have traced one of them already), never one an object size
    assert len(staged) <= len(rungs) == 21
    assert 12 <= enc._cache_size() - enc0 <= len(staged)
    assert 12 <= dig._cache_size() - dig0 <= len(staged)
    assert len(staged) >= 12  # the draw reaches most of the ladder
    ks1 = KERNEL_STATS.snapshot()["ragged"]
    assert ks1["launches"] - ks0["launches"] == 400
    assert ks1["widths_true"] >= len(widths)
    assert ks1["widths_staged"] <= len(rungs)
    pad = (ks1["staged_bytes"] - ks0["staged_bytes"]) / (
        ks1["true_bytes"] - ks0["true_bytes"]
    )
    assert 1.0 <= pad < 1.25


class _Gated(TpuBackend):
    """Holds its first digest until released, so that what arrives
    meanwhile queues up behind the dispatcher and flushes together."""

    def __init__(self, devices):
        super().__init__(devices=devices)
        self.entered = threading.Event()
        self.release = threading.Event()

    def digest(self, shards, lengths=None):
        if not self.entered.is_set():
            self.entered.set()
            assert self.release.wait(30)
        return super().digest(shards, lengths)


@pytest.mark.parametrize("interpret", [False, True], ids=["portable", "pallas"])
def test_three_lengths_on_one_rung_share_one_launch(interpret, monkeypatch):
    import jax

    if interpret:
        monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    inner = _Gated(jax.devices()[:1])
    b = BatchingBackend(inner, deadline_s=5.0)
    rng = np.random.default_rng(5)
    lengths = [TILE + 32, TILE + 4096, 2 * TILE - 64]  # the two-tile rung
    datas = [rng.integers(0, 256, (1, K, L), dtype=np.uint8) for L in lengths]
    results = [None] * 3

    def put(i):
        results[i] = b.encode_digest_end(b.encode_digest_begin(datas[i], M))

    try:
        blocker = threading.Thread(
            target=b.digest, args=(np.zeros((1, 1, 64), np.uint8),)
        )
        blocker.start()
        assert inner.entered.wait(30)
        threads = [threading.Thread(target=put, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        while len(b._jobs) < 3:  # all three queued behind the held flush
            threading.Event().wait(0.005)
        before = KERNEL_STATS.snapshot()
        inner.release.set()
        for t in threads + [blocker]:
            t.join(30)
        after = KERNEL_STATS.snapshot()
    finally:
        inner.release.set()
        b.shutdown()
    launched = (
        after["device_passes"]["encode_words_fused1"]
        - before["device_passes"].get("encode_words_fused1", 0)
    )
    assert launched == 1
    r0, r1 = before["ragged"], after["ragged"]
    assert r1["mixed_launches"] - r0["mixed_launches"] == 1
    # the blocker's digest is the other launch; three stripes of K rows
    assert r1["rows"] - r0["rows"] == 3 * K + 1
    assert r1["true_bytes"] - r0["true_bytes"] == K * sum(lengths) + 64
    for i, L in enumerate(lengths):
        want_par, want_dig = _reference(datas[i], M)
        dig, ref = results[i]
        assert np.array_equal(dig, want_dig)
        par = ref.drain()
        assert par.shape == (1, M, L) and np.array_equal(par, want_par)


def _shard_files(root: str) -> "dict[str, bytes]":
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if f.startswith("part."):
                p = os.path.join(d, f)
                # <drive>/<bucket>/<object>/<data dir>/part.N: the data
                # dir is a fresh UUID a PUT, the rest names the shard
                rel = os.path.relpath(p, root).split(os.sep)
                out["/".join(rel[:3] + rel[4:])] = open(p, "rb").read()
    return out


@pytest.mark.parametrize("interpret", [False, True], ids=["portable", "pallas"])
def test_sixteen_ragged_sizes_round_trip_and_the_drives_bytes_do_not_change(
    interpret, tmp_path, monkeypatch
):
    """The fixture directory is written by the host codec at the exact
    widths (no tile, no ladder, no length operand: the form every
    object on a drive was written in).  The device seam reads it back
    byte for byte, and writes the same 16 objects as the same shard
    files.  Sizes are the benchmark's 16, cut to a sixteenth (a block
    of 640 KiB, shards of 1 to 9 tiles at EC 4+2)."""
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage.xl import XLStorage

    monkeypatch.setenv("MINIO_MESH", "0")
    monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1" if interpret else "")
    if not interpret:
        monkeypatch.delenv("MINIO_TPU_CODEC_INTERPRET")
    block = 655360
    sizes = [s // 16 for s in SIXTEEN]
    assert all(s % 32 and -(-s // K) % TILE for s in sizes)
    rng = np.random.default_rng(16)
    bodies = {f"o{j:02d}": rng.integers(0, 256, s, dtype=np.uint8).tobytes()
              for j, s in enumerate(sizes)}

    def layer(name, backend):
        monkeypatch.setenv("MINIO_ERASURE_BACKEND", backend)
        reset_backend()
        disks = [XLStorage(str(tmp_path / name / f"d{i}")) for i in range(K + M)]
        return ErasureObjects(disks, parity_blocks=M, block_size=block)

    def put_all(ol):
        ol.make_bucket("ragged")
        for name, body in bodies.items():
            ol.put_object("ragged", name, io.BytesIO(body), len(body))

    def get_all(ol):
        for name, body in bodies.items():
            buf = io.BytesIO()
            ol.get_object("ragged", name, buf)
            assert buf.getvalue() == body, name

    put_all(layer("reference", "cpu"))
    before = KERNEL_STATS.snapshot()
    get_all(layer("reference", "tpu"))  # the parent's objects, read here
    ol = layer("change", "tpu")
    put_all(ol)
    get_all(ol)
    after = KERNEL_STATS.snapshot()
    want, got = (_shard_files(str(tmp_path / n)) for n in ("reference", "change"))
    assert len(want) == 16 * (K + M) and want == got
    # every launch was of ragged rows at whole tiles, on few widths
    r0, r1 = before["ragged"], after["ragged"]
    assert r1["launches"] > r0["launches"]
    widths = {int(w) for w, n in r1["staged_rows"].items()
              if n != r0["staged_rows"].get(w, 0)}
    rungs = backend_mod.width_rungs(Erasure(K, M, block).shard_size_padded())
    assert widths and widths <= set(rungs) and len(rungs) == 9
    passes = {k: after["pallas_passes"].get(k, 0) - before["pallas_passes"].get(k, 0)
              for k in ("encode_words_fused1",)}
    assert (passes["encode_words_fused1"] > 0) == interpret
    # a later reader of the host codec takes the seam's files too
    get_all(layer("change", "cpu"))


def test_the_host_codec_takes_exact_widths_only():
    be = CpuBackend()
    data = np.zeros((1, K, 64), dtype=np.uint8)
    assert be.stage_width(4000) == 4000
    be.encode(data, M, np.array([64], np.int32))  # exact: fine
    with pytest.raises(ValueError, match="exact width"):
        be.encode(data, M, np.array([32], np.int32))
    with pytest.raises(ValueError, match="lengths"):
        TpuBackend().digest(data, np.array([96], np.int32))


def test_a_length_is_in_no_static_argnames():
    """``shard_len`` (or any length) static would be a program an object
    size again."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for sub in ("ops", "parallel"):
        d = os.path.join(root, "minio_tpu", sub)
        for name in sorted(os.listdir(d)):
            if not name.endswith(".py"):
                continue
            src = open(os.path.join(d, name)).read()
            for m in re.finditer(r"static_arg(?:names|nums)\s*=\s*\(([^)]*)\)", src):
                assert not re.search(r"shard_len|lengths?\b|nbytes", m.group(1)), (
                    name, m.group(0))
            assert not re.search(r"compile_kernel\([^)]*shard_len", src, re.S), name
    for fn in (codec_step.encode_words_fused1, codec_step.digest_words,
               codec_step.verify_and_reconstruct_words,
               codec_step.verify_hashes_words, codec_step.encode_and_hash_words):
        data = np.zeros((1, 2, 8), np.uint32)
        # two lengths, one program
        size = None
        for L in (32, 16):
            if fn is codec_step.encode_words_fused1:
                fn(np.zeros((1, 2, 8), np.uint32), 1, np.array([L], np.int32))
            elif fn is codec_step.encode_and_hash_words:
                fn(data, 1, np.array([L], np.int32))
            elif fn is codec_step.digest_words:
                fn(data, np.array([[L, L]], np.int32))
            elif fn is codec_step.verify_hashes_words:
                fn(data, np.zeros((1, 2, 8), np.uint32), np.array([[L, L]], np.int32))
            else:
                sv, mat = codec_step.host_pattern(np.ones(3, bool), 2, 1)
                fn(np.zeros((1, 3, 8), np.uint32), np.zeros((1, 3, 8), np.uint32),
                   np.ones(3, bool), sv, mat, 2, 1, np.array([L], np.int32))
            size = size or fn._cache_size()
            assert fn._cache_size() == size


def test_warming_loads_a_widths_family_behind_its_first_launch(monkeypatch):
    """A serving process (server/__main__ starts this, nothing else
    does) loads the sibling programs of a staged width on a background
    thread once a launch has shown the width: later launches of other
    row counts at it trace nothing."""
    import time

    import jax

    monkeypatch.setenv("MINIO_ERASURE_BACKEND", "tpu")
    monkeypatch.setenv("MINIO_CODEC_BATCH", "off")
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    reset_backend()
    be = backend_mod.get_backend()
    assert backend_mod._device_backend()._warmer is None  # not by itself
    assert backend_mod.start_warming()
    warmer = backend_mod._device_backend()._warmer
    try:
        L = 3 * TILE - 64
        data = np.random.default_rng(2).integers(0, 256, (1, K, L), dtype=np.uint8)
        dig, ref = be.encode_digest_end(be.encode_digest_begin(data, M))
        ref.release()
        want = sum(
            len(list(warmer._backend._family(f)))
            for f in (("encode", K, M, 3 * TILE), ("digest", 3 * TILE),
                      ("reconstruct", K, M, 3 * TILE))
        )
        assert want == 3 + 9 + 2
        deadline = time.monotonic() + 120
        while warmer.loaded < want and time.monotonic() < deadline:
            time.sleep(0.05)
        assert warmer.loaded == want
        sizes = (codec_step.encode_words_fused1._cache_size(),
                 codec_step.digest_words._cache_size(),
                 codec_step.reconstruct_words_batch._cache_size())
        rng = np.random.default_rng(3)
        for rows in (1, 5, 8):
            sh = rng.integers(0, 256, (1, rows, L - 32 * rows), dtype=np.uint8)
            assert np.array_equal(be.digest(sh), CpuBackend().digest(sh))
        two = rng.integers(0, 256, (2, K, L), dtype=np.uint8)
        dig, ref = be.encode_digest_end(be.encode_digest_begin(two, M))
        assert np.array_equal(ref.drain(), _reference(two, M)[0])
        assert sizes == (codec_step.encode_words_fused1._cache_size(),
                         codec_step.digest_words._cache_size(),
                         codec_step.reconstruct_words_batch._cache_size())
    finally:
        backend_mod.stop_warming()
    assert backend_mod._device_backend()._warmer is None
    assert not warmer._thread.is_alive()
