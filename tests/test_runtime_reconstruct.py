"""The loss pattern as a runtime operand: one reconstruct program per
geometry, width and batch bucket, whatever rows a read decodes from.

* every one of the C(12, 8) = 495 survivor sets of EC 8+4, and a seeded
  sample of those of 4+2 and 16+4, decodes to the bytes that were
  encoded - through the portable form and through the Pallas form
  (interpreted: MINIO_TPU_CODEC_INTERPRET=1 at a tile-aligned width);
* batch sizes on and off the seam's ladder: padding rows never reach the
  output;
* 495 patterns trace ONE program;
* a served read with hedging and the outlier breaker firing (a slow
  drive, as tests/test_chaos.py injects it) reads back identical while
  ``reconstruct.patterns_seen`` grows and nothing new compiles.

The known answer is the data that was encoded (``gf.encode_ref``, plain
numpy over the multiplication table): a decode that returns it is right,
whatever matrix it used.
"""

import io
import itertools

import numpy as np
import pytest

from minio_tpu.codec import backend as backend_mod
from minio_tpu.codec.backend import TpuBackend
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.ops import codec_step, gf, rs_pallas

ALIGNED = 4 * rs_pallas._TW  # bytes: one kernel tile per shard row
SMALL = 256


def _one_device():
    """The seam as a one-chip server runs it (the suite's eight virtual
    devices would send every call over the mesh)."""
    import jax

    return TpuBackend(devices=jax.devices()[:1])


def _stripes(B, k, m, L, seed):
    """(B, n, L) encoded stripes and their (B, k, L) data."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
    parity = np.stack([gf.encode_ref(d, m) for d in data])
    return np.concatenate([data, parity], axis=1), data


def _patterns(k, m, sample, seed):
    """Survivor sets of a geometry: all of them, or a seeded sample
    that always holds the two extremes (no parity used, all of it)."""
    n = k + m
    if sample is None:
        return list(itertools.combinations(range(n), k))
    rng = np.random.default_rng(seed)
    picked = {tuple(range(k)), tuple(range(m, n))}
    while len(picked) < sample:
        picked.add(tuple(sorted(rng.choice(n, k, replace=False).tolist())))
    return sorted(picked)


def _lose(shards, survivors):
    """The stripes as a read would hold them: garbage where no row was
    read."""
    n = shards.shape[1]
    present = np.zeros(n, dtype=bool)
    present[list(survivors)] = True
    held = shards.copy()
    held[:, ~present] = 0xA5
    return held, present


CASES = [
    # geometry, sample (None = every pattern), form, batch; "mesh" is
    # the portable form over the suite's eight virtual devices
    ((8, 4), None, "mesh", 1),  # shard axis: matrix columns per device
    ((8, 4), 12, "mesh", 8),  # stripe axis
    ((4, 2), 15, "mesh", 3),
    ((8, 4), None, "portable", 1),
    ((8, 4), None, "portable", 11),  # off the ladder: padded to 16
    ((8, 4), None, "pallas", 1),
    ((8, 4), 12, "pallas", 2),
    ((8, 4), 12, "pallas", 9),  # off the ladder: padded to 16
    ((4, 2), 15, "portable", 1),  # C(6, 4) = 15: all of them
    ((4, 2), 15, "pallas", 3),
    ((16, 4), 24, "portable", 2),
    ((16, 4), 24, "portable", 7),
    ((16, 4), 6, "pallas", 1),
]


@pytest.mark.parametrize(
    "geometry,sample,form,batch",
    CASES,
    ids=[f"ec{k}+{m}-{s or 'all'}-{f}-B{b}" for (k, m), s, f, b in CASES],
)
def test_every_pattern_decodes_to_what_was_encoded(
    geometry, sample, form, batch, monkeypatch
):
    k, m = geometry
    L = ALIGNED if form == "pallas" else SMALL
    if form == "pallas":
        monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    assert codec_step.pallas_dispatch(L // 4)[0] == (form == "pallas")
    be = TpuBackend() if form == "mesh" else _one_device()
    shards, data = _stripes(batch, k, m, L, seed=k * 100 + m)
    before = KERNEL_STATS.snapshot()
    patterns = _patterns(k, m, sample, seed=batch)
    for survivors in patterns:
        held, present = _lose(shards, survivors)
        got = be.reconstruct(held, present, k, m)
        assert got.shape == (batch, k, L)  # no padding row comes out
        assert np.array_equal(got, data), f"survivors={survivors}"
    after = KERNEL_STATS.snapshot()
    name = "mesh_reconstruct" if form == "mesh" else "reconstruct_words_batch"
    ran = after["device_passes"][name] - before["device_passes"].get(name, 0)
    assert ran == len(patterns)
    pallas = after["pallas_passes"].get(name, 0) - before[
        "pallas_passes"
    ].get(name, 0)
    assert pallas == (ran if form == "pallas" else 0)


HEAL_CASES = [
    # geometry, sample of loss patterns, form: the heal pass over what
    # the grid above decodes plus a spare row, one chosen row rotted
    ((4, 2), 15, "portable"),
    ((8, 4), 24, "portable"),
    ((16, 4), 24, "portable"),
    ((4, 2), 6, "pallas"),
    ((8, 4), 6, "pallas"),
    ((16, 4), 3, "pallas"),
]


@pytest.mark.parametrize(
    "geometry,sample,form",
    HEAL_CASES,
    ids=[f"ec{k}+{m}-{s}-{f}" for (k, m), s, f in HEAL_CASES],
)
def test_heal_pass_verifies_and_decodes_past_a_rotted_survivor(
    geometry, sample, form, monkeypatch
):
    """``reconstruct_and_verify``, what heal runs: one fused pass a
    call, the verdict names the rotted row and the lost ones, and where
    the rot hit a row the decode chose, the stripe is re-solved from the
    rows that verified - the data comes back as encoded either way."""
    k, m = geometry
    n = k + m
    L = ALIGNED if form == "pallas" else SMALL
    if form == "pallas":
        monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    be = _one_device()
    shards, data = _stripes(2, k, m, L, seed=k * 10 + m)
    digests = be.digest(shards)
    name = "verify_and_reconstruct_words"
    before = KERNEL_STATS.snapshot()
    patterns = _patterns(k, m, sample, seed=n)
    for i, survivors in enumerate(patterns):
        # one row more than k was read: the spare to decode round the rot
        spare = next(r for r in range(n) if r not in survivors)
        held, present = _lose(shards, survivors + (spare,))
        want_ok = np.tile(present, (2, 1))
        rotted = np.flatnonzero(present)[i % k]  # a row the decode chose
        held[1, rotted, i % L] ^= 0x40
        want_ok[1, rotted] = False
        got, ok = be.reconstruct_and_verify(held, digests, present, k, m)
        assert np.array_equal(ok, want_ok), f"survivors={survivors}"
        assert np.array_equal(got, data), f"survivors={survivors}"
    after = KERNEL_STATS.snapshot()
    ran = after["device_passes"][name] - before["device_passes"].get(name, 0)
    assert ran == len(patterns)
    pallas = after["pallas_passes"].get(name, 0) - before[
        "pallas_passes"
    ].get(name, 0)
    assert pallas == (ran if form == "pallas" else 0)


def test_a_ninth_row_and_a_mask_order_change_nothing():
    """A hedged read may hold more than k rows: the decode uses the
    first k present, and a mask that differs only past them is the same
    pattern (one matrix, one table entry)."""
    k, m, L = 8, 4, SMALL
    be = _one_device()
    shards, data = _stripes(1, k, m, L, seed=9)
    held, present = _lose(shards, (0, 1, 3, 4, 5, 6, 7, 8))
    backend_mod._plans.clear()  # the table of a fresh process
    backend_mod._patterns.clear()
    seen = backend_mod.patterns_seen()
    assert np.array_equal(be.reconstruct(held, present, k, m), data)
    assert backend_mod.patterns_seen() == seen + 1
    held9, present9 = _lose(shards, (0, 1, 3, 4, 5, 6, 7, 8, 11))
    assert np.array_equal(be.reconstruct(held9, present9, k, m), data)
    assert backend_mod.patterns_seen() == seen + 1


def test_495_patterns_trace_one_program():
    """No pattern among the static arguments: every survivor set of EC
    8+4, at one width and batch, runs the program the first one traced -
    on the jitted entry and on the fused verify+reconstruct alike."""
    k, m, L = 8, 4, SMALL + 32  # a width no other test compiled
    n = k + m
    shards, data = _stripes(2, k, m, L, seed=5)
    words = codec_step.host_bytes_to_words(shards)
    digests = np.asarray(codec_step.digest_words(words, L))
    rb = codec_step.reconstruct_words_batch
    vr = codec_step.verify_and_reconstruct_words
    sizes = None
    for survivors in itertools.combinations(range(n), k):
        held, present = _lose(shards, survivors)
        idx, matrix = codec_step.host_pattern(present, k, m)
        got = rb(codec_step.host_bytes_to_words(held), idx, matrix, k, m)
        assert np.array_equal(
            codec_step.host_words_to_bytes(np.asarray(got)), data
        )
        got, ok = vr(
            codec_step.host_bytes_to_words(held), digests, present,
            idx, matrix, k, m, L,
        )
        assert np.array_equal(
            codec_step.host_words_to_bytes(np.asarray(got)), data
        )
        assert np.array_equal(np.asarray(ok), np.tile(present, (2, 1)))
        if sizes is None:  # after the first pattern: both are traced
            sizes = (rb._cache_size(), vr._cache_size())
    assert (rb._cache_size(), vr._cache_size()) == sizes


@pytest.mark.parametrize("rows,want", [
    (1, 1), (2, 2), (3, 3), (5, 5), (8, 8), (9, 16), (16, 16), (17, 32),
    (255, 256),
])
def test_the_ladder_is_unit_steps_to_eight_then_powers_of_two(rows, want):
    assert backend_mod.ladder(rows) == want


def test_a_launch_holds_a_power_of_two_of_rows_within_its_bytes():
    # a 10 MiB block at EC 8+4: 1.25 MiB rows, 15 MiB stripes
    row = 10 * (1 << 20) // 8
    assert backend_mod.launch_rows(row) == 16  # digest: rungs 1-8, 16
    assert backend_mod.launch_rows(12 * row) == 2  # reconstruct: 1, 2
    assert backend_mod.launch_rows(64 << 20) == 1  # never none
    assert backend_mod.launch_rows(512) == backend_mod.LADDER_CAP


@pytest.mark.parametrize("rows", [1, 2, 3, 4, 5, 6, 7, 8, 16])
def test_digest_at_every_rung_is_one_launch_of_the_cpu_codecs_digests(rows):
    """The rungs a healthy read of EC 8+4 settles on (1-8 shards of a
    block, 16 of a coalesced flush): one launch each, no padding (the
    rows lie at a rung of the width ladder), the digests the host codec
    computes."""
    be = _one_device()
    L = ALIGNED
    shards = np.random.default_rng(rows).integers(
        0, 256, (1, rows, L), dtype=np.uint8
    )
    before = KERNEL_STATS.snapshot()
    got = be.digest(shards)
    after = KERNEL_STATS.snapshot()
    assert np.array_equal(got, backend_mod.CpuBackend().digest(shards))
    assert (
        after["device_passes"]["digest_words"]
        - before["device_passes"].get("digest_words", 0)
    ) == 1
    h2d = {r["plane"]: r["bytes"] for r in after["h2d"]}
    h2d0 = {r["plane"]: r["bytes"] for r in before["h2d"]}
    assert h2d["data"] - h2d0.get("data", 0) == shards.nbytes  # no pad


def test_digest_rows_walk_the_ladder_and_split_above_a_launch(monkeypatch):
    """(B, n) flattens to rows; 9 rows run the 16-row program, and rows
    past one launch's bytes run as further launches - the digests are
    those of the rows, in order, and no padding row's comes out."""
    be = _one_device()
    L = 1024
    rng = np.random.default_rng(3)
    shards = rng.integers(0, 256, (1, 9, L), dtype=np.uint8)
    want = np.asarray(
        codec_step.digest_words(codec_step.host_bytes_to_words(shards), L)
    )
    dw = codec_step.digest_words
    assert np.array_equal(be.digest(shards), want)
    size = dw._cache_size()
    for B, n in ((1, 10), (1, 13), (2, 6), (1, 16), (3, 5)):  # -> 16 rows
        sh = rng.integers(0, 256, (B, n, L), dtype=np.uint8)
        ref = np.stack([
            np.asarray(codec_step.digest_words(
                codec_step.host_bytes_to_words(sh[b : b + 1]), L
            ))[0] for b in range(B)
        ])
        size = max(size, dw._cache_size())  # the references' own shapes
        assert np.array_equal(be.digest(sh), ref)
        assert dw._cache_size() == size  # the seam added no program
    # sixteen rows a launch: 41 rows = 16 + 16 + 9 (padded to 16)
    monkeypatch.setattr(
        backend_mod, "LAUNCH_BYTES", 16 * backend_mod.width_rung(L)
    )
    before = KERNEL_STATS.snapshot()["device_passes"]["digest_words"]
    size = dw._cache_size()
    sh = rng.integers(0, 256, (1, 41, L), dtype=np.uint8)
    got = be.digest(sh)
    after = KERNEL_STATS.snapshot()["device_passes"]["digest_words"]
    assert after - before == 3 and dw._cache_size() == size
    for r in range(41):
        one = np.asarray(codec_step.digest_words(
            codec_step.host_bytes_to_words(sh[:, r : r + 1]), L
        ))[0, 0]
        assert np.array_equal(got[0, r], one)


def test_reconstruct_splits_above_a_launch(monkeypatch):
    k, m, L = 4, 2, SMALL
    be = _one_device()
    shards, data = _stripes(5, k, m, L, seed=21)
    held, present = _lose(shards, (1, 2, 4, 5))
    monkeypatch.setattr(
        backend_mod, "LAUNCH_BYTES", 2 * 6 * backend_mod.width_rung(L)
    )
    before = KERNEL_STATS.snapshot()["device_passes"].get(
        "reconstruct_words_batch", 0
    )
    got = be.reconstruct(held, present, k, m)
    after = KERNEL_STATS.snapshot()["device_passes"][
        "reconstruct_words_batch"
    ]
    assert after - before == 3  # 2 + 2 + 1 stripes
    assert np.array_equal(got, data)


# -- the served path: hedges and the outlier breaker, one program ----------


def test_hedged_reads_decode_many_patterns_with_one_program(
    tmp_path, monkeypatch
):
    """Two drives made slow in turn under a dozen objects: reads hedge
    past them, decode from whichever rows came, and every object reads
    back identical.  The patterns seen grow; the programs do not, after
    the first pattern's; the breaker demotes for slowness, not errors."""
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage import health as disk_health
    from minio_tpu.storage.faults import FaultDisk
    from minio_tpu.storage.xl import XLStorage

    monkeypatch.setenv("MINIO_TPU_HEDGE_FACTOR", "2")
    monkeypatch.setenv("MINIO_TPU_HEDGE_MIN_MS", "2")
    disk_health.reset_registry()
    block = 4096
    fds = [
        FaultDisk(XLStorage(str(tmp_path / f"disk{i}")), seed=200 + i)
        for i in range(6)
    ]
    try:
        ol = ErasureObjects(fds, block_size=block)
        ol.make_bucket("hedged")
        rng = np.random.default_rng(27)
        objects = {}
        for i in range(12):
            body = rng.integers(0, 256, block, dtype=np.uint8).tobytes()
            ol.put_object("hedged", f"o{i}", io.BytesIO(body), len(body))
            objects[f"o{i}"] = body

        def read_all():
            for name, body in objects.items():
                buf = io.BytesIO()
                ol.get_object("hedged", name, buf)
                assert buf.getvalue() == body, name

        for _ in range(3):  # warm the digest ladder and the estimator
            read_all()
        # the first pattern compiles the reconstruct program
        fds[0].inject("read_at", delay_s=0.05)
        read_all()
        fds[0].clear()
        ks0 = KERNEL_STATS.snapshot()
        programs = (
            codec_step.reconstruct_words_batch._cache_size(),
            codec_step.digest_words._cache_size(),
        )
        for slow in (1, 2, 3):
            fds[slow].inject("read_at", delay_s=0.05)
            read_all()
            fds[slow].clear()
        ks1 = KERNEL_STATS.snapshot()
    finally:
        for fd in fds:
            fd.clear()
        disk_health.reset_registry()
    assert ks1["hedge"]["launched"] > ks0["hedge"]["launched"]
    assert ks1["hedge"]["shard_reads"] > ks0["hedge"]["shard_reads"]
    r0, r1 = ks0["reconstruct"], ks1["reconstruct"]
    assert r1["patterns_seen"] > r0["patterns_seen"]
    assert r1["matrix_cache"]["miss"] > r0["matrix_cache"]["miss"]
    assert r1["calls"] > r0["calls"]
    # no drive was lost: every one of these decodes is a healthy set's
    assert r1["healthy_calls"] - r0["healthy_calls"] == r1["calls"] - r0["calls"]
    assert r1["rows_rebuilt"] > r0["rows_rebuilt"]
    assert ks1["breaker"]["outlier"] >= 1
    assert ks1["breaker"]["error"] == ks0["breaker"]["error"]
    assert (
        codec_step.reconstruct_words_batch._cache_size(),
        codec_step.digest_words._cache_size(),
    ) == programs
