"""One-pass codec tests.

Covers the single-pass PUT/GET codec kernels end to end:

* bit-identity of ``encode_words_fused1`` (portable and Pallas
  interpret) against the eager seam's ``encode_and_hash_words`` AND the
  CPU-native reference, across k/m geometries including k=1, m=0,
  ragged tails, and all-zero stripes;
* bit-identity of ``verify_and_reconstruct_words`` against the
  verify_hashes_words -> reconstruct_words_batch pair, with bitrot;
* the seam against ``CpuBackend`` over the degenerate shapes (k=1, m=0,
  a ragged row) and drop patterns, PUT and heal;
* pass accounting through the backend seam: PUT is exactly ONE device
  pass and the drain launches nothing; heal is one pass
  (KERNEL_STATS ``device_passes``, with the Pallas/portable split);
* the digest-only contract: ``encode_digest_end`` materializes digest
  bytes only, the parity plane crosses D2H at drain;
* donation safety: ``donate_argnums`` on the data words never corrupts
  a retained reference or the host source array.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from minio_tpu.codec.backend import (
    CpuBackend,
    TpuBackend,
    reset_backend,
)
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.ops import codec_step, gf, hash as ph, rs_pallas


@pytest.fixture(autouse=True)
def _fresh_backend_state():
    reset_backend()
    yield
    reset_backend()


@pytest.fixture
def single_device(monkeypatch):
    """Force the single-device codec path (no 8-device test mesh)."""
    monkeypatch.setenv("MINIO_MESH", "0")


def _stripes(batch, k, length, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (batch, k, length)).astype(np.uint8)


def _eager_encode(words, m, L):
    """The eager seam's entry (heal's re-encode): the same bits."""
    parity, digests = codec_step.encode_and_hash_words(words, m, L)
    return np.asarray(parity), np.asarray(digests)


# -- bit-identity: fused1 vs the eager seam vs CPU native ----------------

# (k, m, L): k=1 degenerate, m=0 digest-only, ragged tail (w=24 words,
# under one 128-lane hash row) and a width with a ragged hash tail
# behind full rows (w=136), all covered.
_GEOMETRIES = [
    (1, 1, 128),
    (2, 1, 128),
    (4, 2, 256),
    (8, 4, 256),
    (4, 0, 128),
    (4, 2, 96),
    (4, 2, 544),
    (4, 2, 2048),
]


@pytest.mark.parametrize("k,m,L", _GEOMETRIES)
def test_fused1_portable_matches_eager_and_native(k, m, L):
    B = 3
    data = _stripes(B, k, L, seed=k * 31 + m)
    data[1] = 0  # one all-zero stripe
    words = codec_step.host_bytes_to_words(data)
    parity, digests = codec_step.encode_words_fused1(
        jnp.asarray(words), m, L
    )
    lp, ld = _eager_encode(jnp.asarray(words), m, L)
    np.testing.assert_array_equal(np.asarray(parity), lp)
    np.testing.assert_array_equal(np.asarray(digests), ld)
    # CPU-native reference: gf.encode_ref parity + phash256_host digests
    pbytes = codec_step.host_words_to_bytes(np.asarray(parity))
    for b in range(B):
        if m:
            np.testing.assert_array_equal(
                pbytes[b], gf.encode_ref(data[b], m)
            )
        rows = np.concatenate([data[b], pbytes[b]], axis=0)
        for s in range(k + m):
            want = ph.phash256_host(rows[s].tobytes())
            assert np.asarray(digests)[b, s].tobytes() == want


def test_fused1_pallas_interpret_smoke():
    """Fast tier-1 smoke: one Pallas tile through the interpreter."""
    k, m, L = 2, 1, 4 * rs_pallas._TW
    data = _stripes(2, k, L, seed=9)
    data[0, :, : L // 2] = 0
    words = jnp.asarray(codec_step.host_bytes_to_words(data))
    got = codec_step.encode_words_fused1(words, m, L, True, True)
    want = _eager_encode(words, m, L)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w_)


@pytest.mark.slow
@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 4)])
def test_fused1_pallas_interpret_full_grid(k, m):
    """The full FUSED_GRID geometry through the Pallas interpreter."""
    L = 4 * rs_pallas._TW
    data = _stripes(2, k, L, seed=k + m)
    data[1] = 0
    words = jnp.asarray(codec_step.host_bytes_to_words(data))
    got = codec_step.encode_words_fused1(words, m, L, True, True)
    want = _eager_encode(words, m, L)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w_)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_get_matches_verify_reconstruct_pair(use_pallas):
    """verify_and_reconstruct_words == verify -> reconstruct, bitrot."""
    k, m = 4, 2
    L = (4 * rs_pallas._TW) if use_pallas else 256
    n = k + m
    data = _stripes(2, k, L, seed=17)
    words = codec_step.host_bytes_to_words(data)
    parity, digests = codec_step.encode_and_hash_words(
        jnp.asarray(words), m, L
    )
    shards = np.concatenate(
        [words, np.asarray(parity)], axis=1
    ).copy()
    digests = np.asarray(digests)
    present = [True] * n
    present[0] = False  # lost
    shards[:, 0] = 0
    shards[1, 3, 5] ^= 0xDEAD  # bitrot on a non-survivor-critical row
    survivors, matrix = codec_step.host_pattern(present, k, m)
    got_data, got_ok = codec_step.verify_and_reconstruct_words(
        jnp.asarray(shards),
        jnp.asarray(digests),
        np.asarray(present),
        survivors,
        matrix,
        k,
        m,
        L,
        use_pallas,
        use_pallas,  # interpret mode when exercising the Pallas path
    )
    ok_pair = np.asarray(
        codec_step.verify_hashes_words(
            jnp.asarray(shards), jnp.asarray(digests), L
        )
    ) & np.asarray(present, bool)
    data_pair = np.asarray(
        codec_step.reconstruct_words_batch(
            jnp.asarray(shards), survivors, matrix, k, m
        )
    )
    np.testing.assert_array_equal(np.asarray(got_ok), ok_pair)
    np.testing.assert_array_equal(np.asarray(got_data), data_pair)


def test_fused_get_below_quorum_raises():
    k, m, L = 4, 2, 256
    present = (True, False, False, True, True, False)
    with pytest.raises(ValueError, match="shards"):
        codec_step.host_pattern(present, k, m)
    # and the device program refuses operands that are not a pattern's
    with pytest.raises(ValueError, match="survivor"):
        codec_step.verify_and_reconstruct_words(
            jnp.zeros((1, 6, L // 4), jnp.uint32),
            jnp.zeros((1, 6, 8), jnp.uint32),
            np.asarray(present),
            np.asarray([0, 3, 4], np.int32),
            np.zeros((3, 3), np.uint8),
            k,
            m,
            L,
        )


# -- the backend seam: pass accounting + digest-only contract ------------


# (B, k, m, L_bytes, dropped shards): k=1 (a parity-only survivor), m=0
# (digest-only, nothing to drop), the ragged 11,264-byte row (w=2816, no
# kernel tile divides it), multi-loss reconstruction.
SEAM_GRID = [
    (2, 4, 2, 4096, (1, 4)),
    (1, 1, 1, 4096, (0,)),
    (2, 3, 0, 4096, ()),
    (1, 4, 2, 11264, (0, 5)),
    (2, 2, 1, 3072, (2,)),
]
_SEAM_IDS = [f"B{B}-ec{k}+{m}-L{L}" for B, k, m, L, _ in SEAM_GRID]


@pytest.mark.parametrize(
    "B,k,m,L", [c[:4] for c in SEAM_GRID], ids=_SEAM_IDS
)
def test_put_seam_matches_cpu_backend(single_device, B, k, m, L):
    """PUT through the digest seam: digests at _end, parity at drain,
    one launch, and the drain launches nothing."""
    data = _stripes(B, k, L, seed=L + k)
    be = TpuBackend()
    KERNEL_STATS.reset()
    dig, ref = be.encode_digest_end(be.encode_digest_begin(data, m))
    pre = dict(KERNEL_STATS.snapshot()["device_passes"])
    par = be.drain(ref)
    ref.release()
    assert pre == {"encode_words_fused1": 1}
    assert KERNEL_STATS.snapshot()["device_passes"] == pre
    want_par, want_dig = CpuBackend().encode(data, m)
    np.testing.assert_array_equal(dig, want_dig)
    np.testing.assert_array_equal(par, want_par)


@pytest.mark.parametrize("B,k,m,L,drop", SEAM_GRID, ids=_SEAM_IDS)
def test_heal_seam_matches_cpu_backend(single_device, B, k, m, L, drop):
    """GET side of the same shapes: reconstruct_and_verify and
    reconstruct with the dropped rows gone, against CpuBackend."""
    data = _stripes(B, k, L, seed=L + k)
    tb, cb = TpuBackend(), CpuBackend()
    par, dig = cb.encode(data, m)
    shards = np.concatenate([data, par], axis=1)
    present = np.array([i not in drop for i in range(k + m)])
    shards[:, ~present] = 0x5A  # garbage where the shard is gone
    got, ok = tb.reconstruct_and_verify(shards, dig, present, k, m)
    want, wok = cb.reconstruct_and_verify(shards, dig, present, k, m)
    np.testing.assert_array_equal(ok, wok)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, data)
    np.testing.assert_array_equal(
        tb.reconstruct(shards, present, k, m), data
    )


@pytest.mark.parametrize("B", [2, 3, 4])
def test_put_seam_pallas_batches_match_cpu_backend(
    single_device, monkeypatch, B
):
    """A coalesced flush of 2-4 blocks (what the benchmark's traffic
    makes) through the kernel's (batch, w-tile) grid, interpreted."""
    monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    k, m, L = 4, 2, 4 * rs_pallas._TW
    data = _stripes(B, k, L, seed=B)
    be = TpuBackend()
    KERNEL_STATS.reset()
    dig, ref = be.encode_digest_end(be.encode_digest_begin(data, m))
    par = be.drain(ref)
    assert KERNEL_STATS.snapshot()["pallas_passes"] == {
        "encode_words_fused1": 1
    }
    want_par, want_dig = CpuBackend().encode(data, m)
    np.testing.assert_array_equal(dig, want_dig)
    np.testing.assert_array_equal(par, want_par)


def test_pallas_and_portable_passes_counted_apart(
    single_device, monkeypatch
):
    """Under the interpreter every width counts as a Pallas pass - the
    seam stages a ragged one at whole tiles and passes its length - and
    the XLA-only digest as portable."""
    monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    be = TpuBackend()
    KERNEL_STATS.reset()
    aligned = _stripes(1, 2, 4 * rs_pallas._TW, seed=3)
    ragged = _stripes(1, 2, 4096, seed=4)
    for data in (aligned, ragged):
        _, ref = be.encode_digest_end(be.encode_digest_begin(data, 1))
        ref.release()
    be.digest(aligned)
    snap = KERNEL_STATS.snapshot()
    assert snap["device_passes"]["encode_words_fused1"] == 2
    assert snap["pallas_passes"] == {"encode_words_fused1": 2}
    assert snap["portable_passes"] == {"digest_words": 1}
    # one program: the two lengths shared the one-tile rung
    assert snap["ragged"]["widths_true"] == 2
    assert snap["ragged"]["staged_rows"] == {str(4 * rs_pallas._TW): 6}


def test_fused1_digest_only_before_drain(single_device):
    """MTPU107 contract at runtime: only digest bytes cross D2H at the
    end seam; the parity plane waits for drain."""
    be = TpuBackend()
    data = _stripes(2, 4, 4096, seed=6)
    KERNEL_STATS.reset()
    dig, ref = be.encode_digest_end(be.encode_digest_begin(data, 2))
    planes = {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    }
    assert planes.get("data", 0) == dig.nbytes
    assert planes.get("parity", 0) == 0
    par = ref.drain()
    ref.release()
    planes = {
        d["plane"]: d["bytes"] for d in KERNEL_STATS.snapshot()["d2h"]
    }
    assert planes["parity"] > 0
    np.testing.assert_array_equal(par, CpuBackend().encode(data, 2)[0])


@pytest.mark.parametrize("form", ["portable", "pallas"])
def test_backend_reconstruct_and_verify_modes_agree(
    single_device, monkeypatch, form
):
    """Both forms of the one heal pass agree with CpuBackend, the
    re-pick after a rotted survivor included."""
    if form == "pallas":
        monkeypatch.setenv("MINIO_TPU_CODEC_INTERPRET", "1")
    tb, cb = TpuBackend(), CpuBackend()
    k, m = 4, 2
    L = 4 * rs_pallas._TW if form == "pallas" else 1024
    data = _stripes(3, k, L, seed=8)
    par, dig = cb.encode(data, m)
    shards = np.concatenate([data, par], axis=1).copy()
    present = [True] * (k + m)
    present[1] = False
    shards[:, 1] = 0
    shards[:, 2, 7] ^= 0x80  # bitrot on a chosen survivor: re-pick path
    KERNEL_STATS.reset()
    got, ok = tb.reconstruct_and_verify(shards, dig, tuple(present), k, m)
    snap = KERNEL_STATS.snapshot()
    want, wok = cb.reconstruct_and_verify(shards, dig, tuple(present), k, m)
    np.testing.assert_array_equal(ok, wok)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, data)
    name = "verify_and_reconstruct_words"
    assert snap["device_passes"].get(name) == 1
    assert snap["pallas_passes"].get(name, 0) == (form == "pallas")


# -- donation safety -----------------------------------------------------


def test_donated_words_never_corrupt_retained_reference():
    """donate_argnums=(0,) may alias the data-words buffer into the
    parity output; a value retained by the caller must stay intact."""
    k, m, L = 4, 2, 2048
    host = _stripes(1, k, L, seed=12)
    words_np = codec_step.host_bytes_to_words(host)
    words = jnp.asarray(words_np)
    retained = words ^ 0  # independent buffer derived pre-donation
    out1 = codec_step.encode_words_fused1(words, m, L)
    np.testing.assert_array_equal(np.asarray(retained), words_np)
    assert np.array_equal(words_np, codec_step.host_bytes_to_words(host))
    # repeat-call determinism: a fresh transfer reproduces everything
    out2 = codec_step.encode_words_fused1(jnp.asarray(words_np), m, L)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
