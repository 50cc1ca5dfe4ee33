"""One-kernel codec (fused1) tests.

Covers the single-pass PUT/GET codec kernels end to end:

* bit-identity of ``encode_words_fused1`` (portable and Pallas
  interpret, SWAR and MXU formulations) against the legacy entry AND
  the CPU-native reference, across k/m geometries including k=1, m=0,
  ragged tails, and all-zero stripes;
* bit-identity of ``verify_and_reconstruct_words`` against the
  verify_hashes_words -> reconstruct_words_batch pair, with bitrot;
* pass accounting through the backend seam: PUT is exactly ONE device
  pass before the drain in both kernel modes, the drain launches
  nothing with the transport screen off and the two screen passes with
  it on; fused1 heal is one pass where legacy takes two (KERNEL_STATS
  ``device_passes``, with the Pallas/portable split);
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


def _legacy_encode(words, m, L):
    """The legacy entry fused1 must match bit for bit."""
    parity, digests = codec_step.encode_and_hash_words(words, m, L)
    return np.asarray(parity), np.asarray(digests)


# -- bit-identity: fused1 vs legacy vs CPU native ------------------------

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
def test_fused1_portable_matches_legacy_and_native(k, m, L):
    B = 3
    data = _stripes(B, k, L, seed=k * 31 + m)
    data[1] = 0  # one all-zero stripe
    words = codec_step.host_bytes_to_words(data)
    parity, digests = codec_step.encode_words_fused1(
        jnp.asarray(words), m, L
    )
    lp, ld = _legacy_encode(jnp.asarray(words), m, L)
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


@pytest.mark.parametrize("formulation", ["swar", "mxu"])
def test_fused1_pallas_interpret_smoke(formulation):
    """Fast tier-1 smoke: one Pallas tile through the interpreter."""
    k, m, L = 2, 1, 4 * rs_pallas._TW
    data = _stripes(2, k, L, seed=9)
    data[0, :, : L // 2] = 0
    words = jnp.asarray(codec_step.host_bytes_to_words(data))
    got = codec_step.encode_words_fused1(
        words, m, L, formulation, True, True
    )
    want = _legacy_encode(words, m, L)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w_)


@pytest.mark.slow
@pytest.mark.parametrize("formulation", ["swar", "mxu"])
@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 4)])
def test_fused1_pallas_interpret_full_grid(k, m, formulation):
    """The full FUSED_GRID geometry through the Pallas interpreter."""
    L = 4 * rs_pallas._TW
    data = _stripes(2, k, L, seed=k + m)
    data[1] = 0
    words = jnp.asarray(codec_step.host_bytes_to_words(data))
    got = codec_step.encode_words_fused1(
        words, m, L, formulation, True, True
    )
    want = _legacy_encode(words, m, L)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w_)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_get_matches_legacy_pair(use_pallas):
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
        "swar",
        use_pallas,
        use_pallas,  # interpret mode when exercising the Pallas path
    )
    ok_legacy = np.asarray(
        codec_step.verify_hashes_words(
            jnp.asarray(shards), jnp.asarray(digests), L
        )
    ) & np.asarray(present, bool)
    data_legacy = np.asarray(
        codec_step.reconstruct_words_batch(
            jnp.asarray(shards), survivors, matrix, k, m
        )
    )
    np.testing.assert_array_equal(np.asarray(got_ok), ok_legacy)
    np.testing.assert_array_equal(np.asarray(got_data), data_legacy)


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


def _encode_passes(mode, compress, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CODEC_KERNEL", mode)
    monkeypatch.setenv("MINIO_TPU_DEVICE_COMPRESS", compress)
    be = TpuBackend()
    data = _stripes(2, 4, 4096, seed=2)
    data[:, :, : 4096 // 2] = 0  # sparse: the pack pass must run
    KERNEL_STATS.reset()
    dig, ref = be.encode_digest_end(be.encode_digest_begin(data, 2))
    pre = dict(KERNEL_STATS.snapshot()["device_passes"])
    par = ref.drain()
    ref.release()
    post = dict(KERNEL_STATS.snapshot()["device_passes"])
    want_par, want_dig = CpuBackend().encode(data, 2)
    np.testing.assert_array_equal(dig, want_dig)
    np.testing.assert_array_equal(par, want_par)
    return pre, post


_PUT_ENTRY = {
    "fused1": "encode_words_fused1",
    "legacy": "encode_and_hash_words_digest",
}


@pytest.mark.parametrize("mode", ["fused1", "legacy"])
def test_put_is_one_device_pass_unscreened(single_device, monkeypatch, mode):
    """Default transport (MINIO_TPU_DEVICE_COMPRESS=off): one launch per
    batch, and the drain launches nothing."""
    pre, post = _encode_passes(mode, "off", monkeypatch)
    assert pre == {_PUT_ENTRY[mode]: 1}
    assert post == pre, f"drain launched kernels: {post}"


@pytest.mark.parametrize("mode", ["fused1", "legacy"])
def test_put_screened_drain_is_two_more_passes(
    single_device, monkeypatch, mode
):
    pre, post = _encode_passes(mode, "on", monkeypatch)
    assert pre == {_PUT_ENTRY[mode]: 1}
    assert sum(post.values()) == 3, post
    assert post["group_flags"] == 1
    assert post["pack_nonzero_groups"] == 1


def test_pallas_and_portable_passes_counted_apart(
    single_device, monkeypatch
):
    """A tile-aligned batch under the interpreter counts as a Pallas
    pass; a ragged one (and the XLA-only digest) as portable."""
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
    assert snap["pallas_passes"] == {"encode_words_fused1": 1}
    assert snap["portable_passes"] == {
        "digest_words": 1,
        "encode_words_fused1": 1,
    }


def test_fused1_digest_only_before_drain(single_device, monkeypatch):
    """MTPU107 contract at runtime: only digest bytes cross D2H at the
    end seam; the parity plane waits for drain."""
    monkeypatch.setenv("MINIO_TPU_CODEC_KERNEL", "fused1")
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


@pytest.mark.parametrize("mode", ["legacy", "fused1"])
def test_backend_reconstruct_and_verify_modes_agree(
    single_device, monkeypatch, mode
):
    monkeypatch.setenv("MINIO_TPU_CODEC_KERNEL", mode)
    tb, cb = TpuBackend(), CpuBackend()
    k, m, L = 4, 2, 1024
    data = _stripes(3, k, L, seed=8)
    par, dig = cb.encode(data, m)
    shards = np.concatenate([data, par], axis=1).copy()
    present = [True] * (k + m)
    present[1] = False
    shards[:, 1] = 0
    shards[:, 2, 7] ^= 0x80  # bitrot on a chosen survivor: re-pick path
    KERNEL_STATS.reset()
    got, ok = tb.reconstruct_and_verify(shards, dig, tuple(present), k, m)
    passes = KERNEL_STATS.snapshot()["device_passes"]
    want, wok = cb.reconstruct_and_verify(shards, dig, tuple(present), k, m)
    np.testing.assert_array_equal(ok, wok)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, data)
    if mode == "fused1":
        assert passes.get("verify_and_reconstruct_words") == 1
    else:
        assert passes.get("digest_words") == 1
        assert passes.get("reconstruct_words_batch", 0) >= 1


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


def test_fused1_is_default_and_legacy_oracle_selectable(monkeypatch):
    monkeypatch.delenv("MINIO_TPU_CODEC_KERNEL", raising=False)
    assert codec_step.codec_kernel_mode() == "fused1"
    monkeypatch.setenv("MINIO_TPU_CODEC_KERNEL", "legacy")
    assert codec_step.codec_kernel_mode() == "legacy"
    # unknown values fall back to the default, matching the other
    # codec knobs (device_compress_mode et al.)
    monkeypatch.setenv("MINIO_TPU_CODEC_KERNEL", "bogus")
    assert codec_step.codec_kernel_mode() == "fused1"
