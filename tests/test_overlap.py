"""Host-driven transfer/compute overlap: the sub-chunk pipeline behind
MINIO_TPU_CODEC_OVERLAP=async.

Bit-identity is the whole contract — ``async`` (sub-chunked ping-pong
chain) must produce byte-identical digests, parity and GET
reconstructions vs ``off`` (one pass per batch) across the geometry
grid: k=1, m=0, ragged tails, sub-chunk sizes that do not divide the
stripe, and the S<3 degenerate fallback.  Also covered:
encode_digest_end idempotency for the sub-chunked handle,
donation-aliasing of the ping-pong buffers, the staging-bytes ledger
lifecycle, overlap-window telemetry, and the warn-once mesh notice.
(The manual-DMA ``pipeline`` kernels were removed: Mosaic refused
them, tests/test_tpu_compile.py.)
"""

import warnings

import numpy as np
import pytest

from minio_tpu.codec import backend as backend_mod
from minio_tpu.codec.backend import TpuBackend, reset_backend
from minio_tpu.codec.erasure import subchunk_words
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.ops import codec_step, hash as phash
from minio_tpu.parallel import mesh as pmesh


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("MINIO_MESH", "0")
    reset_backend()
    KERNEL_STATS.reset()
    yield
    reset_backend()


def _data(B, k, L, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (B, k, L), dtype=np.uint8
    )


def _roundtrip(data, m, drop=()):
    """PUT digest-seam encode + drain + GET reconstruct_and_verify."""
    B, k, L = data.shape
    be = TpuBackend()
    h = be.encode_digest_begin(data, m)
    digests, ref = be.encode_digest_end(h)
    parity = ref.drain()
    n = k + m
    shards = np.concatenate(
        [data, parity.reshape(B, m, L)], axis=1
    ).copy()
    present = [i not in drop for i in range(n)]
    for i in drop:
        shards[:, i, :] = 0x5A  # garbage where the shard is gone
    out, ok = be.reconstruct_and_verify(shards, digests, present, k, m)
    return np.asarray(digests), np.asarray(parity), out, ok


def _modes_equal(monkeypatch, mode, data, m, drop=(), sub_kb=None):
    """Run ``off`` then ``mode``; assert every output bit-identical."""
    if sub_kb is not None:
        monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", str(sub_kb))
    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", "off")
    base = _roundtrip(data, m, drop)
    KERNEL_STATS.reset()
    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", mode)
    got = _roundtrip(data, m, drop)
    for b, g, what in zip(base, got, ("digests", "parity", "data", "ok")):
        assert np.array_equal(b, g), f"{mode}: {what} diverged"
    return KERNEL_STATS.snapshot()


# -- sub-chunk sizing (erasure.subchunk_words) ---------------------------


def test_subchunk_words_quantized_and_clamped(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "4")
    # 4 KiB = 1024 words, rounded down to the group quantum
    assert subchunk_words(1024 * 3, 256) == 1024
    assert subchunk_words(1024 * 3, 768) == 768
    # S < 3: pipeline refuses (ping-pong cannot amortize)
    assert subchunk_words(1024 * 2, 256) == 0
    # never below one quantum
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "0.001")
    assert subchunk_words(256 * 64, 256) == 256
    # garbage env falls back to the default 256 KiB
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "bogus")
    assert subchunk_words(65536 * 4, 256) == 65536


# -- async twin: bit-identity across the geometry grid -------------------

# (B, k, m, L_bytes, sub_kb, dropped shards): exercises k=1, m=0,
# ragged tails (cw not dividing w) and multi-loss reconstruction.
ASYNC_GRID = [
    (2, 4, 2, 4096, 1, (1, 4)),     # w=1024, cw=256, S=4, dividing
    (1, 1, 1, 4096, 1, (0,)),       # k=1: parity-only survivor
    (2, 3, 0, 4096, 1, ()),         # m=0: digest-only, nothing to drop
    (1, 4, 2, 11264, 3, (0, 5)),    # w=2816, cw=768: ragged tail 512
    (2, 2, 1, 3072, 1, (2,)),       # w=768, cw=256, S=3 exactly
]


@pytest.mark.parametrize("B,k,m,L,sub_kb,drop", ASYNC_GRID)
def test_async_bit_identical_to_off(monkeypatch, B, k, m, L, sub_kb, drop):
    snap = _modes_equal(
        monkeypatch, "async", _data(B, k, L, seed=L), m,
        drop=drop, sub_kb=sub_kb,
    )
    ow = snap["overlap_windows"]
    assert ow["put"] > 0, "async PUT pipeline never overlapped"
    if m or drop or True:  # GET always runs in _roundtrip
        assert ow["get"] > 0, "async GET pipeline never overlapped"
    assert snap["device_passes"].get("encode_subchunk_words", 0) >= 3


def test_async_sparse_parity_packs_per_chunk(monkeypatch):
    """A sparse tail keeps the packed-prefix drain leg bit-identical
    per chunk (the occupancy screen runs chunk-locally)."""
    monkeypatch.setenv("MINIO_TPU_DEVICE_COMPRESS", "auto")
    data = _data(2, 4, 11264, seed=9)
    data[:, :, 2048:] = 0  # zero tail -> zero parity groups there
    _modes_equal(monkeypatch, "async", data, 2, drop=(1,), sub_kb=3)


def test_async_degenerate_small_batch_falls_back(monkeypatch):
    """S < 3 chunks: the async mode must fall back to the serialized
    path (bit-identical trivially) and record zero overlap windows."""
    snap = _modes_equal(
        monkeypatch, "async", _data(1, 2, 1024), 1, drop=(0,), sub_kb=256
    )
    assert snap["overlap_windows"] == {"put": 0, "get": 0}
    assert "encode_subchunk_words" not in snap["device_passes"]
    assert snap["device_passes"].get("encode_words_fused1") == 1


def test_removed_pipeline_value_resolves_to_off(monkeypatch):
    """``pipeline`` is no longer a value of the knob: like any unknown
    value it resolves to the default, never to another kernel."""
    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", "pipeline")
    assert codec_step.codec_overlap_mode() == "off"
    monkeypatch.delenv("MINIO_TPU_CODEC_OVERLAP")
    assert codec_step.codec_overlap_mode() == "off"


# -- handle lifecycle ----------------------------------------------------


def test_subchunk_encode_end_idempotent(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", "async")
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "1")
    be = TpuBackend()
    h = be.encode_digest_begin(_data(2, 4, 4096), 2)
    digests, ref = be.encode_digest_end(h)
    assert len(ref._planes) >= 3  # held as the sub-chunk arrays
    digests2, ref2 = be.encode_digest_end(h)
    assert digests2 is digests and ref2 is ref
    parity = ref.drain()
    assert ref.drain() is parity  # memoized single D2H
    ref.release()  # post-drain release is a no-op
    assert np.asarray(parity).shape == (2, 2, 4096)


def test_subchunk_release_without_drain(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", "async")
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "1")
    be = TpuBackend()
    h = be.encode_digest_begin(_data(1, 2, 4096), 1)
    _, ref = be.encode_digest_end(h)
    cache = backend_mod.parity_plane_cache()
    assert cache.stats()["occupancy_bytes"] >= ref.nbytes > 0
    ref.release()
    assert cache.stats()["occupancy_bytes"] == 0


def test_subchunk_ref_accounts_every_chunk(monkeypatch):
    """The cache must see the whole parity plane, summed over chunks."""
    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", "async")
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "1")
    B, k, m, L = 2, 4, 2, 4096
    be = TpuBackend()
    h = be.encode_digest_begin(_data(B, k, L), m)
    _, ref = be.encode_digest_end(h)
    assert ref.nbytes == B * m * L
    ref.release()


def test_staging_ledger_lifecycle(monkeypatch):
    """The ping-pong staging reservation is live between begin and end
    (2 sub-chunk buffers), posted to the shared device budget, and
    drops to zero after encode_digest_end."""
    from minio_tpu.cache.allocator import device_budget

    monkeypatch.setenv("MINIO_TPU_CODEC_OVERLAP", "async")
    monkeypatch.setenv("MINIO_TPU_CODEC_SUBCHUNK_KB", "1")
    B, k, L = 2, 4, 4096
    be = TpuBackend()
    h = be.encode_digest_begin(_data(B, k, L), 2)
    cw = subchunk_words(L // 4, 8)
    assert backend_mod._staging_bytes == 2 * B * k * cw * 4
    assert device_budget().usage("codec_staging") == (
        backend_mod._staging_bytes
    )
    be.encode_digest_end(h)
    assert backend_mod._staging_bytes == 0
    assert device_budget().usage("codec_staging") == 0


# -- donation-aliasing regression ----------------------------------------


def test_subchunk_ping_pong_donation_aliasing():
    """Drive the donated chunk chain directly: the accumulator donated
    into program s and aliased into its output must carry the exact
    phash partials into program s+1 — the final digests must match the
    one-shot host hash (the PR 14 aliasing bug class, runtime leg)."""
    import jax.numpy as jnp

    B, k, m, w = 2, 3, 2, 768
    L, cw = w * 4, 256
    rng = np.random.default_rng(3)
    words = rng.integers(0, 2**32, (B, k, w), dtype=np.uint32)
    acc = jnp.zeros((B, k + m, 8), jnp.uint32)
    parity_c = []
    for i, off in enumerate(range(0, w, cw)):
        chunk = jnp.asarray(words[:, :, off:off + cw])
        p_c, acc = codec_step.encode_subchunk_words(
            chunk, acc, np.uint32(off), m, L,
            finalize=i == (w // cw) - 1,
        )
        parity_c.append(p_c)
    parity = np.concatenate([np.asarray(p) for p in parity_c], axis=-1)
    all_rows = np.concatenate(
        [words.transpose(1, 0, 2), np.asarray(parity).transpose(1, 0, 2)]
    ).transpose(1, 0, 2)
    want = phash.phash256_host_batched(all_rows, L)
    assert np.array_equal(np.asarray(acc), want)


# -- mesh fallback -------------------------------------------------------


def test_mesh_overlap_fallback_warns_once(monkeypatch):
    monkeypatch.setattr(pmesh, "_overlap_fallback_warned", False)
    with pytest.warns(RuntimeWarning, match="not supported on the"):
        pmesh.warn_overlap_fallback()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pmesh.warn_overlap_fallback()  # second call is silent
