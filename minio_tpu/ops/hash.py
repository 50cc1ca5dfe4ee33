"""phash256: the framework's TPU-native bitrot checksum.

Role-equivalent to HighwayHash-256 in the reference (the default bitrot
algorithm, cmd/bitrot.go:41-58 / cmd/xl-storage-format-v1.go:119), but
designed for a vector machine instead of 64-bit scalar SIMD:

* HighwayHash chains 32-byte packets sequentially - a ~40k-step dependency
  chain per 1 MiB shard block, unusable on TPU.  phash256 is a two-level
  construction: every uint32 word is mixed with a position-derived key
  (splitmix32 of its index - computed in parallel), and the mixes are
  XOR-reduced in independent partitions.  Depth is O(log n), lanes map
  onto the 8x128 VPU.
* Each word contributes to two independent 32-bit mixes (different odd
  multipliers), and the digest interleaves 4 partitions of each, so a
  corrupted/moved/dropped word escapes detection with probability ~2^-64.
  This is an integrity checksum against bitrot, like the reference's
  HighwayHash use - not a cryptographic MAC.
* uint64 is avoided entirely (TPU has no 64-bit integer lanes).

Host (numpy) and device (jnp) implementations are bit-identical; tests
assert agreement and corruption-detection properties.

Threat model
------------
phash256 defends against ACCIDENTAL corruption only - bit flips from
decaying media, torn writes, firmware bugs, truncation.  For a random
flip the two independent 32-bit mixes per word give a miss probability
of ~2^-64 per partition pair, far below the residual error rate of the
disks underneath.  It does NOT resist a deliberate forger: the
position-derived keys (splitmix32 of the word index, line ~55) are
fixed and public, so an adversary who can write shard bytes can also
compute matching digests - there is no secret anywhere in the
construction.  This matches how the reference deploys its bitrot
hashes: HighwayHash-256 is keyed in principle, but cmd/bitrot.go:41-58
uses a MAGIC, HARD-CODED key for exactly this role ("hash channel
separation", not secrecy), so its deployment is equally forgeable and
both systems treat on-disk tamper-resistance as out of scope (an
attacker with write access to a drive can rewrite xl.meta wholesale,
digests included).  Confidentiality/integrity against adversaries is
layered above: SSE (AES-GCM, authenticated) for object data, signed
requests for the API plane.

Keyed escape hatch: if a deployment ever needs an unforgeable bitrot
digest, derive the per-word keys from a secret instead of the public
index mix - ``key = _mix(idx * _C1 + secret32)`` keeps the same
O(log n) shape and lane layout; only the key schedule changes.  The
bitrot registry (codec/bitrot.py) already dispatches per-algorithm, so
a "phash256k" entry can coexist with stored objects.
"""

from __future__ import annotations

import numpy as np

# odd constants from splitmix64/murmur3 literature, truncated to 32 bits
_C1 = np.uint32(0x9E3779B9)  # golden ratio
_C2 = np.uint32(0x85EBCA6B)
_C3 = np.uint32(0xC2B2AE35)
_M1 = np.uint32(0xCC9E2D51)
_M2 = np.uint32(0x1B873593)

PHASH_SIZE = 32  # digest bytes
_PARTS = 4  # partitions per mix lane; 2 mixes x 4 parts = 8 u32 words


def _mix_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= _C2
    x ^= x >> np.uint32(13)
    x *= _C3
    x ^= x >> np.uint32(16)
    return x


def _digest_np(words: np.ndarray, nbytes: int) -> np.ndarray:
    n = words.shape[0]
    pad = (-n) % _PARTS
    if pad:
        words = np.concatenate([words, np.zeros(pad, np.uint32)])
    idx = np.arange(words.shape[0], dtype=np.uint32)
    key = _mix_np(idx * _C1 + np.uint32(1))
    m1 = _mix_np((words ^ key) * _M1)
    m2 = _mix_np((words + key) * _M2)
    # Strided (word-index mod 4) partitions: any contiguous chunk of the
    # stream reduces to 4 partials independently, which lets the device
    # kernel fold tile partials in any order (see rs_pallas fused kernel).
    p1 = np.bitwise_xor.reduce(m1.reshape(-1, _PARTS), axis=0)
    p2 = np.bitwise_xor.reduce(m2.reshape(-1, _PARTS), axis=0)
    out = np.concatenate([p1, p2])
    # fold in total length so truncation/extension changes every word
    lenmix = (np.uint64(nbytes) * np.uint64(_C1)).astype(np.uint32)
    out = _mix_np(out ^ lenmix + np.arange(8, dtype=np.uint32))
    return out


def phash256_host_batched(words: np.ndarray, nbytes: int) -> np.ndarray:
    """Host digest over the last axis: (..., w) uint32 -> (..., 8) uint32.

    Vectorized numpy twin of phash256_words_batched (bit-identical); used
    by the CPU codec backend so host and device shard files interoperate.
    """
    n = words.shape[-1]
    if n % _PARTS:
        raise ValueError(f"word count {n} must be a multiple of {_PARTS}")
    idx = np.arange(n, dtype=np.uint32)
    key = _mix_np(idx * _C1 + np.uint32(1))
    m1 = _mix_np((words ^ key) * _M1)
    m2 = _mix_np((words + key) * _M2)
    lead = words.shape[:-1]
    p1 = np.bitwise_xor.reduce(
        m1.reshape(*lead, n // _PARTS, _PARTS), axis=-2
    )
    p2 = np.bitwise_xor.reduce(
        m2.reshape(*lead, n // _PARTS, _PARTS), axis=-2
    )
    out = np.concatenate([p1, p2], axis=-1)
    lenmix = (np.uint64(nbytes) * np.uint64(_C1)).astype(np.uint32)
    return _mix_np(out ^ lenmix + np.arange(8, dtype=np.uint32))


def phash256_host(data: bytes | np.ndarray) -> bytes:
    """256-bit parallel bitrot digest of a byte string (host reference)."""
    buf = np.frombuffer(bytes(data), dtype=np.uint8)
    nbytes = buf.shape[0]
    pad = (-nbytes) % 4
    if pad:
        buf = np.concatenate([buf, np.zeros(pad, np.uint8)])
    words = buf.view(np.uint32)
    return _digest_np(words, nbytes).tobytes()


def _mix_jnp(x):
    import jax.numpy as jnp

    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _C2
    x = x ^ (x >> 13)
    x = x * _C3
    x = x ^ (x >> 16)
    return x


def phash256_words(words, nbytes):
    """Device digest of a (w,) uint32 word array -> (8,) uint32.

    ``nbytes`` is the true byte length represented: an int, or a traced
    scalar (words past it are padding and count for nothing).  Word
    count must already be a multiple of 4 (the erasure layer pads shards
    to 32-byte multiples, mirroring how the reference pads shards to
    ShardSize, cmd/erasure-coding.go:115-117).
    """
    (n,) = words.shape
    if n % _PARTS:
        raise ValueError(f"word count {n} must be a multiple of {_PARTS}")
    return phash256_words_batched(words[None], nbytes)[0]


def _xor_fold(x, axis: int):
    import jax

    return jax.lax.reduce(
        x, np.uint32(0), jax.lax.bitwise_xor, (axis % x.ndim,)
    )


# lane width of the partition fold below: one full vreg row
_LANES = 128


def _fold_parts(mix):
    """(..., n) u32 mixes -> (..., 4) word-index-mod-4 partition XORs.

    Folds 128-word rows first and the 128 lanes down to the 4
    partitions last, so the large intermediate keeps a full lane dim:
    reshaping the stream to (..., n/4, 4) instead pads every 4-lane row
    to a vreg on TPU (the compiler reported 1.5 GiB of temporaries for
    an 80 MiB batch and compile times of 30-50 s at 4-row shapes).
    128 % 4 == 0, so lane l of every row holds partition l % 4; a
    ragged tail of < 128 words takes the direct 4-lane fold.
    """
    n = mix.shape[-1]
    lead = mix.shape[:-1]
    main = n - n % _LANES
    acc = None
    if main:
        rows = _xor_fold(
            mix[..., :main].reshape(*lead, main // _LANES, _LANES), -2
        )
        acc = _xor_fold(rows.reshape(*lead, _LANES // _PARTS, _PARTS), -2)
    if n - main:
        tail = _xor_fold(
            mix[..., main:].reshape(*lead, (n - main) // _PARTS, _PARTS),
            -2,
        )
        acc = tail if acc is None else acc ^ tail
    return acc


def row_words(nbytes):
    """Per-row byte lengths -> the (..., 1) uint32 word counts that
    ``tile_partials_batched`` masks by (a length is a whole number of
    words: shards are padded to 32 bytes)."""
    import jax.numpy as jnp

    return (jnp.asarray(nbytes).astype(jnp.uint32) >> 2)[..., None]


def phash256_words_batched(words, nbytes):
    """Device digest over the LAST axis: (..., w) uint32 -> (..., 8).

    Vectorized over leading axes with no vmap - every op is a full-size
    array op, so hashing (n_shards, batch, w) is one VPU pass.

    ``nbytes`` is an int (every row is 4 * w bytes of shard, the exact-
    width form) or an array of the leading shape, TRACED: row r holds
    nbytes[r] bytes and is padding past them, and digests to the same
    32 bytes as its exact-width form.
    """
    if isinstance(nbytes, (int, np.integer)):
        return finalize_partials(tile_partials_batched(words, 0), nbytes)
    return finalize_partials(
        tile_partials_batched(words, 0, row_words(nbytes)), nbytes
    )


def tile_partials_batched(words, offset, nwords=None):
    """XOR partials of one contiguous sub-chunk over the LAST axis.

    words: (..., w) uint32 with w a multiple of _PARTS; offset: scalar
    uint32 global word index of the chunk start, TRACED so every
    sub-chunk of a stream reuses one compiled program.  offset must be
    a multiple of _PARTS (the strided word-index-mod-4 partitions must
    stay aligned across chunks); the codec sub-chunk sizing guarantees
    this by cutting on hash-partition boundaries.  nwords: None, or
    (..., 1) uint32 TRACED, each row's true length in words: a word at
    or past it contributes nothing (unmasked, a zero padding word would
    contribute ``mix((0 ^ key) * M1)``).  Returns (..., 8)
    partials - XOR-fold the chunks in any order, then apply
    finalize_partials to obtain phash256_words_batched output.
    """
    import jax
    import jax.numpy as jnp

    n = words.shape[-1]
    if n % _PARTS:
        raise ValueError(f"word count {n} must be a multiple of {_PARTS}")
    idx = jnp.uint32(offset) + jax.lax.iota(jnp.uint32, n)
    key = _mix_jnp(idx * _C1 + jnp.uint32(1))
    m1 = _mix_jnp((words ^ key) * _M1)
    m2 = _mix_jnp((words + key) * _M2)
    if nwords is not None:
        live = idx < nwords
        m1 = jnp.where(live, m1, jnp.uint32(0))
        m2 = jnp.where(live, m2, jnp.uint32(0))
    return jnp.concatenate([_fold_parts(m1), _fold_parts(m2)], axis=-1)


def finalize_partials(partials, nbytes):
    """Length-fold of XOR-combined tile partials: (..., 8) -> (..., 8).
    ``nbytes``: an int, or a traced array of the leading shape."""
    import jax
    import jax.numpy as jnp

    if isinstance(nbytes, (int, np.integer)):
        lenmix = jnp.uint32(nbytes) * _C1
    else:
        lenmix = (jnp.asarray(nbytes).astype(jnp.uint32) * _C1)[..., None]
    return _mix_jnp(partials ^ lenmix + jax.lax.iota(jnp.uint32, 8))
