"""Fused erasure data-plane steps: one device pass per stripe batch.

The reference's PutObject hot loop does RS-encode on CPU and then streams
each shard through a HighwayHash writer (cmd/erasure-encode.go:73-109 +
cmd/bitrot-streaming.go:38-88) - two passes over every byte.  Here both
happen in a single fused device pass per batch: parity generation and the
per-shard bitrot digest read each data byte from HBM once, and only parity
+ digests leave the device (the host already holds the data bytes).

Layout contract: the device works exclusively on uint32 "words" (4 field
elements per lane).  uint8<->uint32 bitcasts on TPU are full relayouts
((32,128) vs (8,128) tiling) costing more than the codec itself, so byte
views happen host-side where numpy's .view() is free.  Use
host_bytes_to_words / host_words_to_bytes at the boundary.

These are the kernels the object layer batches concurrent requests into
(the analogue of erasure-sets feeding per-disk queues).
"""

from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from . import gf, hash as phash, rs, rs_pallas

# encode_and_hash_words_digest donates its input buffer so the device
# reuses the H2D staging allocation for parity; on host-only platforms
# (the CPU test backend) XLA cannot always honor the donation and says
# so per call — that is expected there, not a bug worth a warning storm.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


def host_bytes_to_words(a: np.ndarray) -> np.ndarray:
    """(..., L) uint8 -> (..., L//4) uint32 view (host, zero-copy)."""
    assert a.dtype == np.uint8 and a.shape[-1] % 4 == 0
    a = np.ascontiguousarray(a)
    return a.view(np.uint32)


def host_words_to_bytes(a: np.ndarray) -> np.ndarray:
    """(..., w) uint32 -> (..., 4w) uint8 view (host, zero-copy)."""
    assert a.dtype == np.uint32
    return np.ascontiguousarray(a).view(np.uint8)


@functools.partial(jax.jit, static_argnames=("parity_shards", "shard_len"))
def encode_and_hash_words(
    words: jax.Array, parity_shards: int, shard_len: int
):
    """Encode + bitrot-hash a batch of stripes in one fused pass.

    words: (batch, k, w) uint32 data shards; shard_len = 4*w (bytes).
    Returns (parity, digests):
      parity:  (batch, m, w) uint32 parity shards
      digests: (batch, k+m, 8) uint32 finalized phash256 per shard
               (data rows first, then parity - the fan-out order of
               cmd/erasure-encode.go:39-54).
    """
    batch, k, w = words.shape
    m = parity_shards
    if shard_len != 4 * w:
        raise ValueError("shard_len must equal 4 * words-per-shard")
    if w % 8:
        raise ValueError("words per shard must be a multiple of 8")
    matrix = gf.parity_matrix(k, m)

    if m > 0 and pallas_compiled(w):
        parity, partials = rs_pallas.encode_hash_fused(words, m)
        return parity, phash.finalize_partials(partials, shard_len)

    # Portable path: RS is column-local, so a batch is ONE flat encode of
    # (k, B*w) - no vmap-of-small-ops - and hashing is one batched pass.
    flat = words.transpose(1, 0, 2).reshape(k, batch * w)
    parity = rs._matmul_static(flat, matrix).reshape(m, batch, w)
    aw = jnp.concatenate(
        [words.transpose(1, 0, 2), parity], axis=0
    )  # (n, B, w)
    digests = phash.phash256_words_batched(aw, shard_len)  # (n, B, 8)
    return parity.transpose(1, 0, 2), digests.transpose(1, 0, 2)


@functools.partial(
    jax.jit,
    static_argnames=("parity_shards", "shard_len"),
    donate_argnums=(0,),
)
def encode_and_hash_words_digest(
    words: jax.Array, parity_shards: int, shard_len: int
):
    """Digest-only fused encode: the device-resident-parity variant.

    Same math and same outputs as encode_and_hash_words, with two
    contract differences the PUT pipeline builds on:

    * ``words`` is DONATED — the H2D input buffer is dead after the
      pass, so XLA may reuse it for parity instead of allocating, and
      the caller must not touch its jax copy again.
    * The caller materializes ONLY ``digests`` eagerly (32 bytes per
      shard — all encode_end needs to frame bitrot metadata and ack);
      ``parity`` stays a device array parked in the backend's parity
      plane cache until the write path drains it D2H lazily.
    """
    return encode_and_hash_words(words, parity_shards, shard_len)


@functools.partial(jax.jit, static_argnames=("group",))
def group_flags(words: jax.Array, group: int):
    """Per-group nonzero flags: (..., w) u32 -> (..., w//group) bool.

    The cheap compressibility screen for the parity D2H transport:
    reading the flags costs one bool per ``group`` words, and a mostly-
    False mask means pack_nonzero_groups can shrink the bus transfer.
    """
    *lead, w = words.shape
    if w % group:
        raise ValueError("words per row must be a multiple of group")
    g = w // group
    return (words.reshape(*lead, g, group) != 0).any(axis=-1)


@functools.partial(jax.jit, static_argnames=("group",))
def pack_nonzero_groups(words: jax.Array, group: int):
    """Compact nonzero groups to the front of each row (device side).

    (..., w) u32 -> (flags (..., g) bool, packed (..., w) u32) where
    g = w // group.  Within each row the nonzero groups keep their
    original relative order at the front and the zero groups follow, so
    the host only pulls ``flags`` plus the first ``flags.sum()`` groups
    over the bus and scatters them back by np.nonzero(flags) — the
    fused on-device compression leg of the parity transport
    (codec/compress.py unpack_nonzero_groups is the inverse).
    """
    *lead, w = words.shape
    if w % group:
        raise ValueError("words per row must be a multiple of group")
    g = w // group
    grouped = words.reshape(*lead, g, group)
    flags = (grouped != 0).any(axis=-1)
    # unique, strictly ordered sort keys (nonzero group j -> j, zero
    # group j -> g + j): the permutation is deterministic without
    # leaning on argsort stability guarantees
    idx = jnp.arange(g, dtype=jnp.int32)
    key = jnp.where(flags, 0, jnp.int32(g)) + idx
    order = jnp.argsort(key, axis=-1)
    packed = jnp.take_along_axis(
        grouped, order[..., None], axis=-2
    ).reshape(*lead, w)
    return flags, packed


# ---------------------------------------------------------------------------
# One-kernel codec (fused1): PUT and GET as one device pass per direction
# ---------------------------------------------------------------------------


def codec_kernel_mode() -> str:
    """MINIO_TPU_CODEC_KERNEL: ``fused1`` (default) or ``legacy``.

    ``legacy`` is the bisection oracle: the pre-fusion pass structure
    (encode_and_hash_words_digest on PUT; verify then reconstruct on
    heal) with byte-identical outputs.  Flip it to attribute a
    regression to the fused entry points vs everything around them.
    """
    v = os.environ.get("MINIO_TPU_CODEC_KERNEL", "fused1").strip().lower()
    return v if v in ("fused1", "legacy") else "fused1"


def codec_formulation() -> str:
    """MINIO_TPU_CODEC_FORMULATION: ``swar`` (default) or ``mxu``.

    Picks the GF(2^8) matrix-product formulation inside the fused
    kernels (see rs_pallas module doc); both are bit-exact.
    """
    v = os.environ.get(
        "MINIO_TPU_CODEC_FORMULATION", "swar"
    ).strip().lower()
    return v if v in ("swar", "mxu") else "swar"


def codec_overlap_mode() -> str:
    """MINIO_TPU_CODEC_OVERLAP: ``async`` | ``off`` (default ``off``).

    The host-driven transfer/compute overlap seam:

    * ``async`` — the stripe batch splits along w into S sub-chunks
      double-buffered through donated ping-pong device buffers
      (encode_subchunk_words), so sub-chunk N+1's H2D overlaps N's pass
      which overlaps N-1's drain.  Honest about launches: S passes per
      direction.
    * ``off`` — one pass per batch; inside it the fused Pallas kernels
      still overlap HBM<->VMEM tile traffic with compute through their
      BlockSpec pipeline.
    """
    v = os.environ.get("MINIO_TPU_CODEC_OVERLAP", "").strip().lower()
    return v if v in ("async", "off") else "off"


def pallas_compiled(words_per_shard: int) -> bool:
    """True when a pass over shards of this width runs the Mosaic-
    compiled Pallas kernel: a tile-aligned width, lowered for a TPU."""
    return words_per_shard % rs_pallas._TW == 0 and rs.lowering_for_tpu()


def pallas_dispatch(words_per_shard: int) -> tuple[bool, bool]:
    """(use_pallas, interpret) statics for the fused1 entry points.

    Tile-aligned widths run the Pallas kernel compiled on TPU;
    MINIO_TPU_CODEC_INTERPRET=1 forces the interpreter on other
    backends (the CI kernel-regression mode, mirroring
    MINIO_TPU_SANITIZE).  Everything else - ragged widths, other
    backends - takes the XLA formulation of the same math inside the
    same jit program, and the backend counts it apart (KERNEL_STATS
    ``portable_passes``).
    """
    if pallas_compiled(words_per_shard):
        return True, False
    if (
        words_per_shard % rs_pallas._TW == 0
        and os.environ.get("MINIO_TPU_CODEC_INTERPRET") == "1"
    ):
        return True, True
    return False, False


@functools.partial(
    jax.jit,
    static_argnames=(
        "parity_shards",
        "shard_len",
        "formulation",
        "use_pallas",
        "interpret",
    ),
    donate_argnums=(0,),
)
def encode_words_fused1(
    words: jax.Array,
    parity_shards: int,
    shard_len: int,
    formulation: str = "swar",
    use_pallas: bool = False,
    interpret: bool = False,
):
    """fused1 PUT codec step: parity + digests in ONE device pass.

    On TPU (or under interpret) a tile-aligned batch is exactly one
    pallas_call (rs_pallas.encode_hash_fused); elsewhere it is one XLA
    program with the same math.

    words: (B, k, w) u32, DONATED like encode_and_hash_words_digest.
    Returns (parity (B, m, w) u32, digests (B, n, 8) u32 finalized).
    Only ``digests`` may be materialized eagerly (MTPU107); parity
    parks in the parity plane cache until drain.
    """
    batch, k, w = words.shape
    m = parity_shards
    if shard_len != 4 * w:
        raise ValueError("shard_len must equal 4 * words-per-shard")
    if w % 8:
        raise ValueError("words per shard must be a multiple of 8")

    if use_pallas and m > 0 and w % rs_pallas._TW == 0:
        parity, partials = rs_pallas.encode_hash_fused(
            words, m, formulation=formulation, interpret=interpret
        )
        return parity, phash.finalize_partials(partials, shard_len)

    # XLA single-program path (the bit-identity oracle for the kernel).
    # Data and parity rows hash separately: concatenating them first
    # would copy the whole batch for the sake of two tiny digest arrays
    ddig = phash.phash256_words_batched(words, shard_len)  # (B, k, 8)
    if m == 0:
        return jnp.zeros((batch, 0, w), jnp.uint32), ddig
    parity = rs._matmul_static_batch(words, gf.parity_matrix(k, m))
    pdig = phash.phash256_words_batched(parity, shard_len)
    return parity, jnp.concatenate([ddig, pdig], axis=1)


def expand_matrix(survivors: jax.Array, matrix: jax.Array, n: int):
    """The (k, k) inverse scattered to its survivors' columns of a
    (k, n) matrix, zero elsewhere: ``full GF@ all n rows`` equals
    ``matrix GF@ the k survivor rows``, so a kernel reads the rows as
    they lie and needs no gather.  Traced: a few bytes of XLA work."""
    k = matrix.shape[0]
    return jnp.zeros((k, n), jnp.uint8).at[:, survivors].set(
        matrix.astype(jnp.uint8)
    )


def _check_pattern(shards, survivors, matrix, k: int, m: int) -> None:
    if shards.shape[1] != k + m:
        raise ValueError("shard rows must equal k + m")
    if survivors.shape != (k,) or matrix.shape != (k, k):
        raise ValueError(
            f"need {k} survivor indices and a ({k}, {k}) matrix, got "
            f"{survivors.shape} and {matrix.shape}"
        )


def matmul_rows(rows: jax.Array, matrix: jax.Array, use_pallas: bool,
                interpret: bool):
    """(B, s, w) shard rows x TRACED (o, s) GF matrix -> (B, o, w): the
    runtime-matrix Pallas kernel on a tile-aligned width, else the XLA
    bit-walk (column-locality makes the batch one flat (s, B*w)
    product).  For use inside a caller's jit or shard_map body."""
    B, s, w = rows.shape
    if use_pallas and w % rs_pallas._TW == 0:
        return rs_pallas.matmul_rows_runtime(rows, matrix, interpret=interpret)
    flat = rows.transpose(1, 0, 2).reshape(s, B * w)
    dw = rs._matmul_words_dynamic(flat, matrix)
    return dw.reshape(matrix.shape[0], B, w).transpose(1, 0, 2)


def reconstruct_rows(
    shards: jax.Array,
    survivors: jax.Array,
    matrix: jax.Array,
    use_pallas: bool,
    interpret: bool,
):
    """(B, n, w) rows as read x the pattern's operands -> (B, k, w).

    The one decode product every read-side entry point shares (inside
    its own jit).  The kernel reads all n rows where they lie, against
    the inverse scattered to its survivors' columns; the XLA form
    gathers the k survivor rows first.  Nothing here depends on WHICH
    rows survived, so neither does the compiled program.
    """
    n, w = shards.shape[1:]
    if use_pallas and w % rs_pallas._TW == 0:
        full = expand_matrix(survivors, matrix, n)
        return matmul_rows(shards, full, True, interpret)
    return matmul_rows(
        jnp.take(shards, survivors, axis=1), matrix, False, False
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "data_shards",
        "parity_shards",
        "shard_len",
        "formulation",
        "use_pallas",
        "interpret",
    ),
)
def verify_and_reconstruct_words(
    shards: jax.Array,
    digests: jax.Array,
    present: jax.Array,
    survivors: jax.Array,
    matrix: jax.Array,
    data_shards: int,
    parity_shards: int,
    shard_len: int,
    formulation: str = "swar",
    use_pallas: bool = False,
    interpret: bool = False,
):
    """fused1 GET codec step: digest-verify + reconstruct in ONE pass.

    Replaces the verify_hashes_words -> reconstruct_words_batch pair on
    the quorum-read/heal path: one pallas_call (or one portable XLA
    program) reads each shard byte once for both the bitrot check and
    the RS product.

    shards: (B, n, w) u32 as read (absent rows hold garbage); digests:
    (B, n, 8) u32 stored.  The loss pattern is three TRACED operands -
    present: bool[n] availability, survivors: int32[k] the rows to
    decode from, matrix: uint8[k, k] their inverse
    (gf.reconstruction_matrix) - so one program serves every pattern.
    Returns (data (B, k, w) u32 reconstructed from the survivor rows,
    ok (B, n) bool = digest match AND present).  The caller rechecks ok
    over its chosen survivors and re-solves per-stripe when one was
    corrupt (backend reconstruct_and_verify escalation).
    """
    k, m = data_shards, parity_shards
    B, n, w = shards.shape
    if shard_len != 4 * w:
        raise ValueError("shard_len must equal 4 * words-per-shard")
    _check_pattern(shards, survivors, matrix, k, m)
    if use_pallas and w % rs_pallas._TW == 0:
        data, partials = rs_pallas.verify_reconstruct_runtime(
            shards,
            expand_matrix(survivors, matrix, n),
            formulation=formulation,
            interpret=interpret,
        )
        got = phash.finalize_partials(partials, shard_len)
    else:
        got = phash.phash256_words_batched(shards, shard_len)
        data = reconstruct_rows(shards, survivors, matrix, False, False)
    ok = jnp.all(got == digests, axis=-1) & present
    return data, ok


# ---------------------------------------------------------------------------
# Sub-chunked pipeline (MINIO_TPU_CODEC_OVERLAP=async): host-driven
# double buffering of one stripe batch, on any backend
# ---------------------------------------------------------------------------
#
# The stripe batch splits along w into S sub-chunks; the backend stages
# chunk s+1 H2D (jax.device_put is async) while chunk s's pass runs and
# chunk s-1's results drain.  RS parity is column-local, so per-chunk
# parity is exact; the phash256 partials XOR-accumulate across chunks
# through a DONATED (B, n, 8) ping-pong accumulator whose key uses the
# GLOBAL word offset (hash.tile_partials_batched), and the LAST chunk
# finalizes in the same program — zero extra launches for the digest.
# ``word_offset`` is traced, so every equal-sized chunk of a stream
# shares one compiled program.


@functools.partial(
    jax.jit,
    static_argnames=("parity_shards", "shard_len", "finalize"),
    donate_argnums=(0, 1),
)
def encode_subchunk_words(
    chunk: jax.Array,
    acc: jax.Array,
    word_offset,
    parity_shards: int,
    shard_len: int,
    finalize: bool = False,
):
    """One PUT sub-chunk: parity + hash partials for a (B, k, cw) u32
    slice of the stripe batch at global ``word_offset``.

    ``chunk`` and ``acc`` are DONATED — the staging buffer dies into
    the parity allocation and the partial accumulator ping-pongs
    through the chunk chain.  Returns (parity (B, m, cw), acc' (B, n,
    8) — FINALIZED digests when ``finalize``, raw partials otherwise).
    ``shard_len`` is the FULL row byte length (the digest length-fold),
    not the chunk's.
    """
    B, k, cw = chunk.shape
    m = parity_shards
    if cw % 8:
        raise ValueError("chunk words must be a multiple of 8")
    if m > 0:
        matrix = gf.parity_matrix(k, m)
        flat = chunk.transpose(1, 0, 2).reshape(k, B * cw)
        parity = rs._matmul_static(flat, matrix).reshape(m, B, cw)
        aw = jnp.concatenate([chunk.transpose(1, 0, 2), parity], axis=0)
        parity = parity.transpose(1, 0, 2)
    else:
        parity = jnp.zeros((B, 0, cw), jnp.uint32)
        aw = chunk.transpose(1, 0, 2)
    acc = acc ^ phash.tile_partials_batched(aw, word_offset).transpose(
        1, 0, 2
    )
    return parity, (
        phash.finalize_partials(acc, shard_len) if finalize else acc
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "data_shards",
        "parity_shards",
        "shard_len",
        "finalize",
    ),
    donate_argnums=(0, 1),
)
def verify_reconstruct_subchunk_words(
    chunk: jax.Array,
    acc: jax.Array,
    digests: jax.Array,
    word_offset,
    present: jax.Array,
    survivors: jax.Array,
    matrix: jax.Array,
    data_shards: int,
    parity_shards: int,
    shard_len: int,
    finalize: bool = False,
):
    """One GET sub-chunk: reconstruct a (B, n, cw) slice of the shard
    rows AND accumulate verify partials (donated ping-pong ``acc`` and
    staging ``chunk``, like encode_subchunk_words).  The loss pattern is
    traced (present bool[n], survivors int32[k], matrix uint8[k, k]) as
    in verify_and_reconstruct_words; a sub-chunk is cut on hash strides,
    not kernel tiles, so the product is the XLA bit-walk.

    Returns (data (B, k, cw) u32, acc' (B, n, 8), ok (B, n) bool).
    ``ok`` is meaningful only on the ``finalize`` call (digest match of
    the WHOLE row AND present); earlier chunks return all-False — the
    backend drains each data chunk D2H while the next one computes and
    reads ``ok`` once from the last.
    """
    B, n, cw = chunk.shape
    _check_pattern(chunk, survivors, matrix, data_shards, parity_shards)
    acc = acc ^ phash.tile_partials_batched(
        chunk.transpose(1, 0, 2), word_offset
    ).transpose(1, 0, 2)
    data = reconstruct_rows(chunk, survivors, matrix, False, False)
    if finalize:
        got = phash.finalize_partials(acc, shard_len)
        ok = jnp.all(got == digests, axis=-1) & present
        return data, acc, ok
    return data, acc, jnp.zeros((B, n), bool)


@functools.partial(jax.jit, static_argnames=("shard_len",))
def verify_hashes_words(
    shards: jax.Array, digests: jax.Array, shard_len: int
):
    """Recompute phash256 for (batch, n, w) uint32 shards, compare.

    Returns (batch, n) bool - True where the shard is intact.  This is the
    read-side bitrot verification (cmd/bitrot-streaming.go:130-146 /
    xl-storage.go bitrotVerify) as one device pass over all shards.
    """
    got = phash.phash256_words_batched(shards, shard_len)  # (B, n, 8)
    return jnp.all(got == digests, axis=-1)


@functools.partial(jax.jit, static_argnames=("shard_len",))
def digest_words(shards: jax.Array, shard_len: int):
    """phash256 of (batch, n, w) uint32 shard rows -> (batch, n, 8).

    The healthy-read bitrot pass (TpuBackend.digest/verify) as ONE XLA
    program; there is no Pallas digest-only kernel.
    """
    return phash.phash256_words_batched(shards, shard_len)


@functools.partial(
    jax.jit,
    static_argnames=(
        "data_shards", "parity_shards", "use_pallas", "interpret"
    ),
)
def reconstruct_words_batch(
    shards: jax.Array,
    survivors: jax.Array,
    matrix: jax.Array,
    data_shards: int,
    parity_shards: int,
    use_pallas: bool = False,
    interpret: bool = False,
):
    """Batched reconstruct: (B, n, w) -> (B, k, w) words, ONE program
    per (k, m, w, B) whatever the loss pattern.

    survivors: int32[k] row indices to decode from, matrix: uint8[k, k]
    their inverse (gf.reconstruction_matrix) - both TRACED, picked on
    the host when the read ends (codec.backend.decode_plan).  Rows not
    among the survivors hold garbage and are ignored.  ``use_pallas`` /
    ``interpret`` are codec_step.pallas_dispatch's statics.
    """
    _check_pattern(shards, survivors, matrix, data_shards, parity_shards)
    return reconstruct_rows(shards, survivors, matrix, use_pallas, interpret)


def host_pattern(present, data_shards: int, parity_shards: int):
    """Host side of the pattern operands: a bool[n] mask -> (survivors
    int32[k] = the first k present rows, matrix uint8[k, k]).  Raises
    ValueError below k survivors (errXLReadQuorum analogue)."""
    idx = np.flatnonzero(np.asarray(present, dtype=bool))[:data_shards]
    if len(idx) < data_shards:
        raise ValueError(f"need {data_shards} shards, have {len(idx)}")
    rm = gf.reconstruction_matrix(
        data_shards, parity_shards, tuple(int(i) for i in idx)
    )
    return idx.astype(np.int32), rm


# ---------------------------------------------------------------------------
# Byte-domain convenience wrappers (tests, small host-side uses)
# ---------------------------------------------------------------------------


def encode_and_hash(data, parity_shards: int):
    """Byte-domain wrapper: (B, k, L) u8 -> ((B, n, L) u8, (B, n, 8) u32).

    Host-side byte views; prefer the *_words APIs on the hot path.
    """
    data = np.asarray(data, dtype=np.uint8)
    batch, k, shard_len = data.shape
    if shard_len % 32:
        raise ValueError("shard_len must be a multiple of 32 bytes")
    words = jnp.asarray(host_bytes_to_words(data))
    parity, digests = encode_and_hash_words(
        words, parity_shards, shard_len
    )
    # eager by design: this byte-domain wrapper serves tests and small
    # host-side callers that want concrete shards back; the hot path
    # goes through the backend's digest-only seam instead
    parity_b = host_words_to_bytes(np.asarray(parity))  # noqa: MTPU107
    shards = np.concatenate([data, parity_b], axis=1)
    return shards, np.asarray(digests)


def verify_hashes(shards, digests, shard_len: int):
    """Byte-domain wrapper over verify_hashes_words."""
    shards = np.asarray(shards, dtype=np.uint8)
    words = jnp.asarray(host_bytes_to_words(shards))
    return np.asarray(
        verify_hashes_words(words, jnp.asarray(digests), shard_len)
    )


def decode_and_verify(
    shards: np.ndarray,
    digests: np.ndarray,
    data_shards: int,
    parity_shards: int,
):
    """Read-path step: verify bitrot, reconstruct from intact shards.

    Host-driven composition (the erasure-decode.go:211-290 Decode
    semantics: verify every block read, escalate to parity on failure,
    flag heal when any shard was bad).

    Returns (data, ok_mask): data (k, shard_len) uint8, ok_mask (n,) bool.
    Raises ValueError when fewer than k shards are intact (errXLReadQuorum
    analogue).
    """
    n = data_shards + parity_shards
    shard_len = shards.shape[-1]
    words = jnp.asarray(host_bytes_to_words(np.asarray(shards)))
    ok = np.asarray(
        verify_hashes_words(words[None], jnp.asarray(digests)[None], shard_len)[0]
    )
    if int(ok.sum()) < data_shards:
        raise ValueError(
            f"bitrot: only {int(ok.sum())}/{n} shards intact, "
            f"need {data_shards}"
        )
    survivors, matrix = host_pattern(ok, data_shards, parity_shards)
    dw = reconstruct_words_batch(
        words[None], survivors, matrix, data_shards, parity_shards
    )[0]
    data = host_words_to_bytes(np.asarray(dw))
    return data, ok


# ---------------------------------------------------------------------------
# Benchmark probes (chained device passes, see bench.py)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("parity_shards", "shard_len")
)
def encode_throughput_probe(
    words: jax.Array, parity_shards: int, shard_len: int, reps
):
    """Run `reps` dependent encode+hash passes inside ONE device program.

    Chains iterations through a cheap XOR so XLA cannot elide work,
    letting per-pass device time be measured without host launch
    overhead.  `reps` is a DYNAMIC trip count
    (fori_loop), so one compiled program serves every chain length the
    adaptive bench harness probes.  Returns a small checksum array.
    """
    def body(_, carry):
        words_c, acc = carry
        parity, digests = encode_and_hash_words(
            words_c, parity_shards, shard_len
        )
        return words_c ^ parity[:, :1], acc ^ digests[0, 0, 0]

    final, acc = jax.lax.fori_loop(
        0, reps, body, (words, jnp.uint32(0))
    )
    return final[0, 0, :8], acc


@functools.partial(
    jax.jit,
    static_argnames=("data_shards", "parity_shards"),
)
def reconstruct_throughput_probe(
    shards: jax.Array,
    survivors: jax.Array,
    matrix: jax.Array,
    data_shards: int,
    parity_shards: int,
    reps,
):
    """Chained batched reconstructs (see encode probe)."""
    k = data_shards
    use_pallas = pallas_compiled(shards.shape[-1])

    def body(_, carry):
        shards_c, acc = carry
        data = reconstruct_words_batch(
            shards_c, survivors, matrix, data_shards, parity_shards,
            use_pallas=use_pallas,
        )
        nxt = shards_c.at[:, :k].set(shards_c[:, :k] ^ data)
        return nxt, acc ^ data[0, 0, 0]

    final, acc = jax.lax.fori_loop(
        0, reps, body, (shards, jnp.uint32(0))
    )
    return final[0, 0, :8], acc


@functools.partial(jax.jit, static_argnames=("shard_len",))
def verify_throughput_probe(
    shards: jax.Array, digests: jax.Array, shard_len: int, reps
):
    """Chained bitrot-verify passes: the HEALTHY read path (no RS math,
    just the device hash + compare every streamed block pays)."""
    def body(_, carry):
        shards_c, acc = carry
        ok = verify_hashes_words(shards_c, digests, shard_len)
        nxt = shards_c ^ jnp.where(ok[0, 0], 0, 1).astype(shards_c.dtype)
        return nxt, acc ^ ok.sum().astype(jnp.uint32)

    final, acc = jax.lax.fori_loop(
        0, reps, body, (shards, jnp.uint32(0))
    )
    return final[0, 0, :8], acc
