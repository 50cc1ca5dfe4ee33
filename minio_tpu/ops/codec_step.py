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

# encode_words_fused1 donates its input buffer so the device reuses
# the H2D staging allocation for parity; on host-only platforms
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


def _per_stripe(lengths, batch: int):
    """The TRACED length operand of every codec program as int32[batch]
    bytes, one a stripe.  A scalar stands for every stripe (a batch of
    one width); the seam passes one length a stripe, so that rows of
    different true lengths share a launch at their staged width."""
    return jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (batch,))


@functools.partial(jax.jit, static_argnames=("parity_shards",))
def encode_and_hash_words(words: jax.Array, parity_shards: int, lengths):
    """Encode + bitrot-hash a batch of stripes in one fused pass.

    words: (batch, k, w) uint32 data shards at their staged width;
    lengths: TRACED int32[batch] (or a scalar), each stripe's true shard
    bytes - the words past them are padding, left out of the hash.
    Returns (parity, digests):
      parity:  (batch, m, w) uint32 parity shards
      digests: (batch, k+m, 8) uint32 finalized phash256 per shard
               (data rows first, then parity - the fan-out order of
               cmd/erasure-encode.go:39-54).
    """
    batch, k, w = words.shape
    m = parity_shards
    if w % 8:
        raise ValueError("words per shard must be a multiple of 8")
    lengths = _per_stripe(lengths, batch)
    matrix = gf.parity_matrix(k, m)

    if m > 0 and pallas_compiled(w):
        parity, partials = rs_pallas.encode_hash_fused(words, lengths, m)
        return parity, phash.finalize_partials(partials, lengths[:, None])

    # Portable path: RS is column-local, so a batch is ONE flat encode of
    # (k, B*w) - no vmap-of-small-ops - and hashing is one batched pass.
    flat = words.transpose(1, 0, 2).reshape(k, batch * w)
    parity = rs._matmul_static(flat, matrix).reshape(m, batch, w)
    aw = jnp.concatenate(
        [words.transpose(1, 0, 2), parity], axis=0
    )  # (n, B, w)
    digests = phash.phash256_words_batched(
        aw, jnp.broadcast_to(lengths, (k + m, batch))
    )  # (n, B, 8)
    return parity.transpose(1, 0, 2), digests.transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# The served codec: PUT and GET as one device pass per direction
# ---------------------------------------------------------------------------


def pallas_compiled(words_per_shard: int) -> bool:
    """True when a pass over shards of this width runs the Mosaic-
    compiled Pallas kernel: a tile-aligned width, lowered for a TPU."""
    return words_per_shard % rs_pallas._TW == 0 and rs.lowering_for_tpu()


def pallas_dispatch(words_per_shard: int) -> tuple[bool, bool]:
    """(use_pallas, interpret) statics for the fused entry points.

    ``words_per_shard`` is the STAGED width.  The seam stages every
    launch at whole tiles (codec.backend.width_rung) and passes the true
    lengths as an operand, so on a TPU every served pass is a Pallas
    one, whatever the objects' sizes.  MINIO_TPU_CODEC_INTERPRET=1
    forces the interpreter on other backends (the CI kernel-regression
    mode, mirroring MINIO_TPU_SANITIZE).  Everything else - other
    backends, a direct caller's odd width - takes the XLA formulation
    of the same math inside the same jit program, and the backend counts
    it apart (KERNEL_STATS ``portable_passes``).
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
    static_argnames=("parity_shards", "use_pallas", "interpret"),
    donate_argnums=(0,),
)
def encode_words_fused1(
    words: jax.Array,
    parity_shards: int,
    lengths,
    use_pallas: bool = False,
    interpret: bool = False,
):
    """PUT codec step: parity + digests in ONE device pass.

    On TPU (or under interpret) a tile-aligned batch is exactly one
    pallas_call (rs_pallas.encode_hash_fused); elsewhere it is one XLA
    program with the same math.

    words: (B, k, w) u32 at the staged width, DONATED - the H2D input
    buffer is dead after the pass, so XLA may reuse it for parity
    instead of allocating, and the caller must not touch its jax copy
    again.  lengths: TRACED int32[B] (or a scalar), each stripe's true
    shard bytes: one program serves every length a width can hold, and
    a stripe digests as its exact-width form does.
    Returns (parity (B, m, w) u32, digests (B, n, 8) u32 finalized).
    Only ``digests`` may be materialized eagerly (MTPU107); parity
    parks in the parity plane cache until drain.
    """
    batch, k, w = words.shape
    m = parity_shards
    if w % 8:
        raise ValueError("words per shard must be a multiple of 8")
    lengths = _per_stripe(lengths, batch)

    if use_pallas and m > 0 and w % rs_pallas._TW == 0:
        parity, partials = rs_pallas.encode_hash_fused(
            words, lengths, m, interpret
        )
        return parity, phash.finalize_partials(partials, lengths[:, None])

    # XLA single-program path (the bit-identity oracle for the kernel).
    # Data and parity rows hash separately: concatenating them first
    # would copy the whole batch for the sake of two tiny digest arrays
    ddig = phash.phash256_words_batched(
        words, jnp.broadcast_to(lengths[:, None], (batch, k))
    )  # (B, k, 8)
    if m == 0:
        return jnp.zeros((batch, 0, w), jnp.uint32), ddig
    parity = rs._matmul_static_batch(words, gf.parity_matrix(k, m))
    pdig = phash.phash256_words_batched(
        parity, jnp.broadcast_to(lengths[:, None], (batch, m))
    )
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
        "data_shards", "parity_shards", "use_pallas", "interpret"
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
    lengths,
    use_pallas: bool = False,
    interpret: bool = False,
):
    """GET codec step: digest-verify + reconstruct in ONE pass on the
    quorum-read/heal path: one pallas_call (or one portable XLA program)
    reads each shard byte once for both the bitrot check and the RS
    product.

    shards: (B, n, w) u32 as read, at the staged width (absent rows
    hold garbage); digests: (B, n, 8) u32 stored; lengths: TRACED
    int32[B] (or a scalar), each stripe's true shard bytes.  The loss
    pattern is three more TRACED operands -
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
    _check_pattern(shards, survivors, matrix, k, m)
    rows = jnp.broadcast_to(_per_stripe(lengths, B)[:, None], (B, n))
    if use_pallas and w % rs_pallas._TW == 0:
        data, partials = rs_pallas.verify_reconstruct_runtime(
            shards,
            expand_matrix(survivors, matrix, n),
            rows[:, 0],
            interpret=interpret,
        )
        got = phash.finalize_partials(partials, rows)
    else:
        got = phash.phash256_words_batched(shards, rows)
        data = reconstruct_rows(shards, survivors, matrix, False, False)
    ok = jnp.all(got == digests, axis=-1) & present
    return data, ok


def _per_row(lengths, lead: tuple):
    """The TRACED length operand of the row-wise programs as int32 of
    the rows' leading shape (B, n): a scalar is every row's, int32[B]
    one a stripe, int32[B, n] one a row."""
    ln = jnp.asarray(lengths, jnp.int32)
    if ln.ndim == 1:
        ln = ln[:, None]
    return jnp.broadcast_to(ln, lead)


@jax.jit
def verify_hashes_words(shards: jax.Array, digests: jax.Array, lengths):
    """Recompute phash256 for (batch, n, w) uint32 shards, compare.

    ``lengths`` as in digest_words.  Returns (batch, n) bool - True
    where the shard is intact.  This is the
    read-side bitrot verification (cmd/bitrot-streaming.go:130-146 /
    xl-storage.go bitrotVerify) as one device pass over all shards.
    """
    got = phash.phash256_words_batched(
        shards, _per_row(lengths, shards.shape[:-1])
    )  # (B, n, 8)
    return jnp.all(got == digests, axis=-1)


@jax.jit
def digest_words(shards: jax.Array, lengths):
    """phash256 of (batch, n, w) uint32 shard rows -> (batch, n, 8).

    lengths: TRACED true bytes of the rows (see _per_row): w is the
    staged width, and a row padded past its length digests to the same
    32 bytes as its exact-width form - one program a (rows, width)
    whatever the lengths.

    The healthy-read bitrot pass (TpuBackend.digest/verify) as ONE XLA
    program; there is no Pallas digest-only kernel.
    """
    return phash.phash256_words_batched(
        shards, _per_row(lengths, shards.shape[:-1])
    )


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
