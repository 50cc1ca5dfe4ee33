"""TPU-native GF(2^8) Reed-Solomon encode/reconstruct as JAX programs.

Replaces the AVX2/NEON galois-multiply assembly in klauspost/reedsolomon
v1.9.9 (consumed by the reference at cmd/erasure-coding.go:54-64 and driven
from cmd/erasure-encode.go / erasure-decode.go).  The design is TPU-first
rather than a port of the byte-table SIMD approach:

* Bytes are packed 4-per-lane into uint32 words, so every VPU lane processes
  4 field elements per op (SWAR).  No gathers, no byte tables on device.
* Multiplication by the generator-matrix constants uses the "xtime powers"
  decomposition: for each data shard we materialize x, 2x, 4x, ..., 128x
  (seven SWAR doublings), and each parity word is then a pure XOR-reduction
  of the powers selected by the bits of its matrix constants.  For EC 8+4
  this is ~56 doublings + ~130 XORs per 32 bytes of data - entirely
  elementwise, so XLA fuses the whole stripe into one VPU kernel and the
  op stays HBM-bound rather than gather-bound.
* The generator matrix is a compile-time constant (one jit cache entry per
  erasure config), while reconstruction uses a *traced* matrix so that any
  missing-shard pattern reuses one compiled program (no recompilation storm
  on degraded reads, the analogue of reedsolomon.Reconstruct's per-call
  sub-matrix inversion).

Shard layout convention matches cmd/erasure-coding.go: shard i of n sits in
row i; rows [0,k) are data, rows [k,n) are parity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf

# SWAR constants for 4 packed GF(2^8) elements per uint32 lane.
_LOW7 = np.uint32(0x7F7F7F7F)
_HIGH1 = np.uint32(0x80808080)
_POLY_LOW = np.uint32(gf.POLY & 0xFF)  # 0x1d replicated via multiply


def _xtime(words: jax.Array) -> jax.Array:
    """Multiply 4 packed field elements by x (i.e. 2) in one SWAR step."""
    carries = (words & _HIGH1) >> 7  # 0x01 in each byte that overflows
    return ((words & _LOW7) << 1) ^ (carries * _POLY_LOW)


def _powers(words: jax.Array) -> list[jax.Array]:
    """[x, 2x, 4x, ..., 128x] for packed words - the mul-by-constant basis."""
    ps = [words]
    for _ in range(7):
        ps.append(_xtime(ps[-1]))
    return ps


def bytes_to_words(shards: jax.Array) -> jax.Array:
    """(..., length) uint8 -> (..., length//4) uint32 (length % 4 == 0)."""
    if shards.dtype != jnp.uint8:
        raise TypeError(f"expected uint8 shards, got {shards.dtype}")
    if shards.shape[-1] % 4:
        raise ValueError("shard length must be a multiple of 4 bytes")
    return jax.lax.bitcast_convert_type(
        shards.reshape(*shards.shape[:-1], shards.shape[-1] // 4, 4), jnp.uint32
    )


def words_to_bytes(words: jax.Array) -> jax.Array:
    """(..., w) uint32 -> (..., 4*w) uint8."""
    out = jax.lax.bitcast_convert_type(words, jnp.uint8)
    return out.reshape(*words.shape[:-1], words.shape[-1] * 4)


def _encode_words(data_words: jax.Array, matrix: np.ndarray) -> jax.Array:
    """(k, w) uint32 -> (m, w) uint32 parity via static XOR-select.

    ``matrix`` is the (m, k) parity block of the systematic generator
    matrix; it is baked into the traced program (constants prune XORs for
    zero bits at trace time).
    """
    k = data_words.shape[0]
    m = matrix.shape[0]
    assert matrix.shape == (m, k)
    if m == 0:
        return jnp.zeros((0, data_words.shape[1]), dtype=jnp.uint32)
    powers = [_powers(data_words[i]) for i in range(k)]
    rows = []
    for r in range(m):
        acc = None
        for c in range(k):
            coeff = int(matrix[r, c])
            for b in range(8):
                if (coeff >> b) & 1:
                    term = powers[c][b]
                    acc = term if acc is None else acc ^ term
        if acc is None:
            acc = jnp.zeros_like(data_words[0])
        rows.append(acc)
    return jnp.stack(rows)


def lowering_for_tpu() -> bool:
    """The one trace-time platform read in ops/: True when the program
    being traced will be compiled for a TPU.  Every static Pallas-vs-XLA
    dispatch (here, codec_step.pallas_dispatch) asks this, so the AOT
    compile test can stand in for the chip by replacing one function."""
    return jax.default_backend() == "tpu"


def _matmul_static(words: jax.Array, matrix: np.ndarray) -> jax.Array:
    """Static-matrix GF matmul: Pallas kernel on TPU, fused XLA elsewhere.

    Trace-time dispatch: on the TPU backend the tiled VMEM kernel
    (rs_pallas.matmul_words, compiled - never interpreted); CPU tests
    and the virtual multi-chip mesh take the portable jnp path.  The
    ratio between the two: not measured on this code.
    """
    if lowering_for_tpu():
        from . import rs_pallas

        return rs_pallas.matmul_words(matrix, words, interpret=False)
    return _encode_words(words, matrix)


def _matmul_static_batch(rows: jax.Array, matrix: np.ndarray) -> jax.Array:
    """(B, s, w) shard rows x static (o, s) matrix -> (B, o, w).

    RS is column-local, so a batch is ONE flat (s, B*w) product - no
    vmap of small ops."""
    B, s, w = rows.shape
    flat = rows.transpose(1, 0, 2).reshape(s, B * w)
    out = _matmul_static(flat, matrix)
    return out.reshape(matrix.shape[0], B, w).transpose(1, 0, 2)


def _matmul_words_dynamic(shards_words: jax.Array, matrix: jax.Array) -> jax.Array:
    """(s, w) uint32 x traced (o, s) uint8 matrix -> (o, w) uint32.

    Used for reconstruction, where the matrix depends on which shards
    survived: bits of the (traced) constants become XOR masks so a single
    compiled program serves every erasure pattern.
    """
    s, w = shards_words.shape
    o = matrix.shape[0]
    m32 = matrix.astype(jnp.uint32)  # (o, s)
    # No (o, s, 8, w) intermediate: walk the xtime chain of each survivor
    # lazily and fold its eight masked terms into one (o, w) partial,
    # then XOR the partials pairwise - a tree, so the expression is
    # 8 + log2(s) deep, not 8 * s (the AOT compile gate's child died
    # once inside libtpu with one frame repeated down its stack while
    # the chain was 8 * s long; it has not since).
    parts = []
    for i in range(s):
        p = shards_words[i]
        acc = None
        for b in range(8):
            bit = (m32[:, i] >> np.uint32(b)) & np.uint32(1)  # (o,)
            mask = (bit * jnp.uint32(0xFFFFFFFF))[:, None]
            term = mask & p[None, :]
            acc = term if acc is None else acc ^ term
            if b != 7:
                p = _xtime(p)
        parts.append(acc)
    if not parts:
        return jnp.zeros((o, w), dtype=jnp.uint32)
    while len(parts) > 1:
        parts = [
            parts[j] ^ parts[j + 1] if j + 1 < len(parts) else parts[j]
            for j in range(0, len(parts), 2)
        ]
    return parts[0]


def _xor_reduce(x: jax.Array, axis: int) -> jax.Array:
    """XOR-reduce along an axis (lax.reduce with bitwise xor)."""
    return jax.lax.reduce(
        x, np.uint32(0), jax.lax.bitwise_xor, (axis,)
    )


@functools.partial(jax.jit, static_argnames=("data_shards", "parity_shards"))
def _encode_jit(data: jax.Array, data_shards: int, parity_shards: int) -> jax.Array:
    matrix = gf.parity_matrix(data_shards, parity_shards)
    words = bytes_to_words(data)
    parity = _matmul_static(words, matrix)
    return words_to_bytes(parity)


def encode(data: jax.Array | np.ndarray, parity_shards: int) -> jax.Array:
    """Encode (k, length) uint8 data shards -> (m, length) parity shards.

    Device analogue of reedsolomon.Encode as called from
    Erasure.EncodeData (cmd/erasure-coding.go:66-86).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    return _encode_jit(data, data.shape[0], parity_shards)


@functools.partial(
    jax.jit, static_argnames=("data_shards", "parity_shards", "want_parity")
)
def _reconstruct_jit(
    shards: jax.Array,
    present_mask: jax.Array,
    recon_matrix: jax.Array,
    data_shards: int,
    parity_shards: int,
    want_parity: bool,
) -> jax.Array:
    """Rebuild all n shards from >=k survivors.

    shards: (n, length) uint8 with garbage rows where present_mask is 0.
    recon_matrix: (k, k) traced GF matrix mapping the first k survivors
    (in index order, compacted) back to data shards.
    """
    k, m = data_shards, parity_shards
    n = k + m
    words = bytes_to_words(shards)  # (n, w)
    # Compact the first k surviving rows to the top, in index order - the
    # row order reconstruction_matrix() was built against.
    order = jnp.argsort(
        jnp.where(present_mask > 0, jnp.arange(n), n + jnp.arange(n))
    )
    survivors = words[order[:k]]
    data_words = _matmul_words_dynamic(survivors, recon_matrix)  # (k, w)
    if want_parity:
        parity = _encode_words(data_words, gf.parity_matrix(k, m))
        all_words = jnp.concatenate([data_words, parity], axis=0)
    else:
        all_words = data_words
    rebuilt = words_to_bytes(all_words)
    keep = present_mask[: rebuilt.shape[0], None].astype(bool)
    return jnp.where(keep, shards[: rebuilt.shape[0]], rebuilt)


def reconstruct(
    shards: jax.Array | np.ndarray,
    present: "np.ndarray | list[bool]",
    data_shards: int,
    parity_shards: int,
    data_only: bool = True,
) -> jax.Array:
    """Device analogue of reedsolomon.ReconstructData / Reconstruct.

    ``shards``: (n, length) uint8; rows with present[i] == False are ignored.
    Returns (k, length) when data_only (DecodeDataBlocks path,
    cmd/erasure-coding.go:89-98) else (n, length) (Heal path,
    cmd/erasure-lowlevel-heal.go:28-48).
    """
    present = np.asarray(present, dtype=bool)
    n = data_shards + parity_shards
    if present.shape != (n,):
        raise ValueError(f"present mask must have {n} entries")
    idx = tuple(int(i) for i in np.nonzero(present)[0])
    if len(idx) < data_shards:
        raise ValueError(
            f"need {data_shards} shards, have {len(idx)}"
        )
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    rm = gf.reconstruction_matrix(data_shards, parity_shards, idx)
    out = _reconstruct_jit(
        shards,
        jnp.asarray(present.astype(np.uint8)),
        jnp.asarray(rm),
        data_shards,
        parity_shards,
        not data_only,
    )
    return out[:data_shards] if data_only else out
