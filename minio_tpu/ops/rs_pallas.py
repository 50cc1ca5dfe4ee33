"""Pallas TPU kernels for the GF(2^8) shard codec hot path.

Two device formulations of "GF matrix @ shards" (the klauspost/reedsolomon
role behind cmd/erasure-coding.go:54-64), selectable inside the fused
kernels below (MINIO_TPU_CODEC_FORMULATION):

1. SWAR/VPU (`_swar_rows`, the default): shards live as uint32 words
   (4 field elements per lane).  Multiply-by-constant uses the
   xtime-powers decomposition with the generator matrix baked into the
   kernel at trace time, so each tile is a straight-line XOR chain over
   VMEM-resident vectors - no tables, no gathers, no dtype conversions.

2. MXU bit-matrix (`_mxu_rows`): GF(2^8) mul-by-constant is an 8x8
   linear map over GF(2), so the whole codec lifts to one
   (8o x 8s) @ (8s x T) bf16 matmul per tile, mod 2.  Higher arithmetic
   intensity but pays ~30 VPU ops/byte in bit unpack/repack.

Throughput of either: not measured on this code (PERF.md).

Every entry point takes ``interpret`` explicitly: False compiles the
kernel with Mosaic (TPU only), True runs the Pallas interpreter (the
CPU test mode).  Nothing here guesses from the platform; the dispatch
that does lives in codec_step.pallas_dispatch / rs._matmul_static.
All kernels pipeline HBM<->VMEM through their BlockSpecs (Pallas
double-buffers the blocked grid), so each is exactly one pallas_call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import gf, rs

# uint32 words per shard per tile (16 KiB of shard bytes per grid step)
_TW = 4096


def _swar_kernel(matrix: np.ndarray):
    """Build a Pallas kernel computing out = matrix GF@ data over a tile.

    matrix (o, s) is a Python-time constant: zero coefficients and zero
    bits are pruned from the XOR chain at trace time, and xtime powers of
    each input row are materialized lazily up to the highest bit any
    coefficient in that column uses (see _swar_rows).
    """
    o, _ = matrix.shape

    def kernel(data_ref, out_ref):
        rows = _swar_rows(matrix, data_ref[...])
        for r in range(o):
            out_ref[r, :] = rows[r]

    return kernel


@functools.partial(
    jax.jit, static_argnames=("matrix_key", "o", "s", "interpret")
)
def _matmul_words_jit(
    words, matrix_key: bytes, o: int, s: int, interpret: bool
):
    matrix = np.frombuffer(matrix_key, dtype=np.uint8).reshape(o, s)
    w = words.shape[1]
    pad = (-w) % _TW
    if pad:
        words = jnp.pad(words, ((0, 0), (0, pad)))
    pw = w + pad
    out = pl.pallas_call(
        _swar_kernel(matrix),
        out_shape=jax.ShapeDtypeStruct((o, pw), jnp.uint32),
        grid=(pw // _TW,),
        in_specs=[pl.BlockSpec((s, _TW), lambda i: (0, i))],
        out_specs=pl.BlockSpec((o, _TW), lambda i: (0, i)),
        interpret=interpret,
    )(words)
    return out[:, :w] if pad else out


def matmul_words(matrix: np.ndarray, words, interpret: bool):
    """(o, s) static GF matrix @ (s, w) uint32 shard words -> (o, w)."""
    o, s = matrix.shape
    key = np.ascontiguousarray(matrix, dtype=np.uint8).tobytes()
    return _matmul_words_jit(words, key, o, s, interpret)


# ---------------------------------------------------------------------------
# Tile bodies shared by the fused kernels: bitrot partials, SWAR rows
# ---------------------------------------------------------------------------


def _tile_hash_partials(all_rows, i, tw: int):
    """phash256 partials of (rows, tw) shard words at w-tile index i.

    Shared by every fused kernel; XOR-accumulate the (rows, 8) result
    into a revisited output block and finalize with
    hash.finalize_partials outside the kernel.
    """
    from . import hash as phash

    gidx = i * tw + jax.lax.broadcasted_iota(jnp.uint32, (1, tw), 1)
    key = phash._mix_jnp(gidx * phash._C1 + jnp.uint32(1))  # (1, tw)
    m1 = phash._mix_jnp((all_rows ^ key) * phash._M1)
    m2 = phash._mix_jnp((all_rows + key) * phash._M2)

    def red(x):
        # XOR-fold the lane dim down to 4: every halving step keeps
        # index-mod-4 classes intact (all widths are multiples of 4),
        # so the result is exactly the strided partition XOR.  Mosaic
        # has no reduce_xor and no lane-dim shape casts; slices + xor
        # lower cleanly.
        width = tw
        while width > 4:
            width //= 2
            x = x[:, :width] ^ x[:, width : 2 * width]
        return x  # (rows, 4)

    return jnp.concatenate([red(m1), red(m2)], axis=1)  # (rows, 8)


def _swar_rows(matrix: np.ndarray, data) -> list:
    """Shared XOR-chain: parity rows of a (k, t) uint32 tile (traced)."""
    o, s = matrix.shape
    need_bits = [
        max((int(matrix[r, c]).bit_length() for r in range(o)), default=0)
        for c in range(s)
    ]
    powers: list[list] = []
    for c in range(s):
        p = data[c, :]
        ps = [p]
        for _ in range(max(need_bits[c] - 1, 0)):
            p = rs._xtime(p)
            ps.append(p)
        powers.append(ps)
    rows = []
    for r in range(o):
        acc = None
        for c in range(s):
            coeff = int(matrix[r, c])
            for b in range(8):
                if (coeff >> b) & 1:
                    t = powers[c][b]
                    acc = t if acc is None else acc ^ t
        if acc is None:
            acc = jnp.zeros_like(data[0, :])
        rows.append(acc)
    return rows


# ---------------------------------------------------------------------------
# MXU bit-matrix formulation of the same rows
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _bit_matrix(matrix_bytes: bytes, o: int, s: int) -> np.ndarray:
    """Lift an (o, s) GF(2^8) matrix to its (8o, 8s) GF(2) representation.

    Row 8r+t, column 8c+b is bit t of matrix[r,c] * x^b: the contribution
    of input-byte-c's bit b to output-byte-r's bit t.
    """
    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(o, s)
    out = np.zeros((8 * o, 8 * s), dtype=np.float32)
    for r in range(o):
        for c in range(s):
            v = int(matrix[r, c])
            for b in range(8):
                prod = gf.gf_mul(v, 1 << b)
                for t in range(8):
                    out[8 * r + t, 8 * c + b] = (prod >> t) & 1
    return out


def _mxu_rows(matrix: np.ndarray, data, mat=None) -> list:
    """MXU formulation of _swar_rows: (s, t) u32 tile -> o output rows.

    Lifts the bytewise GF(2^8) product to the (8o, 8s) GF(2) bit matrix
    (_bit_matrix) and evaluates all four byte positions of every word in
    ONE bf16 matmul mod 2: the codec is byte-local, so byte positions
    stack on the lane dim.  Exact because every intermediate is a small
    integer (bit-counts <= 8s < 2^8) carried in f32.

    ``mat`` is the pre-lifted bit matrix when called inside a Pallas
    kernel (kernels cannot capture traced constants, so the caller
    threads it through an input ref); None rebuilds it from ``matrix``.
    """
    o, s = matrix.shape
    if o == 0:
        return []
    t = data.shape[-1]
    if mat is None:
        key = np.ascontiguousarray(matrix, dtype=np.uint8).tobytes()
        mat = jnp.asarray(_bit_matrix(key, o, s))
    mat = mat.astype(jnp.bfloat16)
    # (s, 4t): byte plane j of every word, side by side on the lane dim
    bts = jnp.concatenate(
        [(data >> jnp.uint32(8 * j)) & jnp.uint32(0xFF) for j in range(4)],
        axis=-1,
    ).astype(jnp.int32)
    bits = jnp.stack(
        [(bts >> b) & 1 for b in range(8)], axis=1
    )  # (s, 8, 4t): row order 8c+b after reshape
    bits = bits.reshape(8 * s, 4 * t).astype(jnp.bfloat16)
    counts = jnp.dot(mat, bits, preferred_element_type=jnp.float32)
    pbits = (counts.astype(jnp.int32) & 1).reshape(o, 8, 4 * t)
    acc8 = pbits[:, 0, :].astype(jnp.uint32)
    for tbit in range(1, 8):
        acc8 = acc8 | (pbits[:, tbit, :].astype(jnp.uint32) << tbit)
    out = acc8[:, :t]
    for j in range(1, 4):
        out = out | (acc8[:, j * t : (j + 1) * t] << jnp.uint32(8 * j))
    return [out[r] for r in range(o)]


def _rows_fn(formulation: str):
    if formulation == "swar":
        return _swar_rows
    if formulation == "mxu":
        return _mxu_rows
    raise ValueError(f"unknown codec formulation: {formulation!r}")


# ---------------------------------------------------------------------------
# One-kernel codec: a single pass per direction (PUT encode+hash, GET
# verify+reconstruct)
# ---------------------------------------------------------------------------


def _encode_kernel_factory(matrix: np.ndarray, tw: int, formulation: str):
    m, k = matrix.shape
    mxu = _rows_fn(formulation) is _mxu_rows

    def impl(data_ref, parity_ref, hacc_ref, mat):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _zero():
            hacc_ref[...] = jnp.zeros_like(hacc_ref)

        data = data_ref[0]  # (k, tw)
        parity_rows = (
            _mxu_rows(matrix, data, mat) if mxu else _swar_rows(matrix, data)
        )
        all_rows = jnp.concatenate(
            [data, jnp.stack(parity_rows)], axis=0
        )  # (n, tw)
        parity_ref[0] = all_rows[k:]
        hacc_ref[0] = hacc_ref[0] ^ _tile_hash_partials(all_rows, i, tw)

    if mxu:

        def kernel(mat_ref, data_ref, parity_ref, hacc_ref):
            impl(data_ref, parity_ref, hacc_ref, mat_ref[...])

    else:

        def kernel(data_ref, parity_ref, hacc_ref):
            impl(data_ref, parity_ref, hacc_ref, None)

    return kernel


def _mxu_operand(matrix: np.ndarray):
    """(bit-matrix input list, matching in_spec list) for an MXU kernel
    on the (batch, w-tile) fused grids."""
    o, s = matrix.shape
    key = np.ascontiguousarray(matrix, dtype=np.uint8).tobytes()
    mat = jnp.asarray(_bit_matrix(key, o, s))
    return [mat], [pl.BlockSpec((8 * o, 8 * s), lambda b, i: (0, 0))]


@functools.partial(
    jax.jit,
    static_argnames=("parity_shards", "formulation", "interpret"),
)
def encode_hash_fused(
    words,
    parity_shards: int,
    formulation: str = "swar",
    interpret: bool = False,
):
    """One-kernel PUT codec pass: (B, k, w) data words -> ((B, m, w)
    parity words, (B, n, 8) un-finalized phash partials covering data
    AND parity rows), ONE pallas_call.

    Grid is (batch, w-tiles); the hash-partial output block for a stripe
    is revisited across its w-tiles and XOR-accumulated in VMEM, so HBM
    traffic is exactly data-in + parity-out (data shards never
    round-trip: the host already holds their bytes).  Finalize partials
    with hash.finalize_partials(partials, shard_len_bytes).
    """
    B, k, w = words.shape
    m = parity_shards
    n = k + m
    if m <= 0:
        raise ValueError("encode_hash_fused needs parity_shards >= 1")
    if w % _TW:
        raise ValueError(f"words per shard ({w}) must be a multiple of {_TW}")
    matrix = gf.parity_matrix(k, m)
    kernel = _encode_kernel_factory(matrix, _TW, formulation)
    extra_in, extra_specs = (
        _mxu_operand(matrix) if formulation == "mxu" else ([], [])
    )
    parity, hacc = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, m, w), jnp.uint32),
            jax.ShapeDtypeStruct((B, n, 8), jnp.uint32),
        ),
        grid=(B, w // _TW),
        in_specs=extra_specs
        + [pl.BlockSpec((1, k, _TW), lambda b, i: (b, 0, i))],
        out_specs=(
            pl.BlockSpec((1, m, _TW), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, n, 8), lambda b, i: (b, 0, 0)),
        ),
        interpret=interpret,
    )(*extra_in, words)
    return parity, hacc


def _vr_kernel_factory(
    rmatrix: np.ndarray, idx: tuple, n: int, tw: int, formulation: str
):
    mxu = _rows_fn(formulation) is _mxu_rows

    def impl(sh_ref, data_ref, hacc_ref, mat):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _zero():
            hacc_ref[...] = jnp.zeros_like(hacc_ref)

        sh = sh_ref[0]  # (n, tw), rows AS READ (absent rows: garbage)
        surv = jnp.stack([sh[j, :] for j in idx])  # (k, tw) static gather
        rows = (
            _mxu_rows(rmatrix, surv, mat) if mxu else _swar_rows(rmatrix, surv)
        )
        data_ref[0] = jnp.stack(rows)
        hacc_ref[0] = hacc_ref[0] ^ _tile_hash_partials(sh, i, tw)

    if mxu:

        def kernel(mat_ref, sh_ref, data_ref, hacc_ref):
            impl(sh_ref, data_ref, hacc_ref, mat_ref[...])

    else:

        def kernel(sh_ref, data_ref, hacc_ref):
            impl(sh_ref, data_ref, hacc_ref, None)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "present_idx",
        "data_shards",
        "parity_shards",
        "formulation",
        "interpret",
    ),
)
def verify_reconstruct_fused(
    shards,
    present_idx: tuple,
    data_shards: int,
    parity_shards: int,
    formulation: str = "swar",
    interpret: bool = False,
):
    """One-kernel GET codec pass: bitrot partials for every shard row +
    reconstruction from the static survivor set, ONE pallas_call.

    shards: (B, n, w) u32 as read; present_idx: the k survivor row
    indices (static).  Returns (data (B, k, w) u32, partials (B, n, 8)
    u32 un-finalized - finalize and compare against stored digests
    outside; each shard byte is read from HBM exactly once for both).
    """
    B, n, w = shards.shape
    k, m = data_shards, parity_shards
    if n != k + m:
        raise ValueError("shard rows must equal k + m")
    idx = tuple(int(i) for i in present_idx)
    if len(idx) != k:
        raise ValueError(f"need exactly {k} survivor indices, got {len(idx)}")
    if w % _TW:
        raise ValueError(f"words per shard ({w}) must be a multiple of {_TW}")
    rm = gf.reconstruction_matrix(k, m, idx)
    kernel = _vr_kernel_factory(rm, idx, n, _TW, formulation)
    extra_in, extra_specs = (
        _mxu_operand(rm) if formulation == "mxu" else ([], [])
    )
    data, hacc = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, k, w), jnp.uint32),
            jax.ShapeDtypeStruct((B, n, 8), jnp.uint32),
        ),
        grid=(B, w // _TW),
        in_specs=extra_specs
        + [pl.BlockSpec((1, n, _TW), lambda b, i: (b, 0, i))],
        out_specs=(
            pl.BlockSpec((1, k, _TW), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, n, 8), lambda b, i: (b, 0, 0)),
        ),
        interpret=interpret,
    )(*extra_in, shards)
    return data, hacc
