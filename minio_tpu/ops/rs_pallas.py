"""Pallas TPU kernels for the GF(2^8) shard codec hot path.

"GF matrix @ shards" (the klauspost/reedsolomon role behind
cmd/erasure-coding.go:54-64) in SWAR form on the VPU (`_swar_rows`):
shards live as uint32 words (4 field elements per lane).
Multiply-by-constant uses the xtime-powers decomposition with the
generator matrix baked into the kernel at trace time, so each tile is a
straight-line XOR chain over VMEM-resident vectors - no tables, no
gathers, no dtype conversions.  Encode only: the generator matrix is one
per geometry.  Decode takes its matrix as an operand (the runtime-matrix
kernels at the end: one program per geometry, whatever the loss
pattern).

Throughput: PERF.md (`encode_roofline`, `reconstruct_roofline`).

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
from jax.experimental.pallas import tpu as pltpu

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


def _tile_hash_partials(all_rows, i, tw: int, nwords):
    """phash256 partials of (rows, tw) shard words at w-tile index i.

    Shared by every fused kernel; XOR-accumulate the (rows, 8) result
    into a revisited output block and finalize with
    hash.finalize_partials outside the kernel.  ``nwords`` is the
    stripe's true length in words, a scalar read from SMEM: a word at or
    past it is padding of the staged width and contributes nothing, so
    the partials are those of the exact-width shard.
    """
    from . import hash as phash

    gidx = (i * tw).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (1, tw), 1
    )
    key = phash._mix_jnp(gidx * phash._C1 + jnp.uint32(1))  # (1, tw)
    live = gidx < nwords.astype(jnp.uint32)
    m1 = jnp.where(live, phash._mix_jnp((all_rows ^ key) * phash._M1), 0)
    m2 = jnp.where(live, phash._mix_jnp((all_rows + key) * phash._M2), 0)

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
# One-kernel codec: a single pass per direction (PUT encode+hash, GET
# verify+reconstruct)
# ---------------------------------------------------------------------------


def _encode_kernel_factory(matrix: np.ndarray, tw: int):
    m, k = matrix.shape

    def kernel(nwords_ref, data_ref, parity_ref, hacc_ref):
        b, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i == 0)
        def _zero():
            hacc_ref[...] = jnp.zeros_like(hacc_ref)

        data = data_ref[0]  # (k, tw)
        all_rows = jnp.concatenate(
            [data, jnp.stack(_swar_rows(matrix, data))], axis=0
        )  # (n, tw)
        parity_ref[0] = all_rows[k:]
        hacc_ref[0] = hacc_ref[0] ^ _tile_hash_partials(
            all_rows, i, tw, nwords_ref[b]
        )

    return kernel


def _nwords(lengths, batch: int):
    """Traced byte lengths int32[batch] -> the kernels' SMEM operand."""
    if lengths.shape != (batch,):
        raise ValueError(
            f"need one length a stripe ({batch}), got {lengths.shape}"
        )
    return lengths.astype(jnp.int32) >> 2


@functools.partial(jax.jit, static_argnames=("parity_shards", "interpret"))
def encode_hash_fused(
    words, lengths, parity_shards: int, interpret: bool = False
):
    """One-kernel PUT codec pass: (B, k, w) data words -> ((B, m, w)
    parity words, (B, n, 8) un-finalized phash partials covering data
    AND parity rows), ONE pallas_call.

    ``lengths``: int32[B] TRACED, the true shard bytes of each stripe;
    w is the staged width, whole tiles, and the words past a stripe's
    length are padding the hash leaves out (scalar prefetch: the lengths
    sit in SMEM before the grid starts).  Reed-Solomon is column-wise,
    so the parity of zero padding columns is zero and needs no mask.

    Grid is (batch, w-tiles); the hash-partial output block for a stripe
    is revisited across its w-tiles and XOR-accumulated in VMEM, so HBM
    traffic is exactly data-in + parity-out (data shards never
    round-trip: the host already holds their bytes).  Finalize partials
    with hash.finalize_partials(partials, lengths).
    """
    B, k, w = words.shape
    m = parity_shards
    n = k + m
    if m <= 0:
        raise ValueError("encode_hash_fused needs parity_shards >= 1")
    if w % _TW:
        raise ValueError(f"words per shard ({w}) must be a multiple of {_TW}")
    matrix = gf.parity_matrix(k, m)
    parity, hacc = pl.pallas_call(
        _encode_kernel_factory(matrix, _TW),
        out_shape=(
            jax.ShapeDtypeStruct((B, m, w), jnp.uint32),
            jax.ShapeDtypeStruct((B, n, 8), jnp.uint32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, w // _TW),
            in_specs=[pl.BlockSpec((1, k, _TW), lambda b, i, nw: (b, 0, i))],
            out_specs=(
                pl.BlockSpec((1, m, _TW), lambda b, i, nw: (b, 0, i)),
                pl.BlockSpec((1, n, 8), lambda b, i, nw: (b, 0, 0)),
            ),
        ),
        interpret=interpret,
    )(_nwords(lengths, B), words)
    return parity, hacc


# ---------------------------------------------------------------------------
# Runtime-matrix kernels: the decode matrix is an OPERAND, not a constant
# ---------------------------------------------------------------------------
#
# Which k of n shards a read got is known only when it ends, and a hedged
# read gets an arbitrary k (C(12, 8) = 495 sets for 8+4).  A kernel with
# the matrix baked in is one program per set; these take the matrix as a
# traced array, so one program serves every loss pattern of a geometry.
#
# The tile is (s, tw) with the shard rows on the
# sublanes.  P_b = x^b * tile is seven dense xtimes; output row r is the
# XOR over (c, b) of P_b[c] where bit b of matrix[r, c] is set.  The bits
# arrive as 0 / 0xFFFFFFFF masks laid out (s * 8, o, 128): entry c * 8 + b
# holds, on sublane r, the mask of matrix[r, c] bit b, one vreg for o <= 8.
# A term is then: broadcast sublane c of P_b over the o output sublanes,
# AND the mask, XOR into the (o, lanes) accumulator - every operand dense,
# no gather (a row that must not contribute has a zero column).

# lanes per inner step: one vreg per mask, a handful live per step
_CH = 128


def runtime_masks(matrix):
    """Traced (o, s) uint8 GF matrix -> (s * 8, o, _CH) uint32 AND-masks
    for the runtime SWAR kernels (a few KiB of XLA work per call)."""
    o, s = matrix.shape
    m32 = matrix.astype(jnp.uint32)
    bits = (m32[:, :, None] >> jnp.arange(8, dtype=jnp.uint32)) & 1
    masks = (jnp.uint32(0) - bits).transpose(1, 2, 0).reshape(s * 8, o, 1)
    return jnp.broadcast_to(masks, (s * 8, o, _CH))


def _runtime_rows(mask_ref, tile, o: int):
    """(s, lanes) tile x the masks' matrix -> (o, lanes), lanes == _CH."""
    s, lanes = tile.shape
    accs = [jnp.zeros((o, lanes), jnp.uint32) for _ in range(4)]
    p = tile
    t = 0
    for b in range(8):
        for c in range(s):
            row = jnp.broadcast_to(p[c : c + 1, :], (o, lanes))
            accs[t % 4] = accs[t % 4] ^ (row & mask_ref[c * 8 + b])
            t += 1
        if b != 7:
            p = rs._xtime(p)
    return (accs[0] ^ accs[1]) ^ (accs[2] ^ accs[3])


def _runtime_kernel_factory(o: int, tw: int, with_hash: bool):
    """Kernel over one (1, s, tw) block of shard rows as read: out =
    (operand matrix) GF@ rows and, ``with_hash``, the phash partials of
    all s rows accumulated over the w-tiles."""

    def kernel(*refs):
        # with_hash: the stripes' lengths come first (scalar prefetch)
        # and the partials' accumulator last
        mask_ref, sh_ref, data_ref = refs[with_hash : with_hash + 3]
        b, i = pl.program_id(0), pl.program_id(1)

        def step(j, carry):
            sl = pl.ds(pl.multiple_of(j * _CH, _CH), _CH)
            data_ref[0, :, sl] = _runtime_rows(mask_ref, sh_ref[0, :, sl], o)
            return carry

        jax.lax.fori_loop(0, tw // _CH, step, 0)
        if with_hash:
            nwords_ref, hacc = refs[0], refs[-1]

            @pl.when(i == 0)
            def _zero():
                hacc[...] = jnp.zeros_like(hacc)

            hacc[0] = hacc[0] ^ _tile_hash_partials(
                sh_ref[0], i, tw, nwords_ref[b]
            )

    return kernel


def _runtime_call(rows, matrix, interpret, lengths=None):
    """``lengths`` (int32[B] TRACED, each stripe's true shard bytes)
    asks for the hash partials too; the product alone needs none (zero
    padding columns decode to zero)."""
    B, s, w = rows.shape
    with_hash = lengths is not None
    o = matrix.shape[0]
    if matrix.shape != (o, s):
        raise ValueError(f"matrix {matrix.shape} does not take {s} rows")
    if w % _TW:
        raise ValueError(f"words per shard ({w}) must be a multiple of {_TW}")
    masks = runtime_masks(matrix)
    # *_: the scalar-prefetch ref, where the call has one
    out_shape = [jax.ShapeDtypeStruct((B, o, w), jnp.uint32)]
    out_specs = [pl.BlockSpec((1, o, _TW), lambda b, i, *_: (b, 0, i))]
    in_specs = [
        pl.BlockSpec(masks.shape, lambda b, i, *_: (0, 0, 0)),
        pl.BlockSpec((1, s, _TW), lambda b, i, *_: (b, 0, i)),
    ]
    grid = (B, w // _TW)
    if not with_hash:
        return pl.pallas_call(
            _runtime_kernel_factory(o, _TW, False),
            out_shape=tuple(out_shape),
            grid=grid,
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            interpret=interpret,
        )(masks, rows)
    out_shape.append(jax.ShapeDtypeStruct((B, s, 8), jnp.uint32))
    out_specs.append(pl.BlockSpec((1, s, 8), lambda b, i, *_: (b, 0, 0)))
    return pl.pallas_call(
        _runtime_kernel_factory(o, _TW, True),
        out_shape=tuple(out_shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=tuple(out_specs),
        ),
        interpret=interpret,
    )(_nwords(lengths, B), masks, rows)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul_rows_runtime(rows, matrix, interpret: bool = False):
    """(B, s, w) u32 shard rows x TRACED (o, s) uint8 GF matrix ->
    (B, o, w), ONE pallas_call, one program whatever the matrix holds."""
    (out,) = _runtime_call(rows, matrix, interpret)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def verify_reconstruct_runtime(
    shards, matrix, lengths, interpret: bool = False
):
    """One-kernel GET codec pass with the decode matrix an operand:
    bitrot partials for every shard row + reconstruction, ONE
    pallas_call.

    shards: (B, n, w) u32 as read, w the staged width; matrix: traced
    (k, n) uint8, the pattern's inverse scattered to its survivors'
    columns (zero columns for the rows that must not contribute);
    lengths: int32[B] TRACED, each stripe's true shard bytes.  Returns
    (data (B, k, w) u32, partials (B, n, 8) u32 un-finalized - finalize
    and compare against stored digests outside; each shard byte is read
    from HBM exactly once for both).
    """
    return _runtime_call(shards, matrix, interpret, lengths)
