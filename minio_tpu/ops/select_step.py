"""Device-side S3 Select scan kernels (SWAR over uint64 word planes).

Layout contract
---------------

* ``arr`` is the chunk's bytes as a flat uint8 plane, padded to a
  multiple of 512 bytes with a filler byte that is never a newline,
  field delimiter, quote, CR, or NUL (the engine uses ``b"x"``), and
  always ending (before the pad) in a newline.
* Flag words are uint64 with ``0x80`` set in each byte lane that
  matches; the word view is a little-endian bitcast of 8 consecutive
  bytes, so lane ``i`` of word ``w`` is byte ``8*w + i``.  uint64
  requires x64 — every caller wraps these entry points in
  ``jax.enable_x64()`` (the flag is part of the jit
  cache key, so the contract checker does the same).
* Shifted lane flags come from static slices of a zero-padded word
  buffer (``W(k)`` = lanes of bytes at p+k), memoized and shared
  across atoms, so the whole screen stays one fused elementwise
  pass; rolling flag words per shift would cost a full memory pass
  each, and a screen needs ~20 shifts.  Wide planes are screened in
  ``WINDOW_WORDS`` cache blocks over that one shared buffer, so the
  flag temporaries stay LLC-resident and window edges keep full
  byte context.
* ``screen_chunk`` is the only O(N) pass: it fuses byte
  classification, the statement-compiled candidate screen, the hazard
  scalar, and per-64-byte (8-word) block popcount sums.  The screen is
  CONSERVATIVE — it may flag rows that do not match, never the
  reverse; exactness lives entirely in the host engines that re-filter
  the candidate rows.  Everything after it is O(candidates).
* Candidate flags sit on the ``\\n`` (anchor mode ``row``) or on any
  field-opening terminator (anchor mode ``field``); the byte AFTER a
  flagged position starts the screened field.

Screen atoms (static, hashable) compiled by s3select/device.py:

* ``("len", lo, hi)`` — first field length in [lo, hi] (a terminator
  at offset length+1 from the flag).
* ``("deep", k)``     — no terminator within the first k field bytes.
* ``("byte0", lo, hi)`` — first field byte in [lo, hi] (ASCII).
* ``("nd", k)``       — a non-digit, non-terminator byte within the
  first k field bytes.
* ``("lex", lit, mode)`` — field lexicographically <, <=, ==, >=, >
  the literal byte string (mode in "lt|le|eq|ge|gt"), exact over the
  first ``len(lit)`` bytes plus the terminator.

MTPU204: every jitted entry point here has a contract block in
minio_tpu/analysis/kernel_contracts.py.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

PAD_BYTE = 0x78  # b"x": never nl/fd/quote/CR/NUL
BLOCK_BYTES = 512  # plane padding granularity (callers pad to this)
POP_WORDS = 8  # words per popcount block (64 bytes): the reshape
# factor of screen_chunk's block sums and extract_positions' ranks
MAX_LEX = 8  # lex/byte-chain depth cap (screen shifts stay bounded)
WINDOW_WORDS = 1 << 18  # 2 MiB per screen window (cache blocking)

_LO = 0x0101010101010101
_HI = 0x8080808080808080


def _u64(x) -> jnp.ndarray:
    return jnp.uint64(np.uint64(x))


def _words(arr):
    """Little-endian uint64 view of the byte plane."""
    return lax.bitcast_convert_type(arr.reshape(-1, 8), jnp.uint64)


def _swar_eq(w, byte):
    """0x80 flag in each lane equal to ``byte``."""
    x = w ^ _u64(byte * _LO)
    return (x - _u64(_LO)) & ~x & _u64(_HI)


def _swar_ge(w, c):
    """0x80 flag where lane >= c; only meaningful for ASCII lanes
    (< 0x80) — non-ASCII lanes are ORed in separately by callers that
    need them."""
    return ((w & ~_u64(_HI)) + _u64((0x80 - c) * _LO)) & _u64(_HI)


def _atom_words(atom, W, term_at, digit_at):
    """Flag-words for one screen atom, anchored one byte BEFORE the
    field (i.e. on the opening terminator).  ``W(k)`` is the word
    plane shifted so lane p carries byte p+k; ``term_at(k)`` /
    ``digit_at(k)`` are the memoized terminator / digit flags on it.
    A mask the old roll-based kernel built as ``byteshift(f(w), k)``
    is ``f(W(k))`` here — same flags, no shift pass."""
    kind = atom[0]
    if kind == "len":
        lo, hi = atom[1], atom[2]
        m = _u64(0)
        for ln in range(lo, hi + 1):
            m = m | term_at(ln + 1)
        return m
    if kind == "deep":
        k = atom[1]
        seen = _u64(0)
        for i in range(1, k + 1):
            seen = seen | term_at(i)
        return ~seen & _u64(_HI)
    if kind == "byte0":
        lo, hi = atom[1], atom[2]
        w1 = W(1)
        m = _swar_ge(w1, lo) & ~_swar_ge(w1, hi + 1)
        if lo == 0:
            # ASCII-only trick misses nothing at the low end, but a
            # [0, hi] range must not claim non-ASCII lanes
            m = m & ~(w1 & _u64(_HI))
        return m
    if kind == "nd":
        k = atom[1]
        seen = _u64(0)
        hit = _u64(0)
        for i in range(1, k + 1):
            nd = ~digit_at(i) & ~term_at(i) & _u64(_HI)
            hit = hit | (nd & ~seen)
            seen = seen | term_at(i)
        return hit
    if kind == "lex":
        lit, mode = atom[1], atom[2]
        n = min(len(lit), MAX_LEX)
        pref = _u64(_HI)  # field[:i] == lit[:i] so far (i = 0)
        hit = _u64(0)
        for i in range(n):
            wi = W(i + 1)
            if mode in ("lt", "le"):
                below = _swar_ge(wi, 0) & ~_swar_ge(wi, lit[i]) \
                    if lit[i] > 0 else _u64(0)
                hit = hit | (pref & below)
                # strict prefix (field ends first) sorts below
                hit = hit | (pref & term_at(i + 1))
            elif mode in ("gt", "ge"):
                above = (_swar_ge(wi, lit[i] + 1) | (wi & _u64(_HI))) \
                    if lit[i] < 0x7F else (wi & _u64(_HI))
                hit = hit | (pref & above & ~term_at(i + 1))
            pref = pref & _swar_eq(wi, lit[i])
        endv = term_at(n + 1)
        if mode in ("eq", "le", "ge"):
            if len(lit) <= MAX_LEX:
                hit = hit | (pref & endv)
            else:
                hit = hit | pref  # prefix-truncated: keep conservative
        if mode in ("gt", "ge"):
            hit = hit | (pref & ~endv)  # longer field, lit is a prefix
        if mode == "lt" and len(lit) > MAX_LEX:
            hit = hit | pref  # can't see past the cap: conservative
        return hit
    raise ValueError(f"unknown screen atom {atom!r}")


def _max_shift(atoms, sci_guard: bool) -> int:
    """Largest forward byte offset any atom (or the hazard pass)
    reads — sizes the zero pad behind the word buffer."""
    m = 1  # bare-CR hazard looks at p+1
    for branch in atoms:
        for atom in branch:
            kind = atom[0]
            if kind == "len":
                m = max(m, atom[2] + 1)
            elif kind in ("deep", "nd"):
                m = max(m, atom[1])
            elif kind == "byte0":
                m = max(m, 1)
            elif kind == "lex":
                m = max(m, min(len(atom[1]), MAX_LEX) + 1)
    return m


@functools.partial(
    jax.jit, static_argnames=("fd", "qc", "atoms", "anchor", "sci_guard")
)
def screen_chunk(
    arr, *, fd: int, qc: int, atoms, anchor: str, sci_guard: bool
):
    """The O(N) fused pass.

    Returns ``(cand, blk, nrows, hazard)``: candidate flag-words
    (uint64), per-64-byte (``POP_WORDS``-word) block candidate
    popcounts (int32), total row count (int32 scalar), and the hazard
    scalar (bool) — quote, bare
    CR, or NUL anywhere in the chunk sends the whole chunk to the
    host engine.  ``atoms`` is a tuple of tuples of screen atoms: the
    outer level ORs (one entry per OR branch), the inner level ANDs.

    Shifted lane flags come from static SLICES of a zero-padded word
    buffer (two slices + two bit-shifts per distinct byte offset,
    memoized and shared across atoms), not from rolling flag words:
    a roll is a full memory pass, and a screen needs ~20 shifts.
    Zero words past the plane end reproduce the roll-based shift's
    fill exactly, so the candidate set is unchanged.

    The screen is cache-blocked: planes wider than ``WINDOW_WORDS``
    are screened window by window (an unrolled loop over static
    slices), so each window's ~6 materialised flag temporaries stay
    LLC-resident instead of spilling to DRAM.  Every window still
    slices the ONE shared padded buffer, so cross-window lookahead,
    the sci guard's byte ``p-1``, and the bare-CR check all read real
    neighbouring bytes — the output is bit-identical to a
    single-window pass.
    """
    w = _words(arr)
    nw = w.shape[0]
    qmax = _max_shift(atoms, sci_guard) // 8 + 1

    def window(s: int, m: int):
        """cand flags + packed block sums for words [s, s+m).

        Each window gets its own small padded buffer — one front word
        (byte ``p-1`` context: the previous window's last word, or
        zero at the plane start), the window's words, then real
        lookahead words from the next window where the plane has
        them, zeros past its end.  The buffer is LLC-sized, so every
        memoized shifted view reads cache-resident lanes."""
        t = min(qmax + 1, nw - s - m)  # real lookahead words available
        front = (
            lax.slice(w, (s - 1,), (s,))
            if s
            else jnp.zeros(1, jnp.uint64)
        )
        pieces = [front, lax.slice(w, (s,), (s + m + t,))]
        if t < qmax + 1:
            pieces.append(jnp.zeros(qmax + 1 - t, jnp.uint64))
        wp = jnp.concatenate(pieces)
        shifted: dict = {}

        def W(k: int):
            got = shifted.get(k)
            if got is None:
                q, r = divmod(k, 8)
                lo = lax.slice(wp, (q + 1,), (q + 1 + m,))
                if r:
                    hi = lax.slice(wp, (q + 2,), (q + 2 + m,))
                    got = (lo >> _u64(8 * r)) | (hi << _u64(64 - 8 * r))
                else:
                    got = lo
                shifted[k] = got
            return got

        def term_at(k: int):
            got = shifted.get(("t", k))
            if got is None:
                wk = W(k)
                got = _swar_eq(wk, 10) | _swar_eq(wk, fd)
                shifted[("t", k)] = got
            return got

        def digit_at(k: int):
            got = shifted.get(("d", k))
            if got is None:
                wk = W(k)
                got = _swar_ge(wk, 0x30) & ~_swar_ge(wk, 0x3A)
                shifted[("d", k)] = got
            return got

        ww = W(0)
        nl = _swar_eq(ww, 10)
        base = nl if anchor == "row" else term_at(0)
        hit = _u64(0)
        for branch in atoms:
            bm = _u64(_HI)
            for atom in branch:
                bm = bm & _atom_words(atom, W, term_at, digit_at)
            hit = hit | bm
        cand = base & hit
        hazflags = (
            _swar_eq(ww, qc)
            | (_swar_eq(ww, 13) & ~_swar_eq(W(1), 10))
            | _swar_eq(ww, 0)
        )
        if sci_guard:
            # a digit-prefixed exponent field ("1000e-8") coerces
            # numeric with a value no length/shape atom can bound:
            # any digit immediately followed by e/E sends the chunk
            # to the host
            e = _swar_eq(ww, 0x65) | _swar_eq(ww, 0x45)
            hazflags = hazflags | (e & digit_at(-1))
        # one reduction pass for all three aggregates: pack the
        # per-word candidate popcount (<=8, bits 0-6 after the
        # POP_WORDS-word block sum), newline popcount (bits 7-13) and
        # hazard bit (bits 14+) into one int32 per word, block-sum
        # once, then unpack per block
        combo = (
            lax.population_count(cand).astype(jnp.int32)
            | (lax.population_count(nl).astype(jnp.int32) << 7)
            | ((hazflags != 0).astype(jnp.int32) << 14)
        )
        bsum = combo.reshape(-1, POP_WORDS).sum(axis=1, dtype=jnp.int32)
        # materialise each window's pair behind a barrier: without it
        # XLA folds the windows into the two output concatenates and
        # recomputes the whole screen once per output
        return lax.optimization_barrier((cand, bsum))

    parts = [
        window(s, min(WINDOW_WORDS, nw - s))
        for s in range(0, nw, WINDOW_WORDS)
    ]
    if len(parts) == 1:
        cand, bs = parts[0]
    else:
        cand = jnp.concatenate([p[0] for p in parts])
        bs = jnp.concatenate([p[1] for p in parts])
    return (
        cand,
        bs & 127,
        ((bs >> 7) & 127).sum(dtype=jnp.int32),
        (bs >> 14).any(),
    )


@functools.partial(jax.jit, static_argnames=("cap",))
def extract_positions(cand, cum, *, cap: int):
    """Byte positions of the first ``cap`` candidate flags.

    ``cum`` is the inclusive cumsum of the block popcounts; ranks
    beyond the true count return clamped garbage the caller slices
    off (it knows the count from ``cum[-1]``)."""
    k = jnp.arange(cap, dtype=jnp.int32)
    blk = jnp.searchsorted(cum, k, side="right").astype(jnp.int32)
    blk = jnp.minimum(blk, cum.shape[0] - 1)
    base = jnp.where(blk > 0, cum[jnp.maximum(blk - 1, 0)], 0)
    lr = k - base
    wrds = cand.reshape(-1, POP_WORDS)[blk]
    pcs = lax.population_count(wrds).astype(jnp.int32)
    pref = jnp.cumsum(pcs, axis=1) - pcs
    inw = (pref <= lr[:, None]) & (lr[:, None] < pref + pcs)
    wsel = jnp.argmax(inw, axis=1).astype(jnp.int32)
    word = jnp.take_along_axis(wrds, wsel[:, None], axis=1)[:, 0]
    need = (
        lr - jnp.take_along_axis(pref, wsel[:, None], axis=1)[:, 0] + 1
    )
    need = jnp.maximum(need, 1).astype(jnp.uint64)
    p = jnp.zeros(cap, dtype=jnp.int32)
    half = 32
    while half:
        lowmask = (_u64(1) << _u64(half)) - _u64(1)
        c = lax.population_count(word & lowmask).astype(jnp.uint64)
        go = c < need
        need = jnp.where(go, need - c, need)
        word = jnp.where(go, word >> _u64(half), word)
        p = jnp.where(go, p + half, p)
        half //= 2
    return ((blk * POP_WORDS + wsel) << 3) + (p >> 3)


@functools.partial(jax.jit, static_argnames=("window",))
def row_spans(arr, anchors, *, window: int):
    """Length of the row starting at ``anchor + 1``: offset of the
    first newline in a forward window, and whether one was found
    (rows wider than the window are host-verified)."""
    start = anchors + 1
    gidx = start[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]
    mat = arr[jnp.clip(gidx, 0, arr.shape[0] - 1)]
    isnl = mat == 10
    found = isnl.any(axis=1)
    return jnp.argmax(isnl, axis=1).astype(jnp.int32), found


@functools.partial(jax.jit, static_argnames=("window",))
def anchors_back(arr, hits, *, window: int):
    """Row anchor (position of the preceding newline, -1 for row 0)
    for mid-row field hits, via a backward window scan; ``found`` is
    False when the window ended before a newline or the chunk start."""
    offs = jnp.arange(window, dtype=jnp.int32)
    gidx = hits[:, None] - offs[None, :]
    mat = arr[jnp.clip(gidx, 0, arr.shape[0] - 1)]
    isnl = (mat == 10) & (gidx >= 0)
    off = jnp.argmax(isnl, axis=1).astype(jnp.int32)
    anynl = isnl.any(axis=1)
    reach0 = (hits - (window - 1)) <= 0
    anch = jnp.where(anynl, hits - off, jnp.int32(-1))
    return anch, anynl | reach0


@functools.partial(jax.jit, static_argnames=("window",))
def gather_rows(arr, starts, *, window: int):
    """(C, window) uint8 view of the rows at ``starts`` — the
    result-proportional buffer the drain seam copies to host."""
    gidx = starts[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]
    return arr[jnp.clip(gidx, 0, arr.shape[0] - 1)]
