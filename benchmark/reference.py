"""The plain reference: what an S3 object store over Reed-Solomon shards has
to answer, written without a line of the program.

Two parts.  ``StoreModel`` is the S3 semantics the generator's requests are
checked against, one answer at a time: a PUT makes a key readable with
exactly those bytes, a DELETE makes it a 404, a STAT names the length.
``decode_object`` is the durability side: it reads an object's shard files
straight off the drive directories, takes any k of the n shards, and decodes
them with a numpy Reed-Solomon over GF(2^8) (polynomial 0x11d, the
Vandermonde-derived systematic matrix of klauspost/reedsolomon, which the
configuration's source uses).  It takes nothing the program computed: no
matrix, table or digest.
"""

from __future__ import annotations

import functools
import os

import numpy as np

TRAILER = 64  # last bytes of every payload name its key and version
FRAME_DIGEST = 32  # bitrot digest before every shard block on the drive
ALIGN = 32  # shard blocks are zero-padded to this on the drive


class StoreModel:
    """Sequential S3 semantics for the keys one owner touches."""

    def __init__(self) -> None:
        self.version: "dict[str, int]" = {}  # key -> live version (absent: no object)
        self.size: "dict[str, int]" = {}
        self._next: "dict[str, int]" = {}

    def put(self, key: str, size: int) -> int:
        v = self._next.get(key, 0) + 1
        self._next[key] = v
        self.version[key] = v
        self.size[key] = size
        return v

    def delete(self, key: str) -> None:
        self.version.pop(key, None)
        self.size.pop(key, None)

    def live(self, key: str) -> "tuple[int, int] | None":
        """(version, size) a GET or STAT has to see, None for a 404."""
        if key not in self.version:
            return None
        return self.version[key], self.size[key]


def trailer(key: str, version: int) -> bytes:
    return f"{key}#{version}".encode().ljust(TRAILER, b".")


# --------------------------------------------------------------------------
# GF(2^8) Reed-Solomon, klauspost's construction
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gf() -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    a = np.arange(1, 256)
    for b in range(1, 256):
        mul[a, b] = exp[log[a] + log[b]]
    return exp, log, mul


def _mul(a: int, b: int) -> int:
    return int(_gf()[2][a, b])


def _inv(a: int) -> int:
    exp, log, _ = _gf()
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(exp[255 - log[a]])


def _pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    exp, log, _ = _gf()
    return int(exp[(int(log[a]) * n) % 255])


def _mat_mul(a: "list[list[int]]", b: "list[list[int]]") -> "list[list[int]]":
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, x in enumerate(row):
                acc ^= _mul(x, b[t][j])
            out[i][j] = acc
    return out


def _mat_inv(m: "list[list[int]]") -> "list[list[int]]":
    n = len(m)
    a = [list(row) + [int(i == j) for j in range(n)] for i, row in enumerate(m)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        s = _inv(a[col][col])
        a[col] = [_mul(x, s) for x in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [x ^ _mul(f, y) for x, y in zip(a[r], a[col])]
    return [row[n:] for row in a]


@functools.lru_cache(maxsize=None)
def encode_matrix(k: int, m: int) -> "tuple[tuple[int, ...], ...]":
    """n x k systematic matrix: Vandermonde rows r^c, times the inverse of
    its top k x k square (klauspost/reedsolomon buildMatrix)."""
    vm = [[_pow(r, c) for c in range(k)] for r in range(k + m)]
    full = _mat_mul(vm, _mat_inv(vm[:k]))
    return tuple(tuple(row) for row in full)


def _apply(matrix: "list[list[int]]", rows: "list[np.ndarray]") -> "list[np.ndarray]":
    mul = _gf()[2]
    out = []
    for coeffs in matrix:
        acc = np.zeros_like(rows[0])
        for c, row in zip(coeffs, rows):
            if c:
                acc ^= mul[c][row]
        out.append(acc)
    return out


def decode_shards(k: int, m: int, have: "dict[int, np.ndarray]") -> "list[np.ndarray]":
    """The k data shards from any k shards ``{shard index (0-based): bytes}``."""
    if len(have) != k:
        raise ValueError(f"need exactly {k} shards, got {len(have)}")
    idx = sorted(have)
    full = encode_matrix(k, m)
    inv = _mat_inv([list(full[i]) for i in idx])
    return _apply(inv, [have[i] for i in idx])


# --------------------------------------------------------------------------
# shard files as the configuration's layout puts them on a drive
# --------------------------------------------------------------------------


def read_xl_meta(path: str) -> dict:
    import msgpack

    with open(path, "rb") as f:
        raw = f.read()
    if raw[:4] != b"XLT1":
        raise ValueError(f"{path}: not an xl.meta")
    return msgpack.unpackb(raw[4:], raw=False)


def shards_on_drives(drives: "list[str]", bucket: str, key: str) -> "dict[int, str]":
    """{shard index (0-based): part file} for the live version of an object,
    from each drive's own xl.meta.  A drive without the object is left out."""
    found: "dict[int, str]" = {}
    for d in drives:
        meta = os.path.join(d, bucket, key, "xl.meta")
        if not os.path.isfile(meta):
            continue
        ver = read_xl_meta(meta)["versions"][-1]
        if ver.get("deleted"):
            continue
        part = os.path.join(d, bucket, key, ver["data_dir"], "part.1")
        if os.path.isfile(part):
            found[int(ver["erasure"]["index"]) - 1] = part
    return found


def _shard_blocks(path: str, size: int, k: int, block_size: int) -> "list[np.ndarray]":
    """The shard's blocks with the interleaved digests stripped."""
    raw = np.fromfile(path, dtype=np.uint8)
    out, off, left = [], 0, size
    while left > 0:
        blen = min(block_size, left)
        padded = (-(-blen // k) + ALIGN - 1) // ALIGN * ALIGN
        out.append(raw[off + FRAME_DIGEST: off + FRAME_DIGEST + padded])
        if len(out[-1]) != padded:
            raise ValueError(f"{path}: short shard file")
        off += FRAME_DIGEST + padded
        left -= blen
    return out


def decode_object(parts: "dict[int, str]", use: "list[int]", size: int, k: int, m: int,
                  block_size: int) -> bytes:
    """The object's bytes decoded from the shards named in ``use`` (k of them)."""
    blocks = {i: _shard_blocks(parts[i], size, k, block_size) for i in use}
    out, left, b = [], size, 0
    while left > 0:
        blen = min(block_size, left)
        ss = -(-blen // k)
        data = decode_shards(k, m, {i: blocks[i][b] for i in use})
        out.append(np.concatenate([row[:ss] for row in data])[:blen].tobytes())
        left -= blen
        b += 1
    return b"".join(out)


def stored_bytes(parts: "dict[int, str]") -> int:
    return sum(os.path.getsize(p) for p in parts.values())
