"""ctypes loader for the native C++ GF(2^8) codec (native/csrc/gf_cpu.cc).

Builds the shared library on first use (g++ -O3 -mavx2) and caches it under
native/build/.  This is the CPU fallback erasure backend - the counterpart
of klauspost/reedsolomon's role in the reference - selected when no TPU is
present or via MINIO_ERASURE_BACKEND=cpu (BASELINE.json north-star seam).

The built artifact is fingerprinted by a hash of the source file, the
compiler flags and the host CPU's feature flags (``libgf_cpu-<hash>.so``):
editing csrc, changing flags or moving the tree to a different CPU yields
a different path and therefore a rebuild, so a stale library body - or one
whose ``-march=native`` resolved to instructions this host lacks - can
never be silently loaded (an mtime check misses checkouts and clock skew,
and the old ``AttributeError`` guard only caught *missing* symbols, not
stale ones).

The hot entry points are batch-native: ``encode_and_hash_cpu`` runs the
fused single-pass encode+digest kernel over a whole (B, k, L) batch in ONE
C call (stripe-parallel inside; ctypes drops the GIL for the duration), and
``reconstruct_batch_cpu`` / ``reconstruct_and_verify_cpu`` are the decode
twins.  The per-stripe ``gf_matmul_cpu`` remains for tests and the
``--codec-micro`` split baseline.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(_ROOT, "native", "csrc", "gf_cpu.cc")
_BUILD_DIR = os.path.join(_ROOT, "native", "build")

_CFLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-pthread"]

# ASan+UBSan build variant (MINIO_TPU_SANITIZE=1): undefined behaviour
# is fatal (-fno-sanitize-recover), frames are kept for readable
# reports.  -O1 instead of -O3: redzone checks dominate anyway and the
# sanitized library exists for the slow test sweep, not for speed.
_SAN_CFLAGS = [
    "-O1",
    "-g",
    "-fno-omit-frame-pointer",
    "-fsanitize=address,undefined",
    "-fno-sanitize-recover=all",
]

_lock = threading.Lock()
_libs: "dict[str, ctypes.CDLL]" = {}


def _variant() -> str:
    """"" for the production build, "san" under MINIO_TPU_SANITIZE=1."""
    return "san" if os.environ.get("MINIO_TPU_SANITIZE") == "1" else ""


def _flags(variant: str = "") -> "list[str]":
    if variant == "san":
        return [f for f in _CFLAGS if f != "-O3"] + _SAN_CFLAGS
    return list(_CFLAGS)


def _host_isa() -> str:
    """What ``-march=native`` resolves against on this host: the CPU's
    feature flags as the kernel reports them (x86 ``flags``, arm
    ``Features``).  Part of the .so identity, because a library built
    on an AVX-512 host and copied with the tree dies of an illegal
    instruction on a host without it."""
    with open("/proc/cpuinfo") as f:
        for line in f:
            if line.startswith(("flags", "Features")):
                return " ".join(sorted(line.split(":", 1)[1].split()))
    return ""


def _fingerprint(variant: str = "") -> str:
    """Hash of the source body + compiler flags + host ISA: the .so
    identity."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(b"\x00" + " ".join(_flags(variant)).encode())
    h.update(b"\x00" + _host_isa().encode())
    return h.hexdigest()[:16]


def _so_path(variant: str = "") -> str:
    suffix = f"-{variant}" if variant else ""
    return os.path.join(
        _BUILD_DIR, f"libgf_cpu-{_fingerprint(variant)}{suffix}.so"
    )


def _build(variant: str = "") -> str:
    so = _so_path(variant)
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = so + f".tmp.{os.getpid()}"
    cmd = ["g++", *_flags(variant), "-o", tmp, _SRC]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so)
    # retire other fingerprints OF THE SAME VARIANT (including the
    # legacy unfingerprinted libgf_cpu.so) so the build dir doesn't
    # accrete one .so per edit; the sanitized and production artifacts
    # coexist - pruning across variants would force a rebuild on every
    # alternation between the test sweep and normal runs
    for name in os.listdir(_BUILD_DIR):
        if (
            name.startswith("libgf_cpu")
            and name.endswith(".so")
            and name.endswith("-san.so") == (variant == "san")
            and os.path.join(_BUILD_DIR, name) != so
        ):
            try:
                os.remove(os.path.join(_BUILD_DIR, name))
            except OSError:
                pass  # another process may hold/clean it concurrently
    return so


def default_threads() -> int:
    """Stripe-parallel worker count for the batch entry points.

    ``MINIO_TPU_NATIVE_THREADS`` overrides; defaults to the host's core
    count.  On a 1-core host this is 1 and the native kernels run
    strictly inline (no thread spawn).
    """
    try:
        v = int(os.environ.get("MINIO_TPU_NATIVE_THREADS") or 0)
    except ValueError:
        v = 0
    if v > 0:
        return v
    return os.cpu_count() or 1


def lib() -> ctypes.CDLL:
    variant = _variant()
    with _lock:
        if variant not in _libs:
            l = ctypes.CDLL(_build(variant))
            l.gf_matmul.argtypes = [
                ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
            ]
            l.gf_matmul.restype = None
            l.gf_mul_acc.argtypes = [
                ctypes.c_uint8, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_size_t,
            ]
            l.gf_mul_acc.restype = None
            l.gf_has_avx2.restype = ctypes.c_int
            # fingerprinted paths make a stale body unreachable, but a
            # hand-copied prebuilt .so could still predate a symbol:
            # its absence must only disable that entry point, never
            # break the ones that DO exist
            if hasattr(l, "phash256_rows"):
                l.phash256_rows.argtypes = [
                    ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
                    ctypes.c_uint64, ctypes.c_void_p,
                ]
                l.phash256_rows.restype = None
            if hasattr(l, "encode_and_hash"):
                l.encode_and_hash.argtypes = [
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,
                    ctypes.c_size_t, ctypes.c_void_p, ctypes.c_char_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
                ]
                l.encode_and_hash.restype = None
            if hasattr(l, "reconstruct_batch"):
                l.reconstruct_batch.argtypes = [
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,
                    ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int,
                ]
                l.reconstruct_batch.restype = None
            if hasattr(l, "reconstruct_and_verify"):
                l.reconstruct_and_verify.argtypes = [
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,
                    ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
                ]
                l.reconstruct_and_verify.restype = None
            _libs[variant] = l
    return _libs[variant]


def _ptr_array(arrs: list[np.ndarray]) -> "ctypes.Array":
    ptrs = (ctypes.c_void_p * len(arrs))()
    for i, a in enumerate(arrs):
        assert a.dtype == np.uint8 and a.flags.c_contiguous
        ptrs[i] = a.ctypes.data_as(ctypes.c_void_p)
    return ptrs


def gf_matmul_cpu(matrix: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """out = matrix (o, s) GF-matmul shards (s, len) -> (o, len), native."""
    o, s = matrix.shape
    assert shards.shape[0] == s
    length = shards.shape[1]
    out = np.zeros((o, length), dtype=np.uint8)
    in_rows = [np.ascontiguousarray(shards[i]) for i in range(s)]
    out_rows = [out[i] for i in range(o)]
    lib().gf_matmul(
        o, s, np.ascontiguousarray(matrix, dtype=np.uint8).tobytes(),
        _ptr_array(in_rows), _ptr_array(out_rows), length,
    )
    return out


def gf_mul_acc_cpu(
    coef: int, src: np.ndarray, dst: np.ndarray
) -> np.ndarray:
    """dst ^= coef * src in GF(2^8), native single mul-acc (tests)."""
    src = np.ascontiguousarray(src, dtype=np.uint8)
    dst = np.ascontiguousarray(dst, dtype=np.uint8)
    assert src.shape == dst.shape
    lib().gf_mul_acc(
        coef,
        src.ctypes.data_as(ctypes.c_void_p),
        dst.ctypes.data_as(ctypes.c_void_p),
        src.shape[0],
    )
    return dst


def encode_cpu(data: np.ndarray, parity_shards: int) -> np.ndarray:
    """Native-CPU RS encode: (k, len) -> (m, len)."""
    from ..ops import gf

    return gf_matmul_cpu(gf.parity_matrix(data.shape[0], parity_shards), data)


def encode_and_hash_cpu(
    data: np.ndarray, parity_shards: int, nthreads: "int | None" = None
) -> "tuple[np.ndarray, np.ndarray]":
    """Fused single-pass batch encode+digest: ONE native call per batch.

    data: (B, k, L) uint8, L a multiple of 32.  Returns
    (parity (B, m, L) uint8, digests (B, k+m, 8) uint32, data rows
    first) - bit-identical to the split gf_matmul + phash256_rows path
    and to the numpy/jax twins, but each byte is touched once while
    L1/L2-hot instead of three times through DRAM.
    """
    from ..ops import gf

    data = np.ascontiguousarray(data, dtype=np.uint8)
    B, k, L = data.shape
    m = parity_shards
    if L % 32:
        raise ValueError(f"shard length {L} must be a multiple of 32")
    parity = np.empty((B, m, L), dtype=np.uint8)
    digests = np.empty((B, k + m, 8), dtype=np.uint32)
    matrix = np.ascontiguousarray(
        gf.parity_matrix(k, m), dtype=np.uint8
    ).tobytes() if m else b""
    lib().encode_and_hash(
        B, k, m, L,
        data.ctypes.data_as(ctypes.c_void_p),
        matrix,
        parity.ctypes.data_as(ctypes.c_void_p),
        digests.ctypes.data_as(ctypes.c_void_p),
        nthreads if nthreads is not None else default_threads(),
    )
    return parity, digests


def _survivors(present: np.ndarray, k: int) -> "tuple[np.ndarray, tuple]":
    idx = tuple(int(i) for i in np.nonzero(present)[0])
    if len(idx) < k:
        raise ValueError(f"need {k} shards to reconstruct, have {len(idx)}")
    return np.asarray(idx[:k], dtype=np.int32), idx


def reconstruct_batch_cpu(
    shards: np.ndarray,
    present: np.ndarray,
    data_shards: int,
    parity_shards: int,
    nthreads: "int | None" = None,
) -> np.ndarray:
    """Batched native reconstruct: (B, n, L) + mask -> (B, k, L), one call."""
    from ..ops import gf

    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    B, n, L = shards.shape
    k = data_shards
    surv, idx = _survivors(np.asarray(present, dtype=bool), k)
    rm = gf.reconstruction_matrix(k, parity_shards, idx)
    out = np.empty((B, k, L), dtype=np.uint8)
    lib().reconstruct_batch(
        B, n, k, L,
        shards.ctypes.data_as(ctypes.c_void_p),
        surv.ctypes.data_as(ctypes.c_void_p),
        np.ascontiguousarray(rm, dtype=np.uint8).tobytes(),
        out.ctypes.data_as(ctypes.c_void_p),
        nthreads if nthreads is not None else default_threads(),
    )
    return out


def reconstruct_and_verify_cpu(
    shards: np.ndarray,
    digests: np.ndarray,
    present: np.ndarray,
    data_shards: int,
    parity_shards: int,
    nthreads: "int | None" = None,
) -> "tuple[np.ndarray, np.ndarray]":
    """Fused GET-side pass: verify digests of the present shards AND
    decode the data rows from the first k of them, one memory pass.

    Returns (data (B, k, L) uint8, ok (B, n) bool).  ``data`` is valid
    for a stripe only where every chosen survivor verified; the caller
    re-picks survivors from ``ok`` on the rare bitrot hit.
    """
    from ..ops import gf

    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    digests = np.ascontiguousarray(digests, dtype=np.uint32)
    B, n, L = shards.shape
    k = data_shards
    if L % 32:
        raise ValueError(f"shard length {L} must be a multiple of 32")
    pres = np.ascontiguousarray(
        np.asarray(present, dtype=bool), dtype=np.uint8
    )
    surv, idx = _survivors(pres.astype(bool), k)
    rm = gf.reconstruction_matrix(k, parity_shards, idx)
    ok = np.empty((B, n), dtype=np.uint8)
    out = np.empty((B, k, L), dtype=np.uint8)
    lib().reconstruct_and_verify(
        B, n, k, L,
        shards.ctypes.data_as(ctypes.c_void_p),
        surv.ctypes.data_as(ctypes.c_void_p),
        np.ascontiguousarray(rm, dtype=np.uint8).tobytes(),
        digests.ctypes.data_as(ctypes.c_void_p),
        pres.ctypes.data_as(ctypes.c_void_p),
        ok.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        nthreads if nthreads is not None else default_threads(),
    )
    return out, ok.astype(bool)


def reconstruct_cpu(
    shards: np.ndarray,
    present: np.ndarray,
    data_shards: int,
    parity_shards: int,
) -> np.ndarray:
    """Native-CPU RS reconstruct of the data rows: -> (k, len)."""
    from ..ops import gf

    present = np.asarray(present, dtype=bool)
    idx = tuple(int(i) for i in np.nonzero(present)[0])
    rm = gf.reconstruction_matrix(data_shards, parity_shards, idx)
    survivors = shards[list(idx[:data_shards])]
    return gf_matmul_cpu(rm, survivors)


def has_avx2() -> bool:
    return bool(lib().gf_has_avx2())


def phash256_rows(words: np.ndarray, nbytes: int) -> np.ndarray:
    """Native phash256 over rows: (..., w) uint32 -> (..., 8) uint32.

    Bit-identical AVX2 twin of ops/hash.py phash256_host_batched; the
    hash dominated the CPU-codec e2e path in profiling (the encode
    itself is native already)."""
    words = np.ascontiguousarray(words, dtype=np.uint32)
    lead = words.shape[:-1]
    n = words.shape[-1]
    if n % 4:
        # mirror the numpy twin's contract so digests can never
        # silently diverge between hosts with and without the lib
        raise ValueError(f"word count {n} must be a multiple of 4")
    flat = words.reshape(-1, n)
    out = np.empty((flat.shape[0], 8), dtype=np.uint32)
    lib().phash256_rows(
        flat.ctypes.data_as(ctypes.c_void_p),
        flat.shape[0],
        n,
        nbytes,
        out.ctypes.data_as(ctypes.c_void_p),
    )
    return out.reshape(*lead, 8)


# ---------------------------------------------------------------------
# Sanitizer harness (MINIO_TPU_SANITIZE=1)
#
# The instrumented library cannot be dlopen'd into an uninstrumented
# CPython: the ASan runtime must be first in the initial library list.
# The supported recipe is a SUBPROCESS with the env from
# sanitizer_env(): LD_PRELOAD of the toolchain's libasan plus
# PYTHONMALLOC=malloc, so ctypes scratch buffers get real redzones
# instead of hiding inside pymalloc arenas (numpy buffers use malloc
# either way).  tests/test_native.py's slow sweep drives this.
# ---------------------------------------------------------------------


def asan_runtime_path() -> "str | None":
    """The toolchain's libasan.so for LD_PRELOAD, or None if absent."""
    try:
        out = subprocess.run(
            ["g++", "-print-file-name=libasan.so"],
            capture_output=True,
            text=True,
            check=True,
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return None
    # an unresolved name is echoed back bare, with no directory part
    if os.path.sep in out and os.path.exists(out):
        return os.path.realpath(out)
    return None


def sanitizer_env(base: "dict | None" = None) -> "dict[str, str]":
    """Subprocess env that makes lib() load the instrumented build."""
    env = dict(os.environ if base is None else base)
    env["MINIO_TPU_SANITIZE"] = "1"
    env["PYTHONMALLOC"] = "malloc"
    rt = asan_runtime_path()
    if rt:
        env["LD_PRELOAD"] = rt
    # leaks are checked explicitly mid-run (lsan_recoverable_leak_check)
    # - the at-exit sweep would drown in CPython's own still-reachable
    # allocations under PYTHONMALLOC=malloc
    env.setdefault("ASAN_OPTIONS", "detect_leaks=1:leak_check_at_exit=0")
    env.setdefault("UBSAN_OPTIONS", "print_stacktrace=1")
    return env


def lsan_recoverable_leak_check() -> int:
    """Run LeakSanitizer now; 0 = clean, nonzero = native leaks found.

    Only meaningful inside a sanitizer_env() subprocess; returns 0 when
    the LSan runtime is not loaded.
    """
    try:
        fn = ctypes.CDLL(None).__lsan_do_recoverable_leak_check
    except (AttributeError, OSError):
        return 0
    fn.restype = ctypes.c_int
    fn.argtypes = []
    return int(fn())
