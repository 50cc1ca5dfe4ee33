"""Native C++ codec: correctness vs the GF reference and the JAX codec."""

import numpy as np
import pytest

from minio_tpu.ops import gf
from minio_tpu.utils import native


def test_build_and_avx2_flag():
    assert isinstance(native.has_avx2(), bool)


def test_encode_matches_reference():
    rng = np.random.default_rng(0)
    for k, m in [(2, 2), (4, 2), (8, 4), (16, 4)]:
        data = rng.integers(0, 256, (k, 1000)).astype(np.uint8)
        got = native.encode_cpu(data, m)
        assert np.array_equal(got, gf.encode_ref(data, m)), (k, m)


def test_encode_unaligned_tail():
    # lengths not multiples of 32 exercise the scalar tail path
    rng = np.random.default_rng(1)
    for L in (1, 31, 33, 100, 1023):
        data = rng.integers(0, 256, (4, L)).astype(np.uint8)
        got = native.encode_cpu(data, 2)
        assert np.array_equal(got, gf.encode_ref(data, 2)), L


def test_reconstruct_roundtrip():
    rng = np.random.default_rng(2)
    k, m = 8, 4
    data = rng.integers(0, 256, (k, 4096)).astype(np.uint8)
    parity = native.encode_cpu(data, m)
    shards = np.concatenate([data, parity])
    present = np.ones(k + m, bool)
    present[[1, 4, 8, 11]] = False
    got = native.reconstruct_cpu(shards, present, k, m)
    assert np.array_equal(got, data)


def test_native_phash_bit_identical_and_fast():
    """AVX2 phash256 twin must match the numpy reference exactly
    (shard files hashed by either verify under the other)."""
    import numpy as np

    from minio_tpu.ops import hash as ph
    from minio_tpu.utils import native

    rng = np.random.default_rng(11)
    for shape in [(3, 4, 256), (12, 4096), (1, 8), (2, 4), (5, 12)]:
        words = rng.integers(0, 2**32, shape, dtype=np.uint32)
        for nbytes in (shape[-1] * 4, shape[-1] * 4 - 3):
            a = native.phash256_rows(words, nbytes)
            b = ph.phash256_host_batched(words, nbytes)
            assert np.array_equal(a, b), (shape, nbytes)


# ---------------------------------------------------------------------
# Fused single-pass batch entry points (encode_and_hash / reconstruct)
# ---------------------------------------------------------------------


def _split_reference(data, m):
    """Parity + digests via the legacy split path primitives."""
    from minio_tpu.ops import hash as ph

    B, k, L = data.shape
    parity = np.stack(
        [native.encode_cpu(data[b], m) for b in range(B)]
    ) if m else np.zeros((B, 0, L), np.uint8)
    allsh = np.ascontiguousarray(np.concatenate([data, parity], axis=1))
    dig = ph.phash256_host_batched(
        allsh.reshape(B * (k + m), -1).view(np.uint32), L
    ).reshape(B, k + m, 8)
    return parity, dig


def test_fused_encode_identity_grid():
    """Native-fused batch kernel vs split native + numpy hash, across
    geometries, batch sizes, and single/multi-tile padded lengths."""
    rng = np.random.default_rng(3)
    for k, m in [(8, 4), (4, 2)]:
        for B in (1, 5):
            for L in (32, 96, 4096 + 32, 40960):
                data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
                par, dig = native.encode_and_hash_cpu(data, m)
                rpar, rdig = _split_reference(data, m)
                assert np.array_equal(par, rpar), (k, m, B, L)
                assert np.array_equal(dig, rdig), (k, m, B, L)


def test_fused_encode_zero_parity_and_threads():
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, (3, 4, 2048), dtype=np.uint8)
    par, dig = native.encode_and_hash_cpu(data, 0)
    assert par.shape == (3, 0, 2048)
    _, rdig = _split_reference(data, 0)
    assert np.array_equal(dig, rdig)
    # the stripe worker pool must be bit-identical to inline
    par1, dig1 = native.encode_and_hash_cpu(data, 2, nthreads=1)
    par3, dig3 = native.encode_and_hash_cpu(data, 2, nthreads=3)
    assert np.array_equal(par1, par3) and np.array_equal(dig1, dig3)


def test_fused_encode_rejects_unpadded_length():
    import pytest

    data = np.zeros((1, 4, 100), dtype=np.uint8)
    with pytest.raises(ValueError):
        native.encode_and_hash_cpu(data, 2)


def test_reconstruct_batch_cpu_matches_per_stripe():
    rng = np.random.default_rng(5)
    k, m = 8, 4
    n = k + m
    data = rng.integers(0, 256, (4, k, 1024), dtype=np.uint8)
    par, _ = native.encode_and_hash_cpu(data, m)
    shards = np.concatenate([data, par], axis=1)
    present = np.ones(n, bool)
    present[[0, 5, 9]] = False
    shards[:, [0, 5, 9]] = 0
    got = native.reconstruct_batch_cpu(shards, present, k, m)
    assert np.array_equal(got, data)
    for b in range(4):
        ref = native.reconstruct_cpu(shards[b], present, k, m)
        assert np.array_equal(got[b], ref)


def test_reconstruct_and_verify_cpu_flags_bitrot():
    rng = np.random.default_rng(6)
    k, m = 4, 2
    n = k + m
    data = rng.integers(0, 256, (3, k, 512), dtype=np.uint8)
    par, dig = native.encode_and_hash_cpu(data, m)
    shards = np.concatenate([data, par], axis=1)
    present = np.ones(n, bool)
    present[1] = False
    shards[:, 1] = 0
    out, ok = native.reconstruct_and_verify_cpu(
        shards, dig, present, k, m
    )
    assert np.array_equal(out, data)
    assert np.array_equal(ok, np.tile(present, (3, 1)))
    # flip one byte in a chosen survivor of stripe 1 only
    shards[1, 0, 7] ^= 0x40
    out, ok = native.reconstruct_and_verify_cpu(
        shards, dig, present, k, m
    )
    assert not ok[1, 0] and ok[0, 0] and ok[2, 0]
    assert np.array_equal(out[0], data[0])
    assert np.array_equal(out[2], data[2])


# ---------------------------------------------------------------------
# CpuBackend: batch-native dispatch, fallback twins, cross-backend
# bit-identity with the jax codec
# ---------------------------------------------------------------------


def _fresh_cpu_backend():
    from minio_tpu.codec.backend import CpuBackend

    return CpuBackend()


def _reset_native_state():
    from minio_tpu.codec.backend import CpuBackend

    CpuBackend._native_ok = None
    CpuBackend._native_hash_ok = None


def test_cpu_backend_one_native_call_no_concat(monkeypatch):
    """Acceptance: encode() is exactly ONE native call per batch and
    never rebuilds the full shard batch to feed the digest."""
    _reset_native_state()
    be = _fresh_cpu_backend()
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (6, 8, 1024), dtype=np.uint8)
    rpar, rdig = _split_reference(data, 4)
    calls = {"fused": 0, "matmul": 0, "hash": 0}
    real = native.encode_and_hash_cpu

    def counting(data, m, nthreads=None):
        calls["fused"] += 1
        return real(data, m, nthreads)

    monkeypatch.setattr(native, "encode_and_hash_cpu", counting)
    monkeypatch.setattr(
        native, "gf_matmul_cpu",
        lambda *a, **k: calls.__setitem__("matmul", calls["matmul"] + 1),
    )
    monkeypatch.setattr(
        native, "phash256_rows",
        lambda *a, **k: calls.__setitem__("hash", calls["hash"] + 1),
    )
    par, dig = be.encode(data, 4)
    assert calls == {"fused": 1, "matmul": 0, "hash": 0}
    assert np.array_equal(par, rpar) and np.array_equal(dig, rdig)


def test_cross_backend_bit_identity():
    """Parity + digests identical across native-fused, native-split
    (legacy path kept callable), numpy twins, and the jax codec."""
    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.ops import codec_step, hash as ph

    _reset_native_state()
    be = _fresh_cpu_backend()
    rng = np.random.default_rng(8)
    for k, m in [(8, 4), (4, 2)]:
        for B, L in [(1, 32), (3, 96), (2, 4096 + 32)]:
            data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
            par_f, dig_f = be.encode(data, m)
            par_s, dig_s = be.encode_split(data, m)
            par_n = backend_mod._numpy_encode(data, m)
            dig_n = np.concatenate(
                [
                    ph.phash256_host_batched(data.view(np.uint32), L),
                    ph.phash256_host_batched(par_n.view(np.uint32), L),
                ],
                axis=1,
            )
            shards_j, dig_j = codec_step.encode_and_hash(data, m)
            par_j = shards_j[:, k:, :]
            for name, (p, d) in {
                "split": (par_s, dig_s),
                "numpy": (par_n, dig_n),
                "jax": (par_j, dig_j),
            }.items():
                assert np.array_equal(par_f, p), (name, k, m, B, L)
                assert np.array_equal(dig_f, d), (name, k, m, B, L)


def test_cpu_backend_fallback_warns_once_and_matches(monkeypatch):
    """A failed native build must demote to the numpy twins cleanly:
    one warning, bit-identical output, no retry storm."""
    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.codec.backend import CpuBackend

    _reset_native_state()
    rng = np.random.default_rng(9)
    k, m = 4, 2
    data = rng.integers(0, 256, (2, k, 256), dtype=np.uint8)
    rpar, rdig = _split_reference(data, m)  # before breaking the lib
    warnings = []
    monkeypatch.setattr(
        backend_mod._log, "warning",
        lambda msg, *a, **k: warnings.append(msg),
    )

    def broken_lib():
        raise OSError("simulated toolchain failure")

    monkeypatch.setattr(native, "lib", broken_lib)
    be = CpuBackend()
    par, dig = be.encode(data, m)
    be.encode(data, m)  # second call: cached decision, no second warn
    assert len(warnings) == 1
    assert CpuBackend._native_ok is False
    assert be.fused_encode is False
    # digest() independently degraded too (its own cache)
    assert CpuBackend._native_hash_ok is False
    assert np.array_equal(par, rpar) and np.array_equal(dig, rdig)
    # degraded decode path: composed reconstruct_and_verify, numpy twin
    n = k + m
    shards = np.concatenate([data, par], axis=1)
    present = np.ones(n, bool)
    present[0] = False
    shards[:, 0] = 0
    out, ok = be.reconstruct_and_verify(shards, dig, present, k, m)
    assert np.array_equal(out, data)
    assert np.array_equal(ok, np.tile(present, (2, 1)))
    _reset_native_state()


def test_cpu_backend_reconstruct_and_verify_repick():
    """Bitrot in a chosen survivor: the fused path re-picks survivors
    from the verified mask and still returns correct data."""
    import pytest

    _reset_native_state()
    be = _fresh_cpu_backend()
    rng = np.random.default_rng(10)
    k, m = 8, 4
    n = k + m
    data = rng.integers(0, 256, (2, k, 1024), dtype=np.uint8)
    par, dig = be.encode(data, m)
    shards = np.concatenate([data, par], axis=1)
    present = np.ones(n, bool)
    shards[0, 2, 11] ^= 0x01  # bitrot in survivor 2, stripe 0 only
    out, ok = be.reconstruct_and_verify(shards, dig, present, k, m)
    assert not ok[0, 2] and ok[1, 2]
    assert np.array_equal(out, data)
    # below quorum: k-1 intact -> ValueError for the caller to map
    few = np.zeros(n, bool)
    few[: k - 1] = True
    with pytest.raises(ValueError):
        be.reconstruct_and_verify(
            shards[:, :, :], dig, few, k, m
        )


def test_wrappers_delegate_fused_seam():
    """Telemetry + batcher wrappers must expose fused_encode and route
    reconstruct_and_verify to the inner fused implementation."""
    from minio_tpu.codec.batcher import BatchingBackend
    from minio_tpu.codec.telemetry import InstrumentedBackend, KernelStats

    _reset_native_state()
    stats = KernelStats()
    inst = InstrumentedBackend(_fresh_cpu_backend(), stats)
    assert inst.fused_encode is True
    rng = np.random.default_rng(12)
    k, m = 4, 2
    data = rng.integers(0, 256, (2, k, 128), dtype=np.uint8)
    par, dig = inst.encode(data, m)
    shards = np.concatenate([data, par], axis=1)
    present = np.ones(k + m, bool)
    out, ok = inst.reconstruct_and_verify(shards, dig, present, k, m)
    assert np.array_equal(out, data) and ok.all()
    ops = {row["op"] for row in stats.snapshot()["ops"]}
    assert "reconstruct_and_verify" in ops
    batched = BatchingBackend(inst)
    try:
        assert batched.fused_encode is True
        out2, ok2 = batched.reconstruct_and_verify(
            shards, dig, present, k, m
        )
        assert np.array_equal(out2, data) and ok2.all()
    finally:
        batched.shutdown()


# ---------------------------------------------------------------------
# Build hygiene: fingerprinted .so path
# ---------------------------------------------------------------------


def test_so_fingerprint_tracks_source_and_flags(tmp_path, monkeypatch):
    """Editing csrc (or changing flags) must change the artifact path,
    forcing a rebuild instead of silently loading a stale body."""
    src = tmp_path / "mini.cc"
    src.write_text('extern "C" int mini_answer(void) { return 41; }\n')
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    p1 = native._build()
    assert p1.endswith(".so") and "libgf_cpu-" in p1
    import ctypes
    import os

    assert ctypes.CDLL(p1).mini_answer() == 41
    # source edit -> new fingerprint -> rebuild; stale artifact pruned
    src.write_text('extern "C" int mini_answer(void) { return 42; }\n')
    p2 = native._build()
    assert p2 != p1
    assert ctypes.CDLL(p2).mini_answer() == 42
    assert not os.path.exists(p1)
    # same source again: cached, no recompile needed to get same path
    assert native._build() == p2
    # flag change alone also re-fingerprints
    monkeypatch.setattr(
        native, "_CFLAGS", [*native._CFLAGS, "-DMINI_EXTRA"]
    )
    assert native._so_path() != p2


def test_so_fingerprint_tracks_host_isa(monkeypatch):
    """-march=native bakes the build host's ISA into the library; a tree
    copied to a host with other CPU features must rebuild, not load."""
    here = native._so_path()
    assert native._host_isa(), "no CPU feature flags found on this host"
    monkeypatch.setattr(
        native, "_host_isa", lambda: "fpu sse2 ssse3"  # no avx anything
    )
    assert native._so_path() != here


# ---------------------------------------------------------------------
# ASan/UBSan-instrumented builds: the san variant compiles under its
# own fingerprint, and a slow sweep replays the bit-identity and
# fault-injection grids above inside a sanitizer subprocess.
# ---------------------------------------------------------------------


def test_sanitizer_variant_has_its_own_fingerprint():
    prod, san = native._so_path(), native._so_path("san")
    assert san != prod
    assert san.endswith("-san.so") and not prod.endswith("-san.so")
    flags = native._flags("san")
    assert "-O3" not in flags
    assert "-fsanitize=address,undefined" in flags
    # production flags untouched
    assert "-O3" in native._flags()


def _run_sanitized(body, tmp_path):
    """Run a python snippet inside the ASan/UBSan subprocess env."""
    import os
    import subprocess
    import sys

    from minio_tpu.analysis import REPO_ROOT

    driver = tmp_path / "san_driver.py"
    driver.write_text(body)
    env = native.sanitizer_env()
    env["PYTHONPATH"] = REPO_ROOT
    return subprocess.run(
        [sys.executable, str(driver)],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
        cwd=REPO_ROOT,
    )


_SAN_SWEEP = """\
import numpy as np

from minio_tpu.ops import hash as ph
from minio_tpu.utils import native

assert native._variant() == "san", "sanitizer env did not propagate"

rng = np.random.default_rng(3)
for k, m in [(8, 4), (4, 2)]:
    for B in (1, 5):
        for L in (32, 96, 4096 + 32, 40960):
            data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
            par, dig = native.encode_and_hash_cpu(data, m)
            rpar = np.stack([native.encode_cpu(data[b], m) for b in range(B)])
            allsh = np.ascontiguousarray(np.concatenate([data, par], axis=1))
            rdig = ph.phash256_host_batched(
                allsh.reshape(B * (k + m), -1).view(np.uint32), L
            ).reshape(B, k + m, 8)
            assert np.array_equal(par, rpar), (k, m, B, L)
            assert np.array_equal(dig, rdig), (k, m, B, L)

# reconstruct_batch vs per-stripe (erasure fault injection)
k, m = 8, 4
data = rng.integers(0, 256, (4, k, 1024), dtype=np.uint8)
par, _ = native.encode_and_hash_cpu(data, m)
shards = np.concatenate([data, par], axis=1)
present = np.ones(k + m, bool)
present[[0, 5, 9]] = False
shards[:, [0, 5, 9]] = 0
got = native.reconstruct_batch_cpu(shards, present, k, m)
assert np.array_equal(got, data)
for b in range(4):
    assert np.array_equal(
        native.reconstruct_cpu(shards[b], present, k, m), data[b]
    )

# reconstruct_and_verify bitrot injection
k, m = 4, 2
data = rng.integers(0, 256, (3, k, 512), dtype=np.uint8)
par, dig = native.encode_and_hash_cpu(data, m)
shards = np.concatenate([data, par], axis=1)
present = np.ones(k + m, bool)
present[1] = False
shards[:, 1] = 0
out, ok = native.reconstruct_and_verify_cpu(shards, dig, present, k, m)
assert np.array_equal(out, data)
assert np.array_equal(ok, np.tile(present, (3, 1)))
shards[1, 0, 7] ^= 0x40
out, ok = native.reconstruct_and_verify_cpu(shards, dig, present, k, m)
assert not ok[1, 0] and ok[0, 0] and ok[2, 0]
assert np.array_equal(out[0], data[0])
assert np.array_equal(out[2], data[2])

rc = native.lsan_recoverable_leak_check()
assert rc == 0, f"LeakSanitizer reported native leaks (rc={rc})"
print("SWEEP_OK")
"""

_SAN_OVERFLOW = """\
import ctypes

import numpy as np

from minio_tpu.utils import native

src = np.ones(64, dtype=np.uint8)
dst = np.zeros(64, dtype=np.uint8)
# corrupted length: 4096 > the 64-byte allocations - ASan must abort
native.lib().gf_mul_acc(
    2,
    src.ctypes.data_as(ctypes.c_void_p),
    dst.ctypes.data_as(ctypes.c_void_p),
    4096,
)
print("UNREACHABLE_OK")
"""


@pytest.mark.slow
def test_sanitizer_sweep_replays_grids_clean(tmp_path):
    if native.asan_runtime_path() is None:
        pytest.skip("toolchain has no libasan.so")
    r = _run_sanitized(_SAN_SWEEP, tmp_path)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "SWEEP_OK" in r.stdout


@pytest.mark.slow
def test_sanitizer_catches_corrupted_length(tmp_path):
    """The harness is live: a heap overflow from a wrong length
    argument must crash the sweep, not pass silently."""
    if native.asan_runtime_path() is None:
        pytest.skip("toolchain has no libasan.so")
    r = _run_sanitized(_SAN_OVERFLOW, tmp_path)
    assert r.returncode != 0, r.stdout + "\n" + r.stderr
    assert "AddressSanitizer" in r.stderr
    assert "UNREACHABLE_OK" not in r.stdout
