"""Kernel contract checker: abstract-eval every jitted codec entry point.

``jax.eval_shape`` runs the tracer without compiling or executing, so the
shape/dtype contracts of the TPU codec kernels — including the Pallas
ones — are checkable on any host, no accelerator required.  For each
jitted entry point in ``minio_tpu/ops/`` a registered contract states,
over a grid of (data_shards, parity_shards, shard_len) erasure configs:

* MTPU201 — output dtypes (words stay uint32, byte shards stay uint8,
  verify masks are bool);
* MTPU202 — output shard shapes (parity rows = m, digest width = 8, ...);
* MTPU203 — encode→reconstruct shape round-trips: encoding (k, L) data
  and reconstructing after dropping all parity-count-many shards must
  yield (k, L) back, in both the byte and the packed-word domain;
* MTPU204 — a jitted entry point with NO registered contract.  The
  registry is closed over module introspection, so adding a kernel
  without a contract fails the gate rather than silently shrinking
  coverage.

Findings anchor at the entry point's ``def`` line and name the offending
config, e.g. ``(data_shards=8, parity_shards=4, shard_len=256)``.
"""

from __future__ import annotations

import os

from .findings import Finding

# (data_shards, parity_shards, shard_len_bytes); shard_len % 32 == 0
# (words-per-shard multiple of 8, the encode_and_hash_words floor).
CONFIG_GRID = [
    (2, 1, 64),
    (4, 2, 128),
    (8, 4, 256),
    (16, 4, 512),
]

# encode_hash_fused tiles at rs_pallas._TW uint32 words (16 KiB shards);
# keep this grid small — abstract eval of the Pallas kernel still traces
# the full XOR chain.
FUSED_GRID = [
    (2, 1, 16384),
    (4, 2, 16384),
    (8, 4, 16384),
]

_BATCH = 3  # leading batch dim for the batched kernels

# ---------------------------------------------------------------------------
# Device-dataflow registry (consumed by analysis/callgraph.py and
# analysis/deviceflow.py).
#
# These tables are the single source of truth for the whole-program
# MTPU5xx dataflow rules: which calls *produce* device-resident values,
# which argument positions are *donated* (dead after the call), and
# which functions are the sanctioned *drain seams* where device values
# may legally materialize on host.  MTPU505 cross-checks every table
# against the tree (a declared fact absent in code, or a code fact
# absent here, is a finding), so the registry cannot rot — the same
# discipline MTPU403 applies to the native export table.
# ---------------------------------------------------------------------------

# short module name -> repo-relative path of the module that defines it
ENTRY_POINT_PATHS = {
    "rs": "minio_tpu/ops/rs.py",
    "rs_pallas": "minio_tpu/ops/rs_pallas.py",
    "codec_step": "minio_tpu/ops/codec_step.py",
    "hash": "minio_tpu/ops/hash.py",
    "select_step": "minio_tpu/ops/select_step.py",
    "backend": "minio_tpu/codec/backend.py",
    "mesh": "minio_tpu/parallel/mesh.py",
    "rules": "minio_tpu/parallel/rules.py",
}

# Every jitted entry point the tree ships, (module_short_name, attr).
# Introspection (jit_entry_points) must find at least these — tier-1
# asserts it — and the callgraph pass must resolve a def node for each.
# Calls to any of these return device-resident values.
KNOWN_ENTRY_POINTS = {
    ("rs", "_encode_jit"),
    ("rs", "_reconstruct_jit"),
    ("rs_pallas", "_matmul_words_jit"),
    ("rs_pallas", "encode_hash_fused"),
    ("rs_pallas", "matmul_rows_runtime"),
    ("rs_pallas", "verify_reconstruct_runtime"),
    ("codec_step", "encode_and_hash_words"),
    ("codec_step", "encode_words_fused1"),
    ("codec_step", "verify_and_reconstruct_words"),
    ("codec_step", "verify_hashes_words"),
    ("codec_step", "digest_words"),
    ("codec_step", "reconstruct_words_batch"),
    ("select_step", "screen_chunk"),
    ("select_step", "extract_positions"),
    ("select_step", "row_spans"),
    ("select_step", "anchors_back"),
    ("select_step", "gather_rows"),
}

# (module_short_name, attr) -> donated positional argument indices.
# A value passed at a donated position is DEAD after the call (XLA may
# alias its buffer into an output); reading it again is the PR 14 bug
# class, caught statically as MTPU501.  MTPU505 cross-checks this table
# against the ``donate_argnums`` literals in the jit decorators.
DONATING_ENTRY_POINTS = {
    ("codec_step", "encode_words_fused1"): (0,),
}

# Mesh kernel kinds registered with the rules.py compile seam that
# declare donation (register_kernel(..., donate_argnums=...)).  MTPU505
# cross-checks against the register_kernel call sites in the tree.
MESH_DONATING_KERNELS = {
    "mesh_encode_hash": (0,),
}

# repo-relative path -> function names that are sanctioned drain seams:
# inside these, device values may materialize on host (np.asarray /
# bytes / .item() / jax.device_get), and their RETURN values are host
# facts, not device facts.  Names ending in ``_end`` or containing
# ``drain`` in these files MUST be registered here (MTPU505), so a new
# seam cannot appear without joining the audited set.
DRAIN_SEAMS = {
    "minio_tpu/codec/backend.py": (
        # PUT side: the begin/end split and the lazy parity-plane drain
        "encode_end",
        "encode_digest_end",
        "drain",
        # the one read-back helper every seam above goes through:
        # block_until_ready (seam_kernel_wait) then np.asarray (seam_d2h)
        "_host_readback",
        # GET side: decode IS the sanctioned D2H — reconstructed rows
        # leave the device here and nowhere else
        "reconstruct",
        "reconstruct_and_verify",
        "verify",
        "digest",
    ),
    "minio_tpu/s3select/device.py": (
        # candidate row bytes are the only payload that crosses D2H,
        # through exactly these functions (MTPU111 enforces locally)
        "_drain_scalars",
        "_drain_array",
        "_drain_fallback_chunk",
        "drain_plane",
    ),
    "minio_tpu/ops/codec_step.py": (
        # byte-domain convenience wrappers: eager by design (tests and
        # small host-side callers), documented in the module
        "encode_and_hash",
        "verify_hashes",
        "decode_and_verify",
    ),
    "minio_tpu/parallel/mesh.py": (
        # the mesh pipeline's sync point: begin dispatches async,
        # _end materializes — the double-buffer overlap contract
        "mesh_encode_hash_end",
    ),
}


def _ops_modules():
    # codec.backend is watched too: the PR 4 fused-codec seams
    # (encode_and_hash / reconstruct_and_verify) route through backend
    # objects, and a jitted wrapper landing there without a contract
    # must fail MTPU204 the same as one in ops/.  parallel.mesh/rules
    # register their kernels with the compile seam instead of module
    # attrs; watching them here catches a stray module-level jit, and
    # the seam registry gets its own MTPU204 closure in run().
    from minio_tpu.codec import backend
    from minio_tpu.ops import (
        codec_step,
        hash as phash,
        rs,
        rs_pallas,
        select_step,
    )
    from minio_tpu.parallel import mesh, rules

    return {
        "rs": rs,
        "rs_pallas": rs_pallas,
        "codec_step": codec_step,
        "hash": phash,
        "select_step": select_step,
        "backend": backend,
        "mesh": mesh,
        "rules": rules,
    }


def is_jitted(obj) -> bool:
    """True for jax.jit-wrapped callables (PjitFunction and kin)."""
    return (
        callable(obj)
        and hasattr(obj, "eval_shape")
        and hasattr(obj, "lower")
        and hasattr(obj, "__wrapped__")
    )


def jit_entry_points() -> "dict[tuple[str, str], object]":
    """(module_short_name, attr_name) -> jitted callable, by introspection.

    This is the ground truth the MTPU204 coverage check (and the tier-1
    introspection test) compare the contract registry against.
    """
    out = {}
    for mod_name, mod in _ops_modules().items():
        for attr, val in sorted(vars(mod).items()):
            if is_jitted(val):
                out[(mod_name, attr)] = val
    return out


def _anchor(fn, default_path: str) -> "tuple[str, int]":
    """Repo-relative path + def line of a jitted callable."""
    code = getattr(getattr(fn, "__wrapped__", fn), "__code__", None)
    if code is None:
        return default_path, 1
    path = code.co_filename
    marker = os.sep + "minio_tpu" + os.sep
    if marker in path:
        path = "minio_tpu" + os.sep + path.split(marker, 1)[1]
    return path.replace(os.sep, "/"), code.co_firstlineno


class _ContractContext:
    """Collects findings for one entry point, tagging the config."""

    def __init__(self, findings, fn, default_path):
        self.findings = findings
        self.path, self.line = _anchor(fn, default_path)
        self.config = ""

    def expect(self, rule: str, got, want, what: str) -> None:
        if got != want:
            self.findings.append(
                Finding(
                    rule,
                    self.path,
                    self.line,
                    f"{what}: got {got}, want {want} at {self.config}",
                )
            )

    def shape(self, got, want, what: str) -> None:
        self.expect("MTPU202", tuple(got.shape), tuple(want), what + " shape")

    def dtype(self, got, want, what: str) -> None:
        self.expect("MTPU201", str(got.dtype), str(want), what + " dtype")

    def fail(self, exc: BaseException) -> None:
        self.findings.append(
            Finding(
                "MTPU202",
                self.path,
                self.line,
                f"abstract eval raised {type(exc).__name__}: {exc} "
                f"at {self.config}",
            )
        )


def run() -> "list[Finding]":
    """Check every registered contract; returns findings (empty = green)."""
    import jax
    import jax.numpy as jnp

    from minio_tpu.ops import codec_step, gf, rs, rs_pallas

    findings: "list[Finding]" = []
    S = jax.ShapeDtypeStruct
    u8, u32, i32 = jnp.uint8, jnp.uint32, jnp.int32
    # a shard's byte length is an OPERAND of every codec program, one a
    # stripe (one a row for the digests): abstract, so one evaluation
    # stands for every length a staged width can hold
    LENS = S((_BATCH,), i32)

    def ctx(fn, default_path):
        return _ContractContext(findings, fn, default_path)

    def cfg_str(k, m, L):
        return f"(data_shards={k}, parity_shards={m}, shard_len={L})"

    checked: "set[tuple[str, str]]" = set()

    def covers(mod, name):
        checked.add((mod, name))

    def pattern(k, n=None):
        """A loss pattern's operands, abstract: (present bool[n],
        survivors int32[k], matrix uint8[k, k]).  No value, so one
        evaluation stands for every pattern of the geometry."""
        return (
            S((n if n is not None else k,), jnp.bool_),
            S((k,), jnp.int32),
            S((k, k), u8),
        )

    # ---- rs.py ----------------------------------------------------------

    covers("rs", "_encode_jit")
    c = ctx(rs._encode_jit, "minio_tpu/ops/rs.py")
    for k, m, L in CONFIG_GRID:
        c.config = cfg_str(k, m, L)
        try:
            out = rs._encode_jit.eval_shape(S((k, L), u8), k, m)
            c.shape(out, (m, L), "parity")
            c.dtype(out, "uint8", "parity")
        except Exception as e:  # pragma: no cover - defensive
            c.fail(e)

    covers("rs", "_reconstruct_jit")
    c = ctx(rs._reconstruct_jit, "minio_tpu/ops/rs.py")
    for k, m, L in CONFIG_GRID:
        n = k + m
        c.config = cfg_str(k, m, L)
        try:
            out = rs._reconstruct_jit.eval_shape(
                S((n, L), u8), S((n,), u8), S((k, k), u8), k, m, True
            )
            c.shape(out, (n, L), "rebuilt (want_parity)")
            c.dtype(out, "uint8", "rebuilt")
            out = rs._reconstruct_jit.eval_shape(
                S((n, L), u8), S((n,), u8), S((k, k), u8), k, m, False
            )
            c.shape(out, (k, L), "rebuilt (data only)")
        except Exception as e:
            c.fail(e)

    # MTPU203: encode -> reconstruct round-trip in the byte domain; the
    # pattern is an operand (mask + matrix), so ONE abstract evaluation
    # stands for every loss pattern of the geometry
    for k, m, L in CONFIG_GRID:
        n = k + m
        c.config = cfg_str(k, m, L)
        try:
            parity = rs._encode_jit.eval_shape(S((k, L), u8), k, m)
            data_only = rs._reconstruct_jit.eval_shape(
                S((k + parity.shape[0], L), parity.dtype),
                S((n,), u8), S((k, k), u8), k, m, False,
            )
            c.expect(
                "MTPU203",
                (tuple(data_only.shape), str(data_only.dtype)),
                ((k, L), "uint8"),
                "encode->reconstruct round-trip (bytes)",
            )
        except Exception as e:
            c.fail(e)

    # ---- codec_step.py --------------------------------------------------

    covers("codec_step", "encode_and_hash_words")
    c = ctx(codec_step.encode_and_hash_words, "minio_tpu/ops/codec_step.py")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L)
        try:
            parity, digests = codec_step.encode_and_hash_words.eval_shape(
                S((_BATCH, k, w), u32), m, LENS
            )
            c.shape(parity, (_BATCH, m, w), "parity")
            c.dtype(parity, "uint32", "parity")
            c.shape(digests, (_BATCH, n, 8), "digests")
            c.dtype(digests, "uint32", "digests")
        except Exception as e:
            c.fail(e)

    covers("codec_step", "verify_hashes_words")
    c = ctx(codec_step.verify_hashes_words, "minio_tpu/ops/codec_step.py")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L)
        try:
            ok = codec_step.verify_hashes_words.eval_shape(
                S((_BATCH, n, w), u32), S((_BATCH, n, 8), u32),
                S((_BATCH, n), i32),
            )
            c.shape(ok, (_BATCH, n), "ok mask")
            c.dtype(ok, "bool", "ok mask")
        except Exception as e:
            c.fail(e)

    covers("codec_step", "digest_words")
    c = ctx(codec_step.digest_words, "minio_tpu/ops/codec_step.py")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L)
        try:
            got = codec_step.digest_words.eval_shape(
                S((_BATCH, n, w), u32), S((_BATCH, n), i32)
            )
            c.shape(got, (_BATCH, n, 8), "digests")
            c.dtype(got, "uint32", "digests")
        except Exception as e:
            c.fail(e)

    covers("codec_step", "reconstruct_words_batch")
    c = ctx(codec_step.reconstruct_words_batch, "minio_tpu/ops/codec_step.py")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L)
        try:
            dw = codec_step.reconstruct_words_batch.eval_shape(
                S((_BATCH, n, w), u32), *pattern(k)[1:], k, m
            )
            c.shape(dw, (_BATCH, k, w), "data words")
            c.dtype(dw, "uint32", "data words")
            # MTPU203: word-domain round-trip — encode a batch, drop m
            # shards, reconstruct; shapes must close.
            parity, _ = codec_step.encode_and_hash_words.eval_shape(
                S((_BATCH, k, w), u32), m, LENS
            )
            rt = codec_step.reconstruct_words_batch.eval_shape(
                S((_BATCH, k + parity.shape[1], w), parity.dtype),
                *pattern(k)[1:],
                k,
                m,
            )
            c.expect(
                "MTPU203",
                (tuple(rt.shape), str(rt.dtype)),
                ((_BATCH, k, w), "uint32"),
                "encode->reconstruct round-trip (words)",
            )
        except Exception as e:
            c.fail(e)

    # ---- codec_step.py: the served one-pass codec ------------------------
    #
    # encode+digest resp. verify+reconstruct as one pass.  The XLA form
    # is checked over CONFIG_GRID; the Pallas path over FUSED_GRID in
    # interpret mode, so contract coverage matches everything the
    # dispatcher can launch.

    covers("codec_step", "encode_words_fused1")
    c = ctx(codec_step.encode_words_fused1, "minio_tpu/ops/codec_step.py")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L) + " [portable]"
        try:
            parity, digests = codec_step.encode_words_fused1.eval_shape(
                S((_BATCH, k, w), u32), m, LENS
            )
            c.shape(parity, (_BATCH, m, w), "fused1 parity")
            c.dtype(parity, "uint32", "fused1 parity")
            c.shape(digests, (_BATCH, n, 8), "fused1 digests")
            c.dtype(digests, "uint32", "fused1 digests")
        except Exception as e:
            c.fail(e)
    for k, m, L in FUSED_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L) + " [pallas]"
        try:
            parity, digests = codec_step.encode_words_fused1.eval_shape(
                S((_BATCH, k, w), u32), m, LENS, True, True
            )
            c.shape(parity, (_BATCH, m, w), "fused1 parity")
            c.dtype(parity, "uint32", "fused1 parity")
            c.shape(digests, (_BATCH, n, 8), "fused1 digests")
            c.dtype(digests, "uint32", "fused1 digests")
        except Exception as e:
            c.fail(e)

    covers("codec_step", "verify_and_reconstruct_words")
    c = ctx(
        codec_step.verify_and_reconstruct_words,
        "minio_tpu/ops/codec_step.py",
    )
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L) + " [portable]"
        try:
            data, ok = codec_step.verify_and_reconstruct_words.eval_shape(
                S((_BATCH, n, w), u32), S((_BATCH, n, 8), u32),
                *pattern(k, n), k, m, LENS,
            )
            c.shape(data, (_BATCH, k, w), "fused GET data words")
            c.dtype(data, "uint32", "fused GET data words")
            c.shape(ok, (_BATCH, n), "fused GET ok mask")
            c.dtype(ok, "bool", "fused GET ok mask")
            # MTPU203: fused1 encode -> fused1 verify+reconstruct closes
            parity, digests = codec_step.encode_words_fused1.eval_shape(
                S((_BATCH, k, w), u32), m, LENS
            )
            rt, _ = codec_step.verify_and_reconstruct_words.eval_shape(
                S((_BATCH, k + parity.shape[1], w), parity.dtype),
                S(tuple(digests.shape), digests.dtype),
                *pattern(k, n), k, m, LENS,
            )
            c.expect(
                "MTPU203",
                (tuple(rt.shape), str(rt.dtype)),
                ((_BATCH, k, w), "uint32"),
                "fused1 encode->verify+reconstruct round-trip (words)",
            )
        except Exception as e:
            c.fail(e)
    for k, m, L in FUSED_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L) + " [pallas]"
        try:
            data, ok = codec_step.verify_and_reconstruct_words.eval_shape(
                S((_BATCH, n, w), u32), S((_BATCH, n, 8), u32),
                *pattern(k, n), k, m, LENS, True, True,
            )
            c.shape(data, (_BATCH, k, w), "fused GET data words")
            c.dtype(data, "uint32", "fused GET data words")
            c.shape(ok, (_BATCH, n), "fused GET ok mask")
            c.dtype(ok, "bool", "fused GET ok mask")
        except Exception as e:
            c.fail(e)

    # ---- select_step.py: S3 Select scan kernels -------------------------
    #
    # SWAR flag-words are uint64, so every contract evaluates under
    # enable_x64 exactly like the runtime call sites (the flag is part
    # of the jit cache key).  The plane grid is tiny — shapes close over
    # N the same way at 64 MiB as at 4 KiB.

    from jax import enable_x64

    from minio_tpu.ops import select_step

    u8_ = jnp.uint8
    _SELECT_PLANES = (4096, 16384)  # bytes; multiples of BLOCK_BYTES
    # one branch per screen-atom kind, so the contract traces every
    # _atom_mask arm the compiler can emit
    _SELECT_ATOMS = (
        (("len", 0, 3),),
        (("deep", 2),),
        (("byte0", 43, 48),),
        (("nd", 4),),
        (("lex", b"42", "lt"),),
        (("lex", b"42", "ge"),),
        (("lex", b"name", "eq"),),
    )

    def sel_cfg(n, extra=""):
        return f"(plane_bytes={n}{extra})"

    with enable_x64():
        u64 = jnp.uint64
        wpb = select_step.POP_WORDS  # words per popcount block

        covers("select_step", "screen_chunk")
        c = ctx(select_step.screen_chunk, "minio_tpu/ops/select_step.py")
        for n in _SELECT_PLANES:
            for anchor in ("row", "field"):
                for sci in (False, True):
                    c.config = sel_cfg(
                        n, f", anchor={anchor}, sci_guard={sci}"
                    )
                    try:
                        cand, blk, nrows, haz = (
                            select_step.screen_chunk.eval_shape(
                                S((n,), u8_),
                                fd=44,
                                qc=34,
                                atoms=_SELECT_ATOMS,
                                anchor=anchor,
                                sci_guard=sci,
                            )
                        )
                        c.shape(cand, (n // 8,), "candidate flag words")
                        c.dtype(cand, "uint64", "candidate flag words")
                        c.shape(
                            blk, (n // (8 * wpb),), "block popcounts"
                        )
                        c.dtype(blk, "int32", "block popcounts")
                        c.shape(nrows, (), "row count")
                        c.dtype(nrows, "int32", "row count")
                        c.shape(haz, (), "hazard scalar")
                        c.dtype(haz, "bool", "hazard scalar")
                    except Exception as e:
                        c.fail(e)

        covers("select_step", "extract_positions")
        c = ctx(
            select_step.extract_positions, "minio_tpu/ops/select_step.py"
        )
        for n in _SELECT_PLANES:
            for cap in (64, 1024):
                c.config = sel_cfg(n, f", cap={cap}")
                try:
                    pos = select_step.extract_positions.eval_shape(
                        S((n // 8,), u64),
                        S((n // (8 * wpb),), jnp.int32),
                        cap=cap,
                    )
                    c.shape(pos, (cap,), "candidate byte positions")
                    c.dtype(pos, "int32", "candidate byte positions")
                except Exception as e:
                    c.fail(e)

        _C = 7  # candidate count for the windowed kernels

        covers("select_step", "row_spans")
        c = ctx(select_step.row_spans, "minio_tpu/ops/select_step.py")
        for n in _SELECT_PLANES:
            for window in (256, 4096):
                c.config = sel_cfg(n, f", window={window}")
                try:
                    lens, found = select_step.row_spans.eval_shape(
                        S((n,), u8_), S((_C,), jnp.int32), window=window
                    )
                    c.shape(lens, (_C,), "row lengths")
                    c.dtype(lens, "int32", "row lengths")
                    c.shape(found, (_C,), "row-end found mask")
                    c.dtype(found, "bool", "row-end found mask")
                except Exception as e:
                    c.fail(e)

        covers("select_step", "anchors_back")
        c = ctx(select_step.anchors_back, "minio_tpu/ops/select_step.py")
        for n in _SELECT_PLANES:
            for window in (256, 1024):
                c.config = sel_cfg(n, f", window={window}")
                try:
                    anch, found = select_step.anchors_back.eval_shape(
                        S((n,), u8_), S((_C,), jnp.int32), window=window
                    )
                    c.shape(anch, (_C,), "row anchors")
                    c.dtype(anch, "int32", "row anchors")
                    c.shape(found, (_C,), "anchor found mask")
                    c.dtype(found, "bool", "anchor found mask")
                except Exception as e:
                    c.fail(e)

        covers("select_step", "gather_rows")
        c = ctx(select_step.gather_rows, "minio_tpu/ops/select_step.py")
        for n in _SELECT_PLANES:
            for window in (64, 1024):
                c.config = sel_cfg(n, f", window={window}")
                try:
                    mat = select_step.gather_rows.eval_shape(
                        S((n,), u8_), S((_C,), jnp.int32), window=window
                    )
                    c.shape(mat, (_C, window), "gathered row matrix")
                    c.dtype(mat, "uint8", "gathered row matrix")
                except Exception as e:
                    c.fail(e)

        # sanity: the padding granularity must be whole popcount
        # blocks, or screen_chunk's reshape would fail on a padded
        # plane (512 bytes / (8 words * 8 bytes) today)
        assert select_step.BLOCK_BYTES % (wpb * 8) == 0
        assert all(n % select_step.BLOCK_BYTES == 0
                   for n in _SELECT_PLANES)

    # ---- rs_pallas.py ---------------------------------------------------

    covers("rs_pallas", "_matmul_words_jit")
    c = ctx(rs_pallas._matmul_words_jit, "minio_tpu/ops/rs_pallas.py")
    for k, m, L in CONFIG_GRID:
        w = L // 4
        key = gf.parity_matrix(k, m).tobytes()
        c.config = cfg_str(k, m, L)
        try:
            out = rs_pallas._matmul_words_jit.eval_shape(
                S((k, w), u32), key, m, k, True
            )
            c.shape(out, (m, w), "pallas parity words")
            c.dtype(out, "uint32", "pallas parity words")
        except Exception as e:
            c.fail(e)

    covers("rs_pallas", "encode_hash_fused")
    c = ctx(rs_pallas.encode_hash_fused, "minio_tpu/ops/rs_pallas.py")
    for k, m, L in FUSED_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L)
        try:
            parity, hacc = rs_pallas.encode_hash_fused.eval_shape(
                S((_BATCH, k, w), u32), LENS, m, True
            )
            c.shape(parity, (_BATCH, m, w), "fused parity")
            c.dtype(parity, "uint32", "fused parity")
            c.shape(hacc, (_BATCH, n, 8), "fused hash partials")
            c.dtype(hacc, "uint32", "fused hash partials")
        except Exception as e:
            c.fail(e)

    covers("rs_pallas", "matmul_rows_runtime")
    c = ctx(rs_pallas.matmul_rows_runtime, "minio_tpu/ops/rs_pallas.py")
    for k, m, L in FUSED_GRID:
        w, n = L // 4, k + m
        # all n rows as read against the (k, n) scattered inverse, and k
        # compacted survivor rows against the (k, k) inverse (the mesh)
        for rows in (n, k):
            c.config = cfg_str(k, m, L) + f" [{rows} rows]"
            try:
                out = rs_pallas.matmul_rows_runtime.eval_shape(
                    S((_BATCH, rows, w), u32), S((k, rows), u8), True
                )
                c.shape(out, (_BATCH, k, w), "runtime-matrix data words")
                c.dtype(out, "uint32", "runtime-matrix data words")
            except Exception as e:
                c.fail(e)

    covers("rs_pallas", "verify_reconstruct_runtime")
    c = ctx(
        rs_pallas.verify_reconstruct_runtime, "minio_tpu/ops/rs_pallas.py"
    )
    for k, m, L in FUSED_GRID:
        w, n = L // 4, k + m
        c.config = cfg_str(k, m, L)
        try:
            data, hacc = rs_pallas.verify_reconstruct_runtime.eval_shape(
                S((_BATCH, n, w), u32), S((k, n), u8), LENS, True
            )
            c.shape(data, (_BATCH, k, w), "fused GET data words")
            c.dtype(data, "uint32", "fused GET data words")
            c.shape(hacc, (_BATCH, n, 8), "fused GET hash partials")
            c.dtype(hacc, "uint32", "fused GET hash partials")
        except Exception as e:
            c.fail(e)

    # ---- parallel/mesh.py: compile-seam mesh kernels --------------------
    #
    # Mesh kernels are not module-level jitted attrs: they are built per
    # geometry through the rules.py compile seam.  Contracts abstract-
    # eval each registered kind through every lowering it registers
    # (jit+NamedSharding, shard_map) on a 1-device probe mesh — geometry-independent
    # shape/dtype truth that holds on any host, mirroring how the ops/
    # kernels are checked without an accelerator.

    from minio_tpu.parallel import mesh as pmesh, rules as prules

    probe = pmesh.make_mesh(jax.devices()[:1], stripe=1, shard=1)
    mesh_checked: "set[str]" = set()

    def mesh_ctx(kind):
        kd = prules.kernel_def(kind)
        return ctx(
            kd.build_local or kd.build_global,
            "minio_tpu/parallel/mesh.py",
        )

    def mesh_modes(kind):
        kd = prules.kernel_def(kind)
        modes = []
        if kd.build_global is not None:
            modes.append("jit")
        if kd.build_local is not None:
            modes.append("shard_map")
        return modes

    def mesh_eval(kind, mode, args, statics):
        fn = prules.compile_kernel(kind, probe, force_mode=mode, **statics)
        return fn.eval_shape(*args)

    mesh_checked.add("sharded_encode")
    c = mesh_ctx("sharded_encode")
    for k, m, L in CONFIG_GRID:
        for mode in mesh_modes("sharded_encode"):
            c.config = cfg_str(k, m, L) + f" [{mode}]"
            try:
                out = mesh_eval(
                    "sharded_encode", mode,
                    (S((_BATCH, k, L), u8),), dict(k=k, m=m),
                )
                c.shape(out, (_BATCH, m, L), "mesh parity bytes")
                c.dtype(out, "uint8", "mesh parity bytes")
            except Exception as e:
                c.fail(e)

    mesh_checked.add("sharded_encode_seq")
    c = mesh_ctx("sharded_encode_seq")
    for k, m, L in CONFIG_GRID:
        for mode in mesh_modes("sharded_encode_seq"):
            c.config = cfg_str(k, m, L) + f" [{mode}]"
            try:
                out = mesh_eval(
                    "sharded_encode_seq", mode,
                    (S((k, L), u8),), dict(k=k, m=m),
                )
                c.shape(out, (m, L), "seq parity bytes")
                c.dtype(out, "uint8", "seq parity bytes")
            except Exception as e:
                c.fail(e)

    mesh_checked.add("mesh_encode_hash")
    c = mesh_ctx("mesh_encode_hash")
    for k, m, L in CONFIG_GRID:
        w = L // 4
        for mode in mesh_modes("mesh_encode_hash"):
            c.config = cfg_str(k, m, L) + f" [{mode}]"
            try:
                parity, ddig, pdig = mesh_eval(
                    "mesh_encode_hash", mode,
                    (S((_BATCH, k, w), u32), LENS),
                    dict(k=k, m=m),
                )
                c.shape(parity, (_BATCH, m, w), "mesh parity words")
                c.dtype(parity, "uint32", "mesh parity words")
                c.shape(ddig, (_BATCH, k, 8), "mesh data digests")
                c.dtype(ddig, "uint32", "mesh data digests")
                c.shape(pdig, (_BATCH, m, 8), "mesh parity digests")
                c.dtype(pdig, "uint32", "mesh parity digests")
            except Exception as e:
                c.fail(e)

    mesh_checked.add("mesh_reconstruct")
    c = mesh_ctx("mesh_reconstruct")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        for mode in mesh_modes("mesh_reconstruct"):
            c.config = cfg_str(k, m, L) + f" [{mode}]"
            try:
                out = mesh_eval(
                    "mesh_reconstruct", mode,
                    (S((_BATCH, k, w), u32), S((k, k), u8)),
                    dict(k=k, m=m),
                )
                c.shape(out, (_BATCH, k, w), "mesh recon words")
                c.dtype(out, "uint32", "mesh recon words")
                # MTPU203: mesh encode -> reconstruct round-trip
                parity, _, _ = mesh_eval(
                    "mesh_encode_hash", mesh_modes("mesh_encode_hash")[0],
                    (S((_BATCH, k, w), u32), LENS),
                    dict(k=k, m=m),
                )
                surv = S((_BATCH, parity.shape[1] + (k - m), w), parity.dtype)
                rt = mesh_eval(
                    "mesh_reconstruct", mode,
                    (surv, S((k, k), u8)), dict(k=k, m=m),
                )
                c.expect(
                    "MTPU203",
                    (tuple(rt.shape), str(rt.dtype)),
                    ((_BATCH, k, w), "uint32"),
                    "mesh encode->reconstruct round-trip (words)",
                )
            except Exception as e:
                c.fail(e)

    mesh_checked.add("mesh_digest")
    c = mesh_ctx("mesh_digest")
    for k, m, L in CONFIG_GRID:
        w = L // 4
        for mode in mesh_modes("mesh_digest"):
            c.config = cfg_str(k, m, L) + f" [{mode}]"
            try:
                out = mesh_eval(
                    "mesh_digest", mode,
                    (S((_BATCH, w), u32), LENS), {},
                )
                c.shape(out, (_BATCH, 8), "mesh digests")
                c.dtype(out, "uint32", "mesh digests")
            except Exception as e:
                c.fail(e)

    mesh_checked.add("mesh_verify_reconstruct")
    c = mesh_ctx("mesh_verify_reconstruct")
    for k, m, L in CONFIG_GRID:
        w, n = L // 4, k + m
        for mode in mesh_modes("mesh_verify_reconstruct"):
            c.config = cfg_str(k, m, L) + f" [{mode}]"
            try:
                data, ok = mesh_eval(
                    "mesh_verify_reconstruct", mode,
                    (S((_BATCH, n, w), u32), S((_BATCH, n, 8), u32), LENS)
                    + pattern(k, n),
                    dict(k=k, m=m),
                )
                c.shape(data, (_BATCH, k, w), "mesh fused GET data words")
                c.dtype(data, "uint32", "mesh fused GET data words")
                c.shape(ok, (_BATCH, n), "mesh fused GET ok mask")
                c.dtype(ok, "bool", "mesh fused GET ok mask")
            except Exception as e:
                c.fail(e)

    # seam-registry closure: a kernel registered with the compile seam
    # but missing a contract block above fails MTPU204 the same way a
    # new module-level jitted entry point does
    for kind in prules.registered_kernels():
        if kind not in mesh_checked:
            kd = prules.kernel_def(kind)
            path, line = _anchor(
                kd.build_local or kd.build_global,
                "minio_tpu/parallel/mesh.py",
            )
            findings.append(
                Finding(
                    "MTPU204",
                    path,
                    line,
                    f"mesh kernel {kind!r} registered with the compile "
                    "seam has no contract check; add one in "
                    "minio_tpu/analysis/kernel_contracts.py",
                )
            )

    # ---- coverage closure (MTPU204) -------------------------------------

    for (mod, name), fn in jit_entry_points().items():
        if (mod, name) not in checked:
            path, line = _anchor(fn, f"minio_tpu/ops/{mod}.py")
            findings.append(
                Finding(
                    "MTPU204",
                    path,
                    line,
                    f"jitted entry point {mod}.{name} has no registered "
                    "kernel contract; add a check in "
                    "minio_tpu/analysis/kernel_contracts.py",
                )
            )

    return findings


def covered_entry_points() -> "set[tuple[str, str]]":
    """The (module, name) pairs the contract run exercises.

    Derived by running the checker against the live registry: everything
    introspection finds minus whatever MTPU204 flags.
    """
    flagged = {
        f.message.split(" ")[3] for f in run() if f.rule == "MTPU204"
    }
    return {
        key
        for key in jit_entry_points()
        if f"{key[0]}.{key[1]}" not in flagged
    }
