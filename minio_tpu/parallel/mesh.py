"""Device-mesh parallelism for the erasure data plane.

The reference scales by fanning shard I/O across disks/nodes with
goroutines + REST (SURVEY.md section 2.4 "parallelism strategies").  The
TPU-native analogue maps those strategies onto a jax.sharding.Mesh:

* axis "stripe" (data-parallel analogue of erasure *sets*,
  cmd/erasure-sets.go:543-580): independent stripes of a batch are placed on
  different devices; no collectives.
* axis "seq" (sequence-parallel analogue of the 10 MiB block streaming,
  cmd/object-api-common.go:31): the byte stream of one object is sharded
  along its length; RS is column-local so each device encodes its slice
  independently - unbounded object size with a fixed per-device working set.
* axis "shard" (tensor-parallel analogue of the per-disk shard fan-out in
  cmd/erasure-encode.go:39-54): the k data shards are sharded across
  devices; each device computes a partial parity (XOR of its terms) and
  partials are combined with a recursive-doubling XOR all-reduce over ICI.

Shardings are not written here: every entry point declares its operand
planes by name and `parallel.rules` resolves them (PARTITION_RULES) and
picks the lowering behind one compile cache keyed on device ids rather
than Mesh identity.  Kernels whose per-device body can lower to a Pallas
call (encode+hash, reconstruct, verify+reconstruct) register a
shard_map body only: Mosaic kernels cannot be partitioned by XLA, so
jit+NamedSharding is left to the pure-XLA kernels (digest, the demo
encodes).

All entry points work under jit/shard_map with static shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf, rs
from . import rules


def make_mesh(
    devices: "list[jax.Device] | None" = None,
    stripe: int | None = None,
    shard: int | None = None,
) -> Mesh:
    """Build a ("stripe", "shard") mesh over the available devices.

    Defaults to putting all devices on the stripe axis (pure
    set-parallelism) since XOR all-reduce traffic is then zero, mirroring
    the reference's default of independent sets per object.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if stripe is None and shard is None:
        stripe, shard = n, 1
    elif stripe is None:
        stripe = n // shard
    elif shard is None:
        shard = n // stripe
    if stripe * shard != n:
        raise ValueError(f"mesh {stripe}x{shard} != {n} devices")
    arr = np.asarray(devices).reshape(stripe, shard)
    return Mesh(arr, ("stripe", "shard"))


def xor_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce with XOR over a mesh axis via recursive doubling.

    GF(2^8) addition is XOR, which psum cannot express; this is the
    collective backing shard-parallel parity accumulation.  Rides ICI as
    log2(n) ppermute steps (falls back to all-gather+fold for non powers
    of two).
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1) == 0:
        idx = jax.lax.axis_index(axis_name)
        step = 1
        while step < n:
            # partner = idx XOR step; ppermute perm maps src->dst
            perm = [(int(i), int(i ^ step)) for i in range(n)]
            other = jax.lax.ppermute(x, axis_name, perm)
            x = x ^ other
            step <<= 1
        return x
    gathered = jax.lax.all_gather(x, axis_name)  # (n, ...)
    return jax.lax.reduce(
        gathered, x.dtype.type(0), jax.lax.bitwise_xor, (0,)
    )


def _partial_parity(
    local_data_words: jax.Array, matrix_cols: np.ndarray
) -> jax.Array:
    """Partial parity for a device's slice of data shards (static matrix)."""
    return rs._encode_words(local_data_words, matrix_cols)


def _col_blocks(matrix: np.ndarray, shard_n: int) -> np.ndarray:
    """Split a generator/reconstruction matrix into per-shard-device columns."""
    k = matrix.shape[1]
    k_local = k // shard_n
    return np.stack(
        [matrix[:, s * k_local : (s + 1) * k_local] for s in range(shard_n)]
    )  # (shard_n, rows, k_local) - static stack, dynamic row pick


def put_sharded(mesh: Mesh, x: np.ndarray, spec: P) -> jax.Array:
    """Place a host array onto the mesh with the given partition spec."""
    return jax.device_put(x, NamedSharding(mesh, spec))


def _pad_batch(arr: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad the leading axis to ``rows`` with a single allocation.

    (np.concatenate would reallocate AND copy the batch through a
    temporary; here the only traffic is one memcpy into fresh zeros, and
    the unpadded case returns the input untouched.)
    """
    if arr.shape[0] == rows:
        return arr
    out = np.zeros((rows,) + arr.shape[1:], dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


# ---------------------------------------------------------------------------
# Kernel bodies, registered with the rules.py compile seam
# ---------------------------------------------------------------------------
#
# Each kernel kind has up to two builders: `build_local` (per-device body
# for shard_map; may use the XOR all-reduce over "shard", may call a
# Pallas kernel) and `build_global` (whole-array program for
# jit+NamedSharding; XLA partitions it, valid only for pure-XLA bodies
# that need no hand-rolled collective).


def _encode_local(mesh: Mesh, k: int, m: int):
    shard_n = mesh.shape["shard"]
    col_blocks = _col_blocks(gf.parity_matrix(k, m), shard_n)

    def step(local: jax.Array) -> jax.Array:
        # local: (B_local, k_local, length) uint8
        idx = jax.lax.axis_index("shard")
        words = rs.bytes_to_words(local)
        my_cols = jnp.asarray(col_blocks)[idx]
        partial = jax.vmap(
            lambda w: rs._matmul_words_dynamic(w, my_cols)
        )(words)
        total = xor_allreduce(partial, "shard")
        return rs.words_to_bytes(total)

    return step


def _encode_global(mesh: Mesh, k: int, m: int):
    matrix = gf.parity_matrix(k, m)

    def step(data: jax.Array) -> jax.Array:
        # data: (B, k, length) uint8
        words = rs.bytes_to_words(data)
        parity = jax.vmap(lambda w: rs._encode_words(w, matrix))(words)
        return rs.words_to_bytes(parity)

    return step


def _encode_seq_global(mesh: Mesh, k: int, m: int):
    matrix = gf.parity_matrix(k, m)

    def step(data: jax.Array) -> jax.Array:
        # data: (k, length) uint8, length sharded; RS is column-local
        words = rs.bytes_to_words(data)
        return rs.words_to_bytes(rs._encode_words(words, matrix))

    return step


def _encode_hash_local(mesh: Mesh, k: int, m: int):
    from ..ops import codec_step, hash as phash

    shard_n = mesh.shape["shard"]
    if shard_n == 1:

        def whole(local: jax.Array, lengths: jax.Array):
            # whole stripes are device-local on a stripe-only mesh: run
            # the fused single-device step (static matrix -> the Pallas
            # kernel on TPU) instead of the dynamic bit-walk.  lengths:
            # (B_local,) the stripes' true shard bytes, the same traced
            # operand the one-chip seam passes
            parity, digests = codec_step.encode_and_hash_words(
                local, m, lengths
            )
            return parity, digests[:, :k], digests[:, k:]

        return whole

    col_blocks = _col_blocks(gf.parity_matrix(k, m), shard_n)

    def step(local: jax.Array, lengths: jax.Array):
        # local: (B_local, k_local, w); lengths: (B_local,)
        idx = jax.lax.axis_index("shard")
        my_cols = jnp.asarray(col_blocks)[idx]
        partial = jax.vmap(
            lambda wds: rs._matmul_words_dynamic(wds, my_cols)
        )(local)
        parity = xor_allreduce(partial, "shard")  # (B_local, m, w)
        rows = lengths[:, None]
        ddig = phash.phash256_words_batched(
            local, jnp.broadcast_to(rows, local.shape[:2])
        )
        pdig = phash.phash256_words_batched(
            parity, jnp.broadcast_to(rows, parity.shape[:2])
        )
        return parity, ddig, pdig

    return step


def _reconstruct_local(
    mesh: Mesh, k: int, m: int, use_pallas: bool = False,
    interpret: bool = False,
):
    shard_n = mesh.shape["shard"]
    if shard_n == 1:
        from ..ops import codec_step

        def whole(local: jax.Array, matrix: jax.Array):
            # local: (B_local, k, w) compacted survivor rows, whole
            # stripes per device; matrix: (k, k) traced, survivors ->
            # data.  The runtime-matrix Pallas kernel on a TPU and a
            # tile-aligned width, else the XLA bit-walk
            return codec_step.matmul_rows(
                local, matrix, use_pallas, interpret
            )

        return whole

    k_local = k // shard_n

    def step(local: jax.Array, matrix: jax.Array):
        # local: (B_local, k_local, w) compacted survivor rows; this
        # device's columns of the traced matrix
        dev = jax.lax.axis_index("shard")
        my_cols = jax.lax.dynamic_slice_in_dim(
            matrix, dev * k_local, k_local, axis=1
        )
        partial = jax.vmap(
            lambda wds: rs._matmul_words_dynamic(wds, my_cols)
        )(local)
        return xor_allreduce(partial, "shard")

    return step


def _digest_global(mesh: Mesh):
    from ..ops import hash as phash

    def step(rows: jax.Array, lengths: jax.Array):
        # rows: (R, w) flattened shard rows at their staged width,
        # lengths: (R,) their true bytes; embarrassingly parallel
        return phash.phash256_words_batched(rows, lengths)

    return step


def _verify_reconstruct_local(
    mesh: Mesh,
    k: int,
    m: int,
    use_pallas: bool = False,
    interpret: bool = False,
):
    from ..ops import codec_step

    def step(words, digests, lengths, present, survivors, matrix):
        # words: (B_local, n, w) quorum rows; whole stripes are
        # device-local on the stripe axis (and replicated over "shard"),
        # so the fused GET step runs per device with no collective.
        # The pattern's three operands are whole on every device
        return codec_step.verify_and_reconstruct_words(
            words,
            digests,
            present,
            survivors,
            matrix,
            k,
            m,
            lengths,
            use_pallas=use_pallas,
            interpret=interpret,
        )

    return step


rules.register_kernel(
    "sharded_encode",
    in_names=("stripe_bytes",),
    out_names=("parity_bytes",),
    build_local=_encode_local,
    build_global=_encode_global,
)
rules.register_kernel(
    "sharded_encode_seq",
    in_names=("seq_bytes",),
    out_names=("seq_parity",),
    build_global=_encode_seq_global,
)
rules.register_kernel(
    "mesh_encode_hash",
    in_names=("stripe_words", "stripe_lengths"),
    out_names=("parity_words", "data_digests", "parity_digests"),
    build_local=_encode_hash_local,
    # the data-words buffer is a fresh device_put per batch; donating it
    # lets XLA alias it into the parity output instead of copying
    donate_argnums=(0,),
)
rules.register_kernel(
    "mesh_reconstruct",
    in_names=("survivor_words", "decode_matrix"),
    out_names=("recon_words",),
    build_local=_reconstruct_local,
)
rules.register_kernel(
    "mesh_digest",
    in_names=("digest_rows", "digest_lengths"),
    out_names=("digest_out",),
    build_global=_digest_global,
)
rules.register_kernel(
    "mesh_verify_reconstruct",
    in_names=(
        "quorum_words", "quorum_digests", "stripe_lengths",
        "decode_present", "decode_survivors", "decode_matrix",
    ),
    out_names=("recon_words", "ok_mask"),
    build_local=_verify_reconstruct_local,
)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def sharded_encode(
    mesh: Mesh, data: jax.Array, parity_shards: int
) -> jax.Array:
    """Encode a batch of stripes across the mesh.

    data: (batch, k, length) uint8, batch sharded over "stripe", the k data
    shards sharded over "shard".  Returns (batch, m, length) parity
    replicated over "shard" (each shard-group device holds the full parity,
    like every disk holding its own shard after the fan-out write).
    """
    _, k, _ = data.shape
    shard_n = mesh.shape["shard"]
    if k % shard_n:
        raise ValueError(f"k={k} not divisible by shard axis {shard_n}")
    fn = rules.compile_kernel(
        "sharded_encode", mesh, k=k, m=parity_shards
    )
    return fn(data)


def sharded_encode_seq(mesh: Mesh, data: jax.Array, parity_shards: int) -> jax.Array:
    """Sequence-parallel encode: one long object sharded along its length.

    data: (k, length) with length sharded over every mesh device (both
    axes flattened); RS columns are independent so there is no collective -
    this is the long-context scaling path (SURVEY.md section 5
    "long-context / sequence parallelism").
    """
    k, _ = data.shape
    fn = rules.compile_kernel(
        "sharded_encode_seq", mesh, k=k, m=parity_shards
    )
    return fn(data)


# ---------------------------------------------------------------------------
# Production mesh paths (the backend seam's multi-device implementation)
# ---------------------------------------------------------------------------
#
# These are what codec.backend.TpuBackend dispatches to when more than one
# device is visible: the "stripe" axis carries independent stripes (the
# erasure-sets data-parallel analogue) and the "shard" axis splits the k
# data shards of each stripe (the per-disk fan-out analogue,
# cmd/erasure-encode.go:39-54) with partial parities combined by the XOR
# all-reduce over ICI.


def pick_axes(n_devices: int, batch: int, data_shards: int) -> tuple[int, int]:
    """Choose (stripe, shard) axis sizes for a batch of stripes.

    Minimize rounds of work (ceil(batch/stripe)), then maximize device
    utilization, then prefer the smaller shard axis (less collective
    traffic).  Large batches therefore get pure stripe parallelism; small
    batches of wide stripes soak leftover devices on the shard axis.
    """
    best_key, best = None, (n_devices, 1)
    for shard in range(1, n_devices + 1):
        if n_devices % shard or data_shards % shard:
            continue
        stripe = n_devices // shard
        rounds = -(-batch // stripe)
        util = min(batch, stripe) * shard
        key = (rounds, -util, shard)
        if best_key is None or key < best_key:
            best_key, best = key, (stripe, shard)
    return best


def _bucket_batch(batch: int, stripe: int) -> int:
    """Pad batch to stripe * next_pow2(rounds): bounds jit cache entries to
    O(log B) per geometry while wasting <2x compute on odd sizes."""
    rounds = -(-batch // stripe)
    p = 1
    while p < rounds:
        p <<= 1
    return stripe * p


def _lengths(lengths, rows: int, words_per_row: int) -> np.ndarray:
    """The host side of the length operand: int32[rows], a scalar (or
    None: the rows' full width) standing for every row."""
    if lengths is None:
        lengths = 4 * words_per_row
    return np.ascontiguousarray(
        np.broadcast_to(np.asarray(lengths, dtype=np.int32), (rows,))
    )


def mesh_encode_hash(
    mesh: Mesh, words: np.ndarray, parity_shards: int, lengths=None
):
    """Mesh-parallel fused encode+digest over a batch of stripes.

    words: (B, k, w) uint32 host array at the staged width; lengths:
    int32[B] (or one int for all, or None: 4 * w), the stripes' true
    shard bytes.  Returns (parity (B, m, w),
    digests (B, k+m, 8)) as numpy, digest rows in data-then-parity order
    (the contract of ops.codec_step.encode_and_hash_words).
    """
    return mesh_encode_hash_end(
        mesh_encode_hash_begin(mesh, words, parity_shards, lengths)
    )


def mesh_encode_hash_begin(
    mesh: Mesh, words: np.ndarray, parity_shards: int, lengths=None
):
    """Dispatch the mesh encode+digest WITHOUT synchronizing.

    jax dispatch is async for shard_map exactly as for plain jit: the
    returned tuple holds device-array futures plus the unpadded batch
    size.  ``mesh_encode_hash_end`` materializes them, so the erasure
    layer's double-buffered pipeline (encode_begin/encode_end) overlaps
    this batch's mesh pass with the previous batch's disk writes on the
    mesh path too, not just the single-device one.

    The device copy of ``words`` is donated to the kernel (the host
    array is untouched; only the fresh on-device buffer is recycled).
    """
    B, k, w = words.shape
    stripe = mesh.shape["stripe"]
    rows = _bucket_batch(B, stripe)
    lens = _pad_batch(_lengths(lengths, B, w), rows)
    words = _pad_batch(words, rows)
    fn = rules.compile_kernel("mesh_encode_hash", mesh, k=k, m=parity_shards)
    dd = put_sharded(mesh, words, rules.spec_for("stripe_words"))
    dl = put_sharded(mesh, lens, rules.spec_for("stripe_lengths"))
    parity, ddig, pdig = fn(dd, dl)
    return parity, ddig, pdig, B


def mesh_encode_hash_end(handle):
    """Materialize a ``mesh_encode_hash_begin`` handle (the sync point)."""
    parity, ddig, pdig, B = handle
    digests = np.concatenate(
        [np.asarray(ddig)[:B], np.asarray(pdig)[:B]], axis=1
    )
    return np.asarray(parity)[:B], digests


def mesh_reconstruct(
    mesh: Mesh,
    words: np.ndarray,
    survivors: np.ndarray,
    matrix: np.ndarray,
    data_shards: int,
    parity_shards: int,
    use_pallas: bool = False,
    interpret: bool = False,
) -> np.ndarray:
    """Mesh-parallel batched reconstruct: (B, n, w) + pattern -> (B, k, w).

    ``survivors`` (int32[k]) and ``matrix`` (uint8[k, k]) are the
    pattern's operands (codec.backend.decode_plan).  Survivor rows are
    compacted host-side (free fancy-index view) so the device program is
    one partial-matmul + XOR all-reduce per device, with the matrix an
    operand: one program per geometry, whatever the pattern.
    """
    k, m = data_shards, parity_shards
    if len(survivors) != k:
        raise ValueError(f"need {k} shards, have {len(survivors)}")
    surv = np.ascontiguousarray(words[:, np.asarray(survivors), :])
    B = surv.shape[0]
    stripe = mesh.shape["stripe"]
    surv = _pad_batch(surv, _bucket_batch(B, stripe))
    fn = rules.compile_kernel(
        "mesh_reconstruct", mesh, k=k, m=m,
        use_pallas=use_pallas, interpret=interpret,
    )
    dd = put_sharded(mesh, surv, rules.spec_for("survivor_words"))
    return np.asarray(fn(dd, np.asarray(matrix, dtype=np.uint8)))[:B]


def mesh_verify_reconstruct(
    mesh: Mesh,
    words: np.ndarray,
    digests: np.ndarray,
    present: np.ndarray,
    survivors: np.ndarray,
    matrix: np.ndarray,
    data_shards: int,
    parity_shards: int,
    lengths=None,
    use_pallas: bool = False,
    interpret: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Mesh-parallel fused GET step: verify digests + reconstruct, one program.

    words: (B, n, w) quorum rows, digests: (B, n, 8) expected phash256,
    lengths: int32[B] the stripes' true shard bytes (one int for all;
    None: 4 * w) - all sharded over "stripe"; present (bool[n]), survivors (int32[k])
    and matrix (uint8[k, k]) are the pattern's operands, whole on every
    device.  Returns ((B, k, w) data, (B, n) ok mask).
    Padded stripes hash to garbage and come back ok=False; the [:B] slice
    drops them before anyone looks.  ``use_pallas``/``interpret`` are
    codec_step.pallas_dispatch's statics, threaded to the per-device
    body.
    """
    k, m = data_shards, parity_shards
    B = words.shape[0]
    stripe = mesh.shape["stripe"]
    rows = _bucket_batch(B, stripe)
    lens = _pad_batch(_lengths(lengths, B, words.shape[-1]), rows)
    words = _pad_batch(words, rows)
    digests = _pad_batch(digests, rows)
    fn = rules.compile_kernel(
        "mesh_verify_reconstruct",
        mesh,
        k=k,
        m=m,
        use_pallas=use_pallas,
        interpret=interpret,
    )
    dw = put_sharded(mesh, words, rules.spec_for("quorum_words"))
    dg = put_sharded(mesh, digests, rules.spec_for("quorum_digests"))
    data, ok = fn(
        dw,
        dg,
        put_sharded(mesh, lens, rules.spec_for("stripe_lengths")),
        np.asarray(present, dtype=bool),
        np.asarray(survivors, dtype=np.int32),
        np.asarray(matrix, dtype=np.uint8),
    )
    return np.asarray(data)[:B], np.asarray(ok)[:B]


def mesh_digest(mesh: Mesh, words: np.ndarray, lengths=None) -> np.ndarray:
    """Mesh-parallel phash256: (R, w) uint32 rows -> (R, 8) digests.

    Rows (any flattened batch of shards, at their staged width; lengths:
    int32[R] their true bytes, one int for all, None: 4 * w) are spread
    over every device on both axes - digesting is embarrassingly
    parallel.
    """
    R, w = words.shape
    n_dev = mesh.devices.size
    rows = _bucket_batch(R, n_dev)
    lens = _pad_batch(_lengths(lengths, R, w), rows)
    words = _pad_batch(words, rows)
    fn = rules.compile_kernel("mesh_digest", mesh)
    dd = put_sharded(mesh, words, rules.spec_for("digest_rows"))
    dl = put_sharded(mesh, lens, rules.spec_for("digest_lengths"))
    return np.asarray(fn(dd, dl))[:R]
