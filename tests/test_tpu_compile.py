"""Ahead-of-time TPU compile gate: no kernel reaches the chip uncompilable.

The sandbox has no accelerator, but the installed libtpu can describe a
v5e topology and run the real XLA:TPU + Mosaic compilers against it:

    topo = jax.experimental.topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu")
    jax.jit(f).lower(ShapeDtypeStruct(..., sharding=SingleDeviceSharding(
        topo.devices[0]))).compile()

This module compiles, that way, every program the codec seam
(``codec/backend.py``) can launch on a TPU, at EC 4+2 / 8+4 / 16+4 with
full 10 MiB blockSizeV1 blocks: the PUT pass at the batch sizes a flush
makes, the digest pass at every rung of the seam's ladder, the
reconstruct pass at every stripe count a launch takes, heal's two
passes; then the ragged width of EC 12+4 and a 4 KiB object, and the
mesh kernels on the four topology devices at B = 1, 4, 8.  The leading
dimensions come from the seam's own ``*_rungs``, so the list
cannot drift from the seam.  A program Mosaic refuses fails here, on the
CPU, before anyone spends chip time on it.

The compiles run in a child process (``python tests/test_tpu_compile.py``
prints one JSON line per case): trace-time dispatch asks
``rs.lowering_for_tpu()``, which the child replaces with ``True``, and a
process of its own keeps that - and libtpu's logging - out of the suite.
Skips only if the topology cannot be built.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":  # the child: before the seam is imported
    sys.path.insert(0, os.path.dirname(HERE))
    os.environ["JAX_PLATFORMS"] = "cpu"

from minio_tpu.codec import backend  # noqa: E402
from minio_tpu.codec.erasure import Erasure  # noqa: E402

BLOCK = 10 * 1024 * 1024
GRID = ((4, 2), (8, 4), (16, 4))


def _cases() -> "list[tuple[str, str, dict]]":
    """(name, kind, params) for every compile; names are the test ids,
    and a single-device kind is the jitted entry point it compiles."""
    out: "list[tuple[str, str, dict]]" = []

    def add(kind, k, m, B, block=BLOCK):
        tag = "" if block == BLOCK else f"-{block}B"
        out.append(
            (f"{kind}-ec{k}+{m}-B{B}{tag}", kind,
             dict(k=k, m=m, B=B, block=block))
        )

    for k, m in GRID:
        L = Erasure(k, m).shard_size_padded(BLOCK)
        # PUT: however many blocks a flush or a stream's batch holds,
        # the seam launches an encode at one of its few rungs
        for B in backend.encode_rungs(k * backend.width_rung(L)):
            add("encode_words_fused1", k, m, B)
        # healthy read: the rows of a flush lie flat, (1, rows, w)
        for rows in backend.digest_rungs(backend.width_rung(L)):
            add("digest_words", k, m, rows)
        # degraded read: stripes of n rows
        for stripes in backend.reconstruct_rungs((k + m) * backend.width_rung(L)):
            add("reconstruct_words_batch", k, m, stripes)
        # heal: verify+reconstruct, then the re-encode
        add("verify_and_reconstruct_words", k, m, 1)
        add("encode_and_hash_words", k, m, 1)
    add("verify_and_reconstruct_words", 8, 4, 8)
    # a 64 MiB object ends in a 4 MiB block: 32-tile shards at EC 8+4,
    # beside the full blocks' 80 in the same stream
    tail = 64 * 1024 * 1024 % BLOCK
    L = Erasure(8, 4).shard_size_padded(tail)
    for B in backend.encode_rungs(8 * backend.width_rung(L)):
        add("encode_words_fused1", 8, 4, B, tail)
    for rows in backend.digest_rungs(backend.width_rung(L)):
        add("digest_words", 8, 4, rows, tail)
    for stripes in backend.reconstruct_rungs(12 * backend.width_rung(L)):
        add("reconstruct_words_batch", 8, 4, stripes, tail)
    # ragged widths are staged at a rung of the width ladder and take
    # the Pallas kernels with their length an operand: EC 12+4's 10 MiB
    # block (54 tiles -> 56), a 4 KiB object at EC 8+4 (512-byte shards
    # -> one tile), and objects of 390 KB (3 tiles) and 2.2 MB (17 -> 20)
    for k, m, block in ((12, 4, BLOCK), (8, 4, 4096), (8, 4, 389679),
                        (8, 4, 2204359)):
        add("encode_words_fused1", k, m, 1, block)
        add("verify_and_reconstruct_words", k, m, 1, block)
        add("digest_words", k, m, backend.ladder(k), block)
        add("reconstruct_words_batch", k, m, 1, block)
    # a four-chip host: stripe axis at B >= 4, shard axis at B = 1
    for B in (1, 4, 8):
        for kind in ("mesh_encode_hash", "mesh_verify_reconstruct",
                     "mesh_reconstruct", "mesh_digest"):
            add(kind, 8, 4, B)
    # MINIO_TPU_SELECT=auto reaches the Select screen on TPU (x64)
    out.append(("select_screen-2MiB", "select_screen", dict(n=2 << 20)))
    return out


CASES = _cases()


# ---------------------------------------------------------------------------
# the child: compile everything, one JSON line per case
# ---------------------------------------------------------------------------


def _child() -> int:
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu"
        )
    except Exception as e:  # noqa: BLE001 - reported, the parent skips
        print(json.dumps({"skip": f"{type(e).__name__}: {e}"[:300]}))
        return 0

    from minio_tpu.ops import codec_step, rs, select_step
    from minio_tpu.parallel import mesh as pm, rules as prules

    rs.lowering_for_tpu = lambda: True  # stand in for the chip
    dev0 = SingleDeviceSharding(topo.devices[0])
    u32 = jnp.uint32

    def S(shape, dtype=u32, sharding=dev0):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def single(kind, p):
        """(function of arrays, abstract args) on one topology device."""
        k, m, B = p["k"], p["m"], p["B"]
        n = k + m
        # as the seam stages it: the width's rung, the lengths abstract
        L = Erasure(k, m).shard_size_padded(p["block"])
        w = backend.width_rung(L) // 4
        use_pallas, interpret = codec_step.pallas_dispatch(w)
        assert use_pallas and not interpret
        lens = S((B,), jnp.int32)
        # the loss pattern's operands: abstract, like the shards - the
        # program cannot depend on which rows survived
        pat = [S((n,), jnp.bool_), S((k,), jnp.int32), S((k, k), jnp.uint8)]
        if kind == "encode_words_fused1":
            return (
                lambda x, ln: codec_step.encode_words_fused1(
                    x, m, ln, use_pallas=use_pallas
                ),
                [S((B, k, w)), lens],
            )
        if kind == "encode_and_hash_words":
            return (
                lambda x, ln: codec_step.encode_and_hash_words(x, m, ln),
                [S((B, k, w)), lens],
            )
        if kind == "digest_words":  # B counts rows here
            return (
                codec_step.digest_words,
                [S((1, B, w)), S((1, B), jnp.int32)],
            )
        if kind == "reconstruct_words_batch":
            return (
                lambda x, sv, mat: codec_step.reconstruct_words_batch(
                    x, sv, mat, k, m, use_pallas=use_pallas
                ),
                [S((B, n, w))] + pat[1:],
            )
        if kind == "verify_and_reconstruct_words":
            return (
                lambda x, d, pr, sv, mat, ln: (
                    codec_step.verify_and_reconstruct_words(
                        x, d, pr, sv, mat, k, m, ln, use_pallas=use_pallas
                    )
                ),
                [S((B, n, w)), S((B, n, 8))] + pat + [lens],
            )
        raise KeyError(kind)

    def mesh_kernel(kind, p):
        """(compiled-seam function, abstract args) on the four devices,
        built exactly as TpuBackend builds it."""
        k, m, B = p["k"], p["m"], p["B"]
        n = k + m
        L = Erasure(k, m).shard_size_padded(BLOCK)
        w = L // 4
        rows = B * k if kind == "mesh_digest" else B
        stripe, shard = pm.pick_axes(
            4, rows, 1 if kind == "mesh_digest" else k
        )
        mesh = pm.make_mesh(list(topo.devices), stripe=stripe, shard=shard)
        bucket = pm._bucket_batch(
            rows, 4 if kind == "mesh_digest" else stripe
        )

        def A(shape, plane, dtype=u32):
            return S(shape, dtype, NamedSharding(mesh, prules.spec_for(plane)))

        if kind == "mesh_encode_hash":
            fn = prules.compile_kernel(kind, mesh, k=k, m=m)
            return fn, [
                A((bucket, k, w), "stripe_words"),
                A((bucket,), "stripe_lengths", jnp.int32),
            ]
        if kind == "mesh_reconstruct":
            fn = prules.compile_kernel(
                kind, mesh, k=k, m=m, use_pallas=shard == 1, interpret=False
            )
            return fn, [
                A((bucket, k, w), "survivor_words"),
                A((k, k), "decode_matrix", jnp.uint8),
            ]
        if kind == "mesh_verify_reconstruct":
            fn = prules.compile_kernel(
                kind, mesh, k=k, m=m, use_pallas=True, interpret=False,
            )
            return fn, [
                A((bucket, n, w), "quorum_words"),
                A((bucket, n, 8), "quorum_digests"),
                A((bucket,), "stripe_lengths", jnp.int32),
                A((n,), "decode_present", jnp.bool_),
                A((k,), "decode_survivors", jnp.int32),
                A((k, k), "decode_matrix", jnp.uint8),
            ]
        if kind == "mesh_digest":
            fn = prules.compile_kernel(kind, mesh)
            return fn, [
                A((bucket, w), "digest_rows"),
                A((bucket,), "digest_lengths", jnp.int32),
            ]
        raise KeyError(kind)

    def run(case):
        name, kind, p = case
        t0 = time.monotonic()
        doc = {"name": name}
        try:
            if kind == "select_screen":
                with jax.enable_x64():
                    compiled = jax.jit(
                        lambda a: select_step.screen_chunk(
                            a, fd=44, qc=34,
                            atoms=((("nd", 5),), (("lex", b"99999", "ge"),)),
                            anchor="row", sci_guard=True,
                        )
                    ).lower(S((p["n"],), jnp.uint8)).compile()
            else:
                fn, args = (
                    mesh_kernel(kind, p)
                    if kind.startswith("mesh_")
                    else single(kind, p)
                )
                jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
                compiled = jitted.lower(*args).compile()
            doc["ok"] = True
            doc["temp_bytes"] = int(
                compiled.memory_analysis().temp_size_in_bytes
            )
        except Exception as e:  # noqa: BLE001 - the verdict IS the output
            doc["ok"] = False
            doc["error"] = f"{type(e).__name__}: {e}"[:600]
        doc["seconds"] = round(time.monotonic() - t0, 2)
        return doc

    # XLA and Mosaic compile outside the GIL: a few threads cut the wall
    # time of the ~75 compiles several-fold; the one x64 case runs alone
    # (enable_x64 is thread-local, but keep the tracing context simple)
    plain = [c for c in CASES if c[1] != "select_screen"]
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        for doc in ex.map(run, plain):
            print(json.dumps(doc), flush=True)
    for case in CASES:
        if case[1] == "select_screen":
            print(json.dumps(run(case)), flush=True)
    return 0


# ---------------------------------------------------------------------------
# the suite side
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def verdicts():
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=840,
    )
    docs = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    if docs and "skip" in docs[0]:
        pytest.skip(f"cannot build the v5e:2x2 topology: {docs[0]['skip']}")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return {d["name"]: d for d in docs}


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_compiles_for_v5e(verdicts, name):
    doc = verdicts.get(name)
    assert doc is not None, f"the compile child never reported {name}"
    assert doc["ok"], f"{name} does not compile for v5e: {doc['error']}"


def test_selector_values_are_all_covered():
    """Every jitted entry point and mesh kernel the seam can reach has
    a compile case, and no knob selects among them: what runs is picked
    from the platform and the width (codec_step.pallas_dispatch)."""
    import ast

    from minio_tpu.analysis.kernel_contracts import KNOWN_ENTRY_POINTS
    from minio_tpu.config.knobs import KNOBS

    jitted = {name for mod, name in KNOWN_ENTRY_POINTS if mod == "codec_step"}
    with open(os.path.join(HERE, "..", "minio_tpu", "codec", "backend.py")) as f:
        tree = ast.parse(f.read())
    reached = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)):
            continue
        if node.value.id == "codec_step" and node.attr in jitted:
            reached.add(node.attr)
        elif node.value.id == "pm" and node.attr.startswith("mesh_"):
            reached.add(node.attr.removesuffix("_begin").removesuffix("_end"))
    assert {"encode_words_fused1", "digest_words", "mesh_digest"} <= reached
    kinds = {c[1] for c in CASES}
    assert reached <= kinds, f"no compile case for {sorted(reached - kinds)}"
    assert {k for k in KNOBS if k.startswith("MINIO_TPU_CODEC_")} == {
        "MINIO_TPU_CODEC_INTERPRET"
    }


if __name__ == "__main__":
    sys.exit(_child())
