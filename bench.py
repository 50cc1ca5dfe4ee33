"""North-star benchmark: erasure encode+reconstruct GiB/s per chip.

Headline config from BASELINE.json: EC 8+4 (12-drive set geometry), 1 MiB
blocks.  Each block is split into 8 data shards of 128 KiB (ShardSize
semantics of cmd/erasure-coding.go:115-117); a batch of blocks is
encoded+hashed in one fused device pass, then reconstructed with 4 shards
lost (the worst-case degraded read of cmd/erasure-decode.go).  A config
grid mirroring the reference's benchmark matrix
(cmd/erasure-encode_test.go:209-248: EC 4+2 / 8+4 / 16+4) plus the
healthy-read verify pass is reported in `detail.grid`.

Throughput accounting matches the reference benchmarks
(cmd/erasure-encode_test.go b.SetBytes(totalsize)): GiB/s of object data
through the codec.  The combined metric is data processed twice (encode
once, reconstruct once) over the sum of both times.

Timing methodology: host dispatch and readback latency jitter by
milliseconds, so naive wall-timing of one launch is noise-dominated
for millisecond kernels.  This harness times CHAINED device programs (a
dynamic-trip-count fori_loop of dependent passes, one compile) at two
chain lengths and takes the marginal time per pass; the long chain is
grown adaptively until the measured delta exceeds 8x the observed
short-chain jitter, and the median over paired trials is reported with
min/max spread so an untrustworthy run is visible in the JSON itself.

vs_baseline = TPU throughput / native AVX2 CPU throughput on this host
(native/csrc/gf_cpu.cc - the same nibble-shuffle algorithm as the
reference's klauspost/reedsolomon AVX2 assembly, single-threaded like the
reference's Go benchmark harness).  North star: >= 8x.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Before trusting a number from an edited tree, run the fast analyzer
loop over just your diff: `python -m minio_tpu.analysis --changed-only`
(MTPU404/405 catch exactly the ctypes buffer bugs that corrupt a
benchmark silently instead of crashing it).
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np

EC_K, EC_M = 8, 4  # headline config
BLOCK = 1 << 20  # 1 MiB object block
BATCH = 64  # blocks per device pass (64 MiB of data per step)
GRID = [(4, 2), (8, 4), (16, 4)]  # cmd/erasure-encode_test.go:209-248


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _marginal_time(run, r1=2, max_extra=4096, trials=5) -> tuple[float, dict]:
    """Median per-pass device seconds via adaptive chain differencing.

    run(r) executes r dependent passes in ONE device program (dynamic
    trip count - no recompile between lengths) and blocks on a tiny
    readback.  The long length r2 grows until the runtime delta clears
    the launch jitter by 8x, then the marginal time is the median of
    paired (run(r2) - run(r1)) / (r2 - r1) estimates.
    """
    run(r1)  # compile + warm
    t1s = [_timed(lambda: run(r1)) for _ in range(5)]
    base = statistics.median(t1s)
    jitter = max(t1s) - min(t1s)
    extra = 32
    while True:
        d = statistics.median(
            [_timed(lambda: run(r1 + extra)) for _ in range(3)]
        ) - base
        if d > max(8 * jitter, 0.2) or extra >= max_extra:
            break
        extra = min(extra * 4, max_extra)
    r2 = r1 + extra
    ests = []
    for _ in range(trials):
        ta = _timed(lambda: run(r1))
        tb = _timed(lambda: run(r2))
        ests.append((tb - ta) / (r2 - r1))
    pos = [e for e in ests if e > 0]
    # inf = "noise won even at the max chain": throughput reports as 0
    # and median_s/rel_spread as null, keeping the JSON line valid
    med = statistics.median(pos) if pos else float("inf")
    stats = {
        "per_pass_s": [round(e, 9) for e in ests],
        "median_s": round(med, 9) if pos else None,
        "rel_spread": (
            round((max(pos) - min(pos)) / med, 3) if pos else None
        ),
        "chain": [r1, r2],
        "short_chain_jitter_s": round(jitter, 6),
    }
    return med, stats


def _bench_config(k: int, m: int, trials=5) -> dict:
    """Encode, degraded reconstruct, and healthy verify at EC k+m."""
    import jax.numpy as jnp

    from minio_tpu.ops import codec_step

    shard_len = BLOCK // k
    rng = np.random.default_rng(0)
    words = jnp.asarray(
        rng.integers(0, 2**32, (BATCH, k, shard_len // 4), dtype=np.uint32)
    )
    gib = BATCH * BLOCK / 2**30

    def run_enc(r):
        out = codec_step.encode_throughput_probe(words, m, shard_len, r)
        np.asarray(out[1])

    t_enc, enc_stats = _marginal_time(run_enc, trials=trials)

    parity, digests = codec_step.encode_and_hash_words(words, m, shard_len)
    shards = jnp.concatenate([words, parity], axis=1)
    # worst-case degraded read: lose m shards (m-1 data + 1 parity)
    assert m >= 2, "grid configs need >=2 parity shards"
    present = np.ones(k + m, dtype=bool)
    present[list(range(m - 1)) + [k + 1]] = False
    survivors, matrix = codec_step.host_pattern(present, k, m)

    def run_rec(r):
        out = codec_step.reconstruct_throughput_probe(
            shards, survivors, matrix, k, m, r
        )
        np.asarray(out[1])

    t_rec, rec_stats = _marginal_time(run_rec, trials=trials)

    def run_ver(r):
        out = codec_step.verify_throughput_probe(
            shards, digests, shard_len, r
        )
        np.asarray(out[1])

    t_ver, ver_stats = _marginal_time(run_ver, trials=trials)

    return {
        "ec": f"{k}+{m}",
        "encode_gibps": gib / t_enc,
        "reconstruct_degraded_gibps": gib / t_rec,
        "verify_healthy_gibps": gib / t_ver,
        "combined_gibps": 2 * gib / (t_enc + t_rec),
        "stats": {
            "encode": enc_stats,
            "reconstruct": rec_stats,
            "verify": ver_stats,
        },
    }


def bench_cpu_baseline() -> dict:
    """Pinned CPU denominator (VERDICT r3 weak #3): median of 5 batches
    with the spread reported, single thread, so the multiplier cannot
    move between rounds for reasons unrelated to the code."""
    import os

    from minio_tpu.utils import native

    rng = np.random.default_rng(0)
    # Single block at a time, single thread - mirrors the reference's
    # BenchmarkErasureEncode loop shape.
    shard_len = BLOCK // EC_K
    data = rng.integers(0, 256, (EC_K, shard_len), dtype=np.uint8)
    reps = 50

    def _time(fn):
        fn()
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(reps):
                fn()
            samples.append((time.perf_counter() - t0) / reps)
        med = statistics.median(samples)
        return med, (max(samples) - min(samples)) / med

    parity = native.encode_cpu(data, EC_M)
    t_enc, sp_enc = _time(lambda: native.encode_cpu(data, EC_M))

    shards = np.concatenate([data, parity])
    present = np.ones(EC_K + EC_M, dtype=bool)
    present[[0, 3, 9, 11]] = False

    t_rec, sp_rec = _time(
        lambda: native.reconstruct_cpu(shards, present, EC_K, EC_M)
    )
    gib = BLOCK / 2**30
    return {
        "encode_gibps": gib / t_enc,
        "reconstruct_gibps": gib / t_rec,
        "combined_gibps": 2 * gib / (t_enc + t_rec),
        "rel_spread": round(max(sp_enc, sp_rec), 3),
        "threads": 1,
        "host_cpus": os.cpu_count(),
        "avx2": native.has_avx2(),
    }


def bench_codec_micro() -> dict:
    """Codec microbench (--codec-micro): CPU-native fused-vs-split, the
    round-14 one-kernel device variant sweep, and the round-18
    transfer/compute overlap modes (BENCH_r18 schema).

    Section "native" (round 7, unchanged): one (64, 8, 128 KiB) batch -
    64 MiB of data, EC 8+4 - encoded both ways on the bare CpuBackend.
    "split" is the pre-fusion shape kept callable as ``encode_split``;
    "fused" is the production ``encode``.

    Section "kernel_variants" (round 14): the one-kernel codec
    (MINIO_TPU_CODEC_KERNEL=fused1) against the legacy pass structure,
    kernel-isolated at the codec_step seam, both directions:

    * encode side: legacy three launches (encode+digest, group_flags,
      pack_nonzero_groups) vs ``encode_words_fused1`` - portable XLA
      formulation timed, Pallas interpreter (SWAR and MXU formulations)
      gated for bit-identity but reported without throughput claims
      (the interpreter is a correctness mode, not a fast path);
    * reconstruct side: verify_hashes_words -> reconstruct_words_batch
      vs ``verify_and_reconstruct_words``.

    Every variant is asserted bit-identical against legacy BEFORE any
    timing (hard gate).  Section "pass_accounting" drives the real
    TpuBackend seam per mode and records KERNEL_STATS device_passes +
    per-plane D2H bytes: fused1 PUT must be exactly one launch (legacy
    three) with digest-only eager readback.

    Section "transfer_overlap" (round 18) sweeps
    MINIO_TPU_CODEC_OVERLAP=off|async|pipeline through the same seam:
    every overlapped mode is bit-identity gated against "off" before
    timing, overlapped modes must open overlap windows, and pipeline
    mode must stay at one kernel launch per direction.
    """
    import os

    import jax
    import jax.numpy as jnp

    from minio_tpu.codec import compress
    from minio_tpu.codec.backend import (
        CpuBackend,
        TpuBackend,
        reset_backend,
    )
    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.ops import codec_step, rs_pallas
    from minio_tpu.utils import native

    rng = np.random.default_rng(0)
    B, k, m = 64, EC_K, EC_M
    shard_len = BLOCK // 8  # 128 KiB: multi-tile, cache-unfriendly total
    data = rng.integers(0, 256, (B, k, shard_len), dtype=np.uint8)
    be = CpuBackend()

    par_f, dig_f = be.encode(data, m)
    par_s, dig_s = be.encode_split(data, m)
    assert np.array_equal(par_f, par_s), "fused/split parity mismatch"
    assert np.array_equal(dig_f, dig_s), "fused/split digest mismatch"

    def _time(fn, reps=5):
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            samples.append(time.perf_counter() - t0)
        med = statistics.median(samples)
        return med, (max(samples) - min(samples)) / med

    t_fused, sp_f = _time(lambda: be.encode(data, m))
    t_split, sp_s = _time(lambda: be.encode_split(data, m))
    gib = data.nbytes / 2**30
    native_section = {
        "ec": f"{k}+{m}",
        "batch": B,
        "shard_len": shard_len,
        "data_mib": data.nbytes // 2**20,
        "fused_gibps": round(gib / t_fused, 3),
        "split_gibps": round(gib / t_split, 3),
        "speedup": round(t_split / t_fused, 2),
        "rel_spread": round(max(sp_f, sp_s), 3),
        "native_threads": native.default_threads(),
        "host_cpus": os.cpu_count(),
        "avx2": native.has_avx2(),
    }

    # -- round 14: one-kernel codec variant sweep -----------------------
    # Geometry is Pallas-eligible (w a multiple of rs_pallas._TW) so the
    # interpreter variants run the SAME tile program the TPU would.
    kb, kk, km = 8, EC_K, EC_M
    kL = 4 * rs_pallas._TW  # 16 KiB shards -> w = _TW words
    G = compress.PARITY_GROUP_WORDS
    n = kk + km
    kdata = rng.integers(0, 256, (kb, kk, kL), dtype=np.uint8)
    kdata[1] = 0  # one all-zero stripe: the pack leg must matter
    kwords = codec_step.host_bytes_to_words(kdata)
    kgib = kdata.nbytes / 2**30

    def _block(x):
        return jax.block_until_ready(x)

    def enc_legacy(w_):
        p, d = codec_step.encode_and_hash_words(w_, km, kL)
        f = codec_step.group_flags(p, G)
        f2, pk = codec_step.pack_nonzero_groups(p, G)
        return _block((p, d, f, f2, pk))

    def enc_fused(w_, formulation="swar", pallas=False):
        return _block(
            codec_step.encode_words_fused1(
                w_, km, kL, G, formulation, pallas, pallas
            )
        )

    dw = jnp.asarray(kwords)
    lp, ld, lf, lf2, lpk = enc_legacy(dw)
    enc_out = {"portable": enc_fused(jnp.asarray(kwords))}
    for form in ("swar", "mxu"):
        enc_out[f"interpret_{form}"] = enc_fused(
            jnp.asarray(kwords), form, True
        )
    for name, (p, d, f, pk) in enc_out.items():
        assert np.array_equal(np.asarray(p), np.asarray(lp)), name
        assert np.array_equal(np.asarray(d), np.asarray(ld)), name
        assert np.array_equal(np.asarray(f), np.asarray(lf2)), name
        assert np.array_equal(np.asarray(pk), np.asarray(lpk)), name

    # both sides pay the same fresh H2D per rep: the fused entry donates
    # its input, so a parked buffer cannot be re-fed on real hardware
    t_leg, sp_leg = _time(lambda: enc_legacy(jnp.asarray(kwords)))
    t_f1, sp_f1 = _time(lambda: enc_fused(jnp.asarray(kwords)))

    # reconstruct side: drop m shards, no bitrot (the verify cost is in
    # hashing every present row either way)
    kshards = np.concatenate(
        [kwords, np.asarray(lp)], axis=1
    )
    present = np.asarray((False,) * km + (True,) * (n - km))
    survivors, matrix = codec_step.host_pattern(present, kk, km)
    digs = jnp.asarray(ld)
    dsh = jnp.asarray(kshards)

    def rec_legacy():
        ok = codec_step.verify_hashes_words(dsh, digs, kL)
        dwords = codec_step.reconstruct_words_batch(
            dsh, survivors, matrix, kk, km
        )
        return _block((ok, dwords))

    def rec_fused(formulation="swar", pallas=False):
        return _block(
            codec_step.verify_and_reconstruct_words(
                dsh, digs, present, survivors, matrix, kk, km, kL,
                formulation, pallas, pallas
            )
        )

    lok, ldw = rec_legacy()
    lok = np.asarray(lok) & np.asarray(present)
    rec_out = {"portable": rec_fused()}
    for form in ("swar", "mxu"):
        rec_out[f"interpret_{form}"] = rec_fused(form, True)
    for name, (rdw, rok) in rec_out.items():
        assert np.array_equal(np.asarray(rok), lok), name
        assert np.array_equal(np.asarray(rdw), np.asarray(ldw)), name

    t_rleg, sp_rleg = _time(rec_legacy)
    t_rf1, sp_rf1 = _time(lambda: rec_fused())

    variants = {
        "ec": f"{kk}+{km}",
        "batch": kb,
        "shard_len": kL,
        "data_mib": round(kdata.nbytes / 2**20, 2),
        "group_words": G,
        "bit_identical_all_variants": True,  # asserted above, hard gate
        "encode": {
            "legacy3_gibps": round(kgib / t_leg, 3),
            "fused1_gibps": round(kgib / t_f1, 3),
            "speedup": round(t_leg / t_f1, 2),
            "rel_spread": round(max(sp_leg, sp_f1), 3),
        },
        "reconstruct": {
            "legacy2_gibps": round(kgib / t_rleg, 3),
            "fused1_gibps": round(kgib / t_rf1, 3),
            "speedup": round(t_rleg / t_rf1, 2),
            "rel_spread": round(max(sp_rleg, sp_rf1), 3),
        },
        "interpret_variants_checked": sorted(
            name for name in enc_out if name.startswith("interpret")
        ),
    }

    # -- pass/D2H accounting through the real backend seam --------------
    saved = {
        key: os.environ.get(key)
        for key in ("MINIO_TPU_CODEC_KERNEL", "MINIO_MESH",
                    "MINIO_TPU_DEVICE_COMPRESS")
    }
    accounting = {}
    try:
        os.environ["MINIO_MESH"] = "0"
        os.environ["MINIO_TPU_DEVICE_COMPRESS"] = "on"
        for mode in ("legacy", "fused1"):
            os.environ["MINIO_TPU_CODEC_KERNEL"] = mode
            reset_backend()
            tb = TpuBackend()
            KERNEL_STATS.reset()
            dig, ref = tb.encode_digest_end(
                tb.encode_digest_begin(kdata.copy(), km)
            )
            pre = dict(KERNEL_STATS.snapshot()["device_passes"])
            planes_pre = {
                d_["plane"]: d_["bytes"]
                for d_ in KERNEL_STATS.snapshot()["d2h"]
            }
            par = ref.drain()
            ref.release()
            post = dict(KERNEL_STATS.snapshot()["device_passes"])
            assert np.array_equal(par, be.encode(kdata, km)[0]), mode
            KERNEL_STATS.reset()
            shards_h = np.concatenate(
                [kdata, codec_step.host_words_to_bytes(np.asarray(lp))],
                axis=1,
            )
            got, ok = tb.reconstruct_and_verify(
                shards_h, np.asarray(ld), (True,) * n, kk, km
            )
            assert np.array_equal(got, kdata), mode
            rv = dict(KERNEL_STATS.snapshot()["device_passes"])
            accounting[mode] = {
                "put_passes": pre,
                "put_passes_after_drain": post,
                "put_total_launches": sum(post.values()),
                "get_passes": rv,
                "get_total_launches": sum(rv.values()),
                "d2h_bytes_before_drain": planes_pre,
                "digest_only_before_drain":
                    planes_pre.get("parity", 0) == 0,
            }
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        reset_backend()
    assert accounting["fused1"]["put_total_launches"] == 1
    assert accounting["fused1"]["put_passes_after_drain"] == \
        accounting["fused1"]["put_passes"]
    assert accounting["legacy"]["put_total_launches"] >= 3
    assert accounting["fused1"]["get_total_launches"] == 1

    # -- round 18: transfer/compute overlap sweep -----------------------
    # Drive the real TpuBackend digest seam per MINIO_TPU_CODEC_OVERLAP
    # mode, PUT and GET.  Bit-identity against "off" is a hard gate
    # BEFORE any timing; KERNEL_STATS must prove overlap windows opened
    # in the overlapped modes while the pipeline mode stays at exactly
    # one launch per direction with digest-only eager D2H.  On a host
    # CPU the portable async mode pays real slicing/dispatch overhead
    # with nothing to hide it behind - the bandwidth win is the TPU
    # story (DMA engines running under the compute), so the numbers
    # here are a cost ceiling, not the claim.
    ob, okk, omm = 2, 4, 2
    on_ = okk + omm
    oL = 4 * 4 * rs_pallas._TW  # 64 KiB shards -> w = 4*_TW words
    odata = rng.integers(0, 256, (ob, okk, oL), dtype=np.uint8)
    odata[0, 1] = 0  # keep the pack leg live across sub-chunks
    ogib = odata.nbytes / 2**30
    saved = {
        key: os.environ.get(key)
        for key in ("MINIO_TPU_CODEC_KERNEL", "MINIO_MESH",
                    "MINIO_TPU_DEVICE_COMPRESS", "MINIO_TPU_CODEC_OVERLAP",
                    "MINIO_TPU_CODEC_SUBCHUNK_KB",
                    "MINIO_TPU_CODEC_INTERPRET")
    }
    on_tpu = jax.default_backend() == "tpu"
    overlap_section = {
        "ec": f"{okk}+{omm}",
        "batch": ob,
        "shard_len": oL,
        "data_mib": round(odata.nbytes / 2**20, 2),
        "subchunk_kb": 16,
        "modes": {},
    }
    try:
        os.environ["MINIO_MESH"] = "0"
        os.environ["MINIO_TPU_DEVICE_COMPRESS"] = "on"
        os.environ["MINIO_TPU_CODEC_KERNEL"] = "fused1"
        os.environ["MINIO_TPU_CODEC_SUBCHUNK_KB"] = "16"  # S=4 sub-chunks

        def _overlap_drive(mode):
            os.environ["MINIO_TPU_CODEC_OVERLAP"] = mode
            if mode == "pipeline" and not on_tpu:
                os.environ["MINIO_TPU_CODEC_INTERPRET"] = "1"
            else:
                os.environ.pop("MINIO_TPU_CODEC_INTERPRET", None)
            reset_backend()
            tb = TpuBackend()

            def put():
                dig_, ref_ = tb.encode_digest_end(
                    tb.encode_digest_begin(odata.copy(), omm)
                )
                par_ = ref_.drain()
                ref_.release()
                return dig_, par_

            def get(dig_, par_):
                shards_ = np.concatenate([odata, par_], axis=1)
                return tb.reconstruct_and_verify(
                    shards_, dig_, (True,) * on_, okk, omm
                )

            KERNEL_STATS.reset()
            dig, ref = tb.encode_digest_end(
                tb.encode_digest_begin(odata.copy(), omm)
            )
            planes_pre = {
                d_["plane"]: d_["bytes"]
                for d_ in KERNEL_STATS.snapshot()["d2h"]
            }
            par = ref.drain()
            ref.release()
            put_snap = KERNEL_STATS.snapshot()
            KERNEL_STATS.reset()
            got, ok = get(dig, par)
            get_snap = KERNEL_STATS.snapshot()
            return (dig, par, got, ok, planes_pre, put_snap, get_snap,
                    put, get)

        base = None
        for mode in ("off", "async", "pipeline"):
            (dig, par, got, ok, planes_pre, put_snap, get_snap,
             put, get) = _overlap_drive(mode)
            # hard bit-identity gate BEFORE any timing
            assert bool(np.all(ok)), mode
            assert np.array_equal(got, odata), mode
            if base is None:
                base = (dig, par)
            else:
                assert np.array_equal(dig, base[0]), mode
                assert np.array_equal(par, base[1]), mode
            ow_put = put_snap["overlap_windows"].get("put", 0)
            ow_get = get_snap["overlap_windows"].get("get", 0)
            pp = dict(put_snap["device_passes"])
            gp = dict(get_snap["device_passes"])
            if mode == "off":
                assert ow_put == 0 and ow_get == 0, (ow_put, ow_get)
            else:
                assert ow_put > 0, mode
                assert ow_get > 0, mode
            if mode == "pipeline":
                # still ONE kernel launch per direction: the overlap
                # lives inside the Pallas grid, not in extra dispatches
                assert sum(pp.values()) == 1, pp
                assert sum(gp.values()) == 1, gp
                assert planes_pre.get("parity", 0) == 0, planes_pre
            entry = {
                "overlap_windows": {"put": ow_put, "get": ow_get},
                "put_launches": sum(pp.values()),
                "get_launches": sum(gp.values()),
                "h2d_data_bytes_put": next(
                    (d_["bytes"] for d_ in put_snap["h2d"]
                     if d_["plane"] == "data"), 0
                ),
                "digest_only_before_drain":
                    planes_pre.get("parity", 0) == 0,
            }
            if mode == "pipeline" and not on_tpu:
                # interpret mode is a correctness gate, not a fast path:
                # no throughput claim off-TPU
                entry["interpret"] = True
            else:
                t_put, sp_put = _time(put, reps=3)
                dig_t, par_t = put()
                t_get, sp_get = _time(
                    lambda: get(dig_t, par_t), reps=3
                )
                entry["put_gibps"] = round(ogib / t_put, 3)
                entry["get_gibps"] = round(ogib / t_get, 3)
                entry["rel_spread"] = round(max(sp_put, sp_get), 3)
            overlap_section["modes"][mode] = entry
        overlap_section["bit_identical_all_modes"] = True  # hard-gated
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        reset_backend()

    return {
        "metric": "codec micro (native fused-vs-split + one-kernel "
        "variant sweep + transfer-overlap modes, bit-identity gated)",
        "native": native_section,
        "kernel_variants": variants,
        "pass_accounting": accounting,
        "transfer_overlap": overlap_section,
    }


class _NullWriter:
    """Byte sink for GET timing (no buffer growth in the numbers)."""

    def __init__(self):
        self.n = 0

    def write(self, b):
        self.n += len(b)


def bench_e2e(
    obj_mib: int = 10, singles: int = 12, threads: int = 8,
    per_thread: int = 4, codec_backend: "str | None" = None,
) -> dict:
    """BASELINE.md config #2: EC 8+4, 10 MiB PutObject/GetObject through
    the real object layer (12 local disks, bitrot framing, xl.meta
    quorum commit) - single stream and 8 concurrent clients, with p99.

    The concurrent section is what the stage-8 batching layer exists
    for: all client threads feed one device queue (codec/batcher.py).
    """
    import concurrent.futures
    import io
    import os
    import shutil
    import tempfile

    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage.xl import XLStorage

    size = obj_mib << 20
    gib = size / 2**30
    root = tempfile.mkdtemp(prefix="minio-tpu-bench-")
    saved_env = os.environ.get("MINIO_ERASURE_BACKEND")
    if codec_backend is not None:
        os.environ["MINIO_ERASURE_BACKEND"] = codec_backend
        backend_mod.reset_backend()
    try:
        disks = [XLStorage(f"{root}/d{i}") for i in range(12)]
        ol = ErasureObjects(disks, parity_blocks=4, block_size=BLOCK)
        ol.make_bucket("bench")
        payload = np.random.default_rng(7).integers(
            0, 256, size, dtype=np.uint8
        ).tobytes()

        def put(key):
            t0 = time.perf_counter()
            ol.put_object("bench", key, io.BytesIO(payload), size)
            return time.perf_counter() - t0

        def get(key):
            t0 = time.perf_counter()
            ol.get_object("bench", key, _NullWriter())
            return time.perf_counter() - t0

        put("warm")  # compile + page in
        get("warm")

        put_lat = [put(f"s{i}") for i in range(singles)]
        get_lat = [get(f"s{i}") for i in range(singles)]

        def fanout(op):
            lats = []
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(threads) as ex:
                futs = [
                    ex.submit(
                        lambda t=t: [
                            op(f"c{t}-{i}") for i in range(per_thread)
                        ]
                    )
                    for t in range(threads)
                ]
                for f in futs:
                    lats.extend(f.result())
            wall = time.perf_counter() - t0
            return wall, lats

        # steady-state warm: the first concurrent fan-out mints new
        # merged-batch shapes in the batcher, each paying a one-time
        # XLA compile - that cost belongs to warmup, not the numbers
        fanout(lambda k: put("warm-" + k))
        fanout(lambda k: get("warm-" + k))
        from minio_tpu.codec.telemetry import KERNEL_STATS

        def _stage_delta(before, after, op):
            """Per-stage seconds spent between two telemetry
            snapshots: where the measured fan-out's wall time went
            (assemble = frame interleave, codec = device passes,
            disk = shard I/O waits)."""
            b = {
                (s["op"], s["stage"]): s["seconds"]
                for s in before.get("stages", [])
            }
            return {
                s["stage"]: round(
                    s["seconds"] - b.get((s["op"], s["stage"]), 0.0), 3
                )
                for s in after.get("stages", [])
                if s["op"] == op
            }

        snap0 = KERNEL_STATS.snapshot()
        put_wall, put_clat = fanout(put)
        snap1 = KERNEL_STATS.snapshot()
        get_wall, get_clat = fanout(get)
        snap2 = KERNEL_STATS.snapshot()
        nops = threads * per_thread

        def p99(lats):
            # nearest-rank: ceil(0.99 n) - for n <= 100 that is the max,
            # honestly including the worst op
            import math

            return sorted(lats)[
                max(0, math.ceil(len(lats) * 0.99) - 1)
            ]

        return {
            "object_mib": obj_mib,
            "codec_backend": codec_backend or "auto",
            "concurrency": threads,
            "put_gibps_1": gib / statistics.median(put_lat),
            "get_gibps_1": gib / statistics.median(get_lat),
            "put_gibps_nc": nops * gib / put_wall,
            "get_gibps_nc": nops * gib / get_wall,
            "put_p99_ms_nc": round(p99(put_clat) * 1e3, 1),
            "get_p99_ms_nc": round(p99(get_clat) * 1e3, 1),
            "put_p50_ms_1": round(
                statistics.median(put_lat) * 1e3, 1
            ),
            "get_p50_ms_1": round(
                statistics.median(get_lat) * 1e3, 1
            ),
            "put_stages_nc": _stage_delta(snap0, snap1, "put"),
            "get_stages_nc": _stage_delta(snap1, snap2, "get"),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
        if codec_backend is not None:
            if saved_env is None:
                os.environ.pop("MINIO_ERASURE_BACKEND", None)
            else:
                os.environ["MINIO_ERASURE_BACKEND"] = saved_env
            backend_mod.reset_backend()


def bench_get_degraded(
    obj_mib: int = 4, n_disks: int = 6, reads: int = 30
) -> dict:
    """Degraded-path GET micro: healthy vs one-slow-disk tail latency.

    One disk (the holder of shard 1, so always in the preferred read
    set) is fault-injected at ~20x the pool-median shard-read latency
    (storage/faults.py); the hedged read loop plus breaker preference
    (codec/erasure.py, storage/health.py) must hold the degraded p99
    near the healthy p99 instead of the straggler's latency.  Reported
    with the hedge launched/won/wasted counters for the degraded phase.
    """
    import io
    import math
    import os
    import shutil
    import tempfile

    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.objectlayer.metadata import hash_order
    from minio_tpu.storage import health as disk_health
    from minio_tpu.storage.faults import FaultDisk
    from minio_tpu.storage.xl import XLStorage

    size = obj_mib << 20
    root = tempfile.mkdtemp(prefix="minio-tpu-degraded-")
    saved_env = os.environ.get("MINIO_ERASURE_BACKEND")
    os.environ["MINIO_ERASURE_BACKEND"] = "cpu"
    backend_mod.reset_backend()
    disk_health.reset_registry()
    try:
        fds = [
            FaultDisk(XLStorage(f"{root}/d{i}"), seed=i)
            for i in range(n_disks)
        ]
        ol = ErasureObjects(fds, block_size=BLOCK)
        ol.make_bucket("bench")
        payload = np.random.default_rng(11).integers(
            0, 256, size, dtype=np.uint8
        ).tobytes()
        ol.put_object("bench", "obj", io.BytesIO(payload), size)

        def get():
            t0 = time.perf_counter()
            ol.get_object("bench", "obj", _NullWriter())
            return time.perf_counter() - t0

        get()  # warm the all-data fast path
        slow = hash_order("bench/obj", n_disks).index(1)
        fds[slow].inject("read_at", error=True)
        get()  # warm the parity-reconstruct solve (one-time compile)
        fds[slow].clear()

        healthy = sorted(get() for _ in range(reads))
        reg = disk_health.registry()
        delay = max(20.0 * (reg.read_quantile(0.5) or 0.0), 0.02)
        h0 = KERNEL_STATS.snapshot()["hedge"]
        fds[slow].inject("read_at", delay_s=delay)
        degraded = sorted(get() for _ in range(reads))
        h1 = KERNEL_STATS.snapshot()["hedge"]

        def pct(lats, q):
            # nearest-rank, honestly including the worst read
            return lats[max(0, math.ceil(len(lats) * q) - 1)]

        return {
            "object_mib": obj_mib,
            "reads_per_phase": reads,
            "injected_delay_ms": round(delay * 1e3, 2),
            "healthy_p50_ms": round(pct(healthy, 0.5) * 1e3, 2),
            "healthy_p99_ms": round(pct(healthy, 0.99) * 1e3, 2),
            "degraded_p50_ms": round(pct(degraded, 0.5) * 1e3, 2),
            "degraded_p99_ms": round(pct(degraded, 0.99) * 1e3, 2),
            "p99_ratio": round(
                pct(degraded, 0.99) / max(pct(healthy, 0.99), 1e-9), 2
            ),
            "hedge": {k: h1[k] - h0.get(k, 0) for k in h1},
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
        disk_health.reset_registry()
        if saved_env is None:
            os.environ.pop("MINIO_ERASURE_BACKEND", None)
        else:
            os.environ["MINIO_ERASURE_BACKEND"] = saved_env
        backend_mod.reset_backend()


def bench_cache_micro(
    n_disks: int = 6,
    reads: int = 40,
    zipf_keys: int = 32,
    zipf_alpha: float = 1.2,
    zipf_reads: int = 200,
) -> dict:
    """Tiered read cache micro: cold (cache off) vs hot (host tier) GET.

    Two sweeps through the real object layer on the native CPU codec:
    a per-size sweep (64 KiB .. 4 MiB, one hot key) and a Zipf sweep
    (``zipf_keys`` objects of 256 KiB, rank-``zipf_alpha`` skew, the
    SAME sampled key sequence replayed in both modes).  Cold runs with
    MINIO_TPU_READ_CACHE=off (the bisection oracle - today's quorum
    read path exactly); hot runs with the host tier after a warm-up
    that lets TinyLFU admit the working set.

    Hard bit-identity gate: in BOTH modes every benchmarked object is
    read back and compared byte-for-byte against the PUT payload before
    timing, and the hot phase re-verifies after the timed loop so a
    cache serving rotted rows fails the bench instead of flattering it.
    """
    import io
    import math
    import os
    import shutil
    import tempfile

    from minio_tpu import cache as rcache
    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage import health as disk_health
    from minio_tpu.storage.xl import XLStorage

    root = tempfile.mkdtemp(prefix="minio-tpu-cachemicro-")
    saved_be = os.environ.get("MINIO_ERASURE_BACKEND")
    saved_rc = os.environ.get("MINIO_TPU_READ_CACHE")
    os.environ["MINIO_ERASURE_BACKEND"] = "cpu"
    backend_mod.reset_backend()
    disk_health.reset_registry()
    rcache.reset_read_cache()
    try:
        disks = [XLStorage(f"{root}/d{i}") for i in range(n_disks)]
        ol = ErasureObjects(disks, block_size=BLOCK)
        ol.make_bucket("bench")
        rng = np.random.default_rng(12)
        sizes = [64 << 10, 256 << 10, 1 << 20, 4 << 20]
        payloads: dict[str, bytes] = {}

        def put(name, body):
            payloads[name] = body
            ol.put_object("bench", name, io.BytesIO(body), len(body))

        for sz in sizes:
            put(
                f"obj-{sz}",
                rng.integers(0, 256, sz, dtype=np.uint8).tobytes(),
            )

        def pct(lats, q):
            # nearest-rank, honestly including the worst read
            return lats[max(0, math.ceil(len(lats) * q) - 1)]

        def timed_get(name):
            t0 = time.perf_counter()
            ol.get_object("bench", name, _NullWriter())
            return time.perf_counter() - t0

        def assert_identical(name):
            buf = io.BytesIO()
            ol.get_object("bench", name, buf)
            got = buf.getvalue()
            if got != payloads[name]:
                raise AssertionError(
                    f"bit-identity gate: {name} read "
                    f"{len(got)}B != stored {len(payloads[name])}B "
                    f"(mode={os.environ['MINIO_TPU_READ_CACHE']})"
                )

        def set_mode(mode):
            os.environ["MINIO_TPU_READ_CACHE"] = mode
            rcache.reset_read_cache()

        size_sweep = []
        for sz in sizes:
            name = f"obj-{sz}"
            row = {"object_kib": sz >> 10}
            for mode, label in (("off", "cold"), ("host", "hot")):
                set_mode(mode)
                assert_identical(name)  # also warms/admits in host mode
                for _ in range(3):
                    timed_get(name)
                lats = sorted(timed_get(name) for _ in range(reads))
                if mode == "host":
                    assert_identical(name)  # re-verify the cached rows
                row[f"{label}_p50_ms"] = round(pct(lats, 0.5) * 1e3, 3)
                row[f"{label}_p99_ms"] = round(pct(lats, 0.99) * 1e3, 3)
                row[f"{label}_mib_s"] = round(
                    (sz / (1 << 20)) / max(pct(lats, 0.5), 1e-9), 1
                )
            row["hot_speedup_p50"] = round(
                row["cold_p50_ms"] / max(row["hot_p50_ms"], 1e-9), 2
            )
            size_sweep.append(row)

        # Zipf sweep: skewed key popularity over a 256 KiB working set;
        # both modes replay the identical pre-sampled sequence.
        zsz = 256 << 10
        znames = [f"zipf-{i}" for i in range(zipf_keys)]
        for nm in znames:
            put(nm, rng.integers(0, 256, zsz, dtype=np.uint8).tobytes())
        probs = np.arange(1, zipf_keys + 1, dtype=np.float64) ** -zipf_alpha
        probs /= probs.sum()
        seq = np.random.default_rng(13).choice(
            zipf_keys, size=zipf_reads, p=probs
        )
        zipf = {
            "keys": zipf_keys,
            "object_kib": zsz >> 10,
            "alpha": zipf_alpha,
            "reads": zipf_reads,
        }
        for mode, label in (("off", "cold"), ("host", "hot")):
            set_mode(mode)
            for nm in znames:
                assert_identical(nm)
            lats = sorted(timed_get(znames[int(i)]) for i in seq)
            if mode == "host":
                for nm in znames:
                    assert_identical(nm)
                st = rcache.read_cache_stats()
                tier = st["tiers"]["host"]
                looks = tier["hits"] + tier["misses"]
                zipf["hot_hit_rate"] = round(
                    tier["hits"] / max(looks, 1), 3
                )
                zipf["hot_entries"] = tier["entries"]
                zipf["admission_rejected"] = st["admission"]["rejected"]
            zipf[f"{label}_p50_ms"] = round(pct(lats, 0.5) * 1e3, 3)
            zipf[f"{label}_p99_ms"] = round(pct(lats, 0.99) * 1e3, 3)
        zipf["hot_speedup_p50"] = round(
            zipf["cold_p50_ms"] / max(zipf["hot_p50_ms"], 1e-9), 2
        )

        hot_set = [r for r in size_sweep if r["object_kib"] <= 1024]
        return {
            "metric": (
                "tiered read cache micro (cold=off oracle vs hot=host "
                f"tier, EC on {n_disks} drives, 1 MiB blocks)"
            ),
            "reads_per_cell": reads,
            "size_sweep": size_sweep,
            "zipf": zipf,
            "bit_identical_all_cells": True,
            "headline_hot_speedup_p50": min(
                r["hot_speedup_p50"] for r in hot_set
            ),
            "headline_gate_3x": all(
                r["hot_speedup_p50"] >= 3.0 for r in hot_set
            ),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
        disk_health.reset_registry()
        if saved_be is None:
            os.environ.pop("MINIO_ERASURE_BACKEND", None)
        else:
            os.environ["MINIO_ERASURE_BACKEND"] = saved_be
        if saved_rc is None:
            os.environ.pop("MINIO_TPU_READ_CACHE", None)
        else:
            os.environ["MINIO_TPU_READ_CACHE"] = saved_rc
        backend_mod.reset_backend()
        rcache.reset_read_cache()


def bench_put_readback(
    obj_mib: int = 4, n_disks: int = 6, puts: int = 8
) -> dict:
    """Device-resident parity plane micro: PUT-ack readback accounting.

    Two runs of the same PUTs through the real object layer on the
    device codec (EC 4+2, single-device mesh so parity planes stay
    cached on device):

      legacy       MINIO_TPU_PARITY_PLANE=off - parity is read back
                   eagerly inside encode_end, before the ack.
      plane_early  MINIO_TPU_PARITY_PLANE=on + MINIO_TPU_PARITY_ACK=
                   early - encode returns 32-byte digests only; parity
                   D2H rides the background band past the data-quorum
                   ack.

    The miniotpu_codec_d2h_bytes_total{plane} counters are snapshotted
    at the ack (last put_object return) and again once the parity cache
    has fully drained.  Because the band drains parity CONCURRENTLY
    with the data-shard fsyncs, wall-clock snapshots alone cannot tell
    "the ack waited on this transfer" from "the band happened to finish
    first" on fast local disks - so the bench additionally splits every
    parity D2H by the thread that performed it: transfers on iopool
    workers are band drains the ack never blocks on; transfers on the
    caller/batcher threads sit on the ack critical path (legacy
    encode_end reads parity back there).  `parity_d2h_by_path` is the
    tentpole metric: ack_path bytes drop to 0 on the plane path.

    Both runs write the same object names into separate roots; the
    on-disk shard part files are compared byte-for-byte at the end
    (bit-identity is a hard acceptance gate, not a sampled check).
    """
    import glob as globmod
    import io
    import os
    import shutil
    import tempfile
    import threading

    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.codec.telemetry import KERNEL_STATS
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage.xl import XLStorage

    size = obj_mib << 20
    payload = np.random.default_rng(17).integers(
        0, 256, size, dtype=np.uint8
    ).tobytes()
    saved = {
        k: os.environ.get(k)
        for k in (
            "MINIO_ERASURE_BACKEND",
            "MINIO_MESH",
            "MINIO_TPU_PARITY_PLANE",
            "MINIO_TPU_PARITY_ACK",
        )
    }
    os.environ["MINIO_ERASURE_BACKEND"] = "tpu"
    os.environ["MINIO_MESH"] = "0"

    def _d2h(snap):
        return {
            row["plane"]: row["bytes"] for row in snap.get("d2h", [])
        }

    def _delta(before, after):
        return {
            plane: after.get(plane, 0) - before.get(plane, 0)
            for plane in ("data", "parity")
        }

    def _shard_parts(root):
        """{relative part path: bytes} across all disks (xl.meta
        excluded - it embeds mod_time)."""
        out = {}
        for p in sorted(
            globmod.glob(f"{root}/d*/bench/**/part.*", recursive=True)
        ):
            rel = os.path.relpath(p, root)
            # strip the minted uuid data_dir segment for cross-run keys
            parts = rel.split(os.sep)
            rel = os.sep.join(parts[:3] + parts[4:])
            with open(p, "rb") as f:
                out[rel] = f.read()
        return out

    def _run(plane_on):
        os.environ["MINIO_TPU_PARITY_PLANE"] = (
            "on" if plane_on else "off"
        )
        os.environ["MINIO_TPU_PARITY_ACK"] = (
            "early" if plane_on else "settle"
        )
        backend_mod.reset_backend()
        root = tempfile.mkdtemp(prefix="minio-tpu-readback-")
        disks = [XLStorage(f"{root}/d{i}") for i in range(n_disks)]
        ol = ErasureObjects(disks, parity_blocks=2, block_size=BLOCK)
        ol.make_bucket("bench")

        def put(key):
            t0 = time.perf_counter()
            ol.put_object("bench", key, io.BytesIO(payload), size)
            return time.perf_counter() - t0

        put("warm")  # compile + page in

        def _settled():
            """Parity cache empty AND the d2h counters quiet."""
            deadline = time.monotonic() + 30.0
            last = None
            while time.monotonic() < deadline:
                snap = KERNEL_STATS.snapshot()
                cur = (
                    snap["parity_cache"]["entries"],
                    _d2h(snap).get("parity", 0),
                )
                if cur == last and cur[0] == 0:
                    return snap
                last = cur
                time.sleep(0.05)
            return KERNEL_STATS.snapshot()

        _settled()  # flush the warm put's band before measuring
        # causal split: tee every parity D2H by the thread that ran it
        by_path = {"ack_path": 0, "band": 0}
        tee_mu = threading.Lock()
        real_record = backend_mod._record_d2h

        def tee(plane, nbytes):
            real_record(plane, nbytes)
            if plane == "parity":
                where = (
                    "band"
                    if threading.current_thread().name.startswith(
                        "iopool"
                    )
                    else "ack_path"
                )
                with tee_mu:
                    by_path[where] += int(nbytes)

        before = _d2h(KERNEL_STATS.snapshot())
        backend_mod._record_d2h = tee
        try:
            lats = [put(f"o{i}") for i in range(puts)]
            at_ack = _d2h(KERNEL_STATS.snapshot())
            t0 = time.monotonic()
            settled_snap = _settled()
        finally:
            backend_mod._record_d2h = real_record
        settle_wait = time.monotonic() - t0
        settled = _d2h(settled_snap)
        return {
            "root": root,
            "put_ack_p50_ms": round(
                statistics.median(lats) * 1e3, 1
            ),
            "d2h_at_ack": _delta(before, at_ack),
            "d2h_settled": _delta(before, settled),
            "parity_d2h_by_path": dict(by_path),
            "settle_wait_ms": round(settle_wait * 1e3, 1),
        }

    try:
        legacy = _run(plane_on=False)
        early = _run(plane_on=True)
        identical = _shard_parts(legacy["root"]) == _shard_parts(
            early["root"]
        )
        data_bytes = puts * size
        return {
            "object_mib": obj_mib,
            "puts": puts,
            "ec": f"{n_disks - 2}+2",
            "legacy": {
                k: v for k, v in legacy.items() if k != "root"
            },
            "plane_early": {
                k: v for k, v in early.items() if k != "root"
            },
            # parity bytes read back ON the ack critical path, per byte
            # of object data (the tentpole metric: 0 on the plane path)
            "ack_path_parity_d2h_per_data_byte": {
                "legacy": round(
                    legacy["parity_d2h_by_path"]["ack_path"]
                    / data_bytes,
                    4,
                ),
                "plane_early": round(
                    early["parity_d2h_by_path"]["ack_path"]
                    / data_bytes,
                    4,
                ),
            },
            "shards_bit_identical": identical,
        }
    finally:
        for r in ("legacy", "early"):
            v = locals().get(r)
            if isinstance(v, dict) and "root" in v:
                shutil.rmtree(v["root"], ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        backend_mod.reset_backend()


def bench_select_scan() -> dict:
    """S3 Select scan rate over an in-memory CSV
    (pkg/s3select/select_benchmark_test.go shape)."""
    from minio_tpu.s3select.engine import run_select

    rows = 200_000
    data = b"id,name,score\n" + b"".join(
        b"%d,user%d,%d\n" % (i, i, i % 100) for i in range(rows)
    )
    body = (
        b"<SelectObjectContentRequest>"
        b"<Expression>SELECT COUNT(*) FROM S3Object WHERE score &gt; 50"
        b"</Expression><ExpressionType>SQL</ExpressionType>"
        b"<InputSerialization><CSV><FileHeaderInfo>USE</FileHeaderInfo>"
        b"</CSV></InputSerialization>"
        b"<OutputSerialization><CSV/></OutputSerialization>"
        b"</SelectObjectContentRequest>"
    )
    run_select(body, data, lambda _: None)  # warm
    t0 = time.perf_counter()
    run_select(body, data, lambda _: None)
    dt = time.perf_counter() - t0

    jdata = b"".join(
        b'{"id": %d, "name": "user%d", "score": %d}\n'
        % (i, i, i % 100)
        for i in range(rows)
    )
    jbody = (
        b"<SelectObjectContentRequest>"
        b"<Expression>SELECT COUNT(*) FROM S3Object WHERE score &gt; 50"
        b"</Expression><ExpressionType>SQL</ExpressionType>"
        b"<InputSerialization><JSON><Type>LINES</Type>"
        b"</JSON></InputSerialization>"
        b"<OutputSerialization><JSON/></OutputSerialization>"
        b"</SelectObjectContentRequest>"
    )
    run_select(jbody, jdata, lambda _: None)  # warm
    t0 = time.perf_counter()
    run_select(jbody, jdata, lambda _: None)
    jdt = time.perf_counter() - t0
    return {
        "csv_scan_mbps": round(len(data) / dt / 2**20, 1),
        "csv_bytes": len(data),
        "json_scan_mbps": round(len(jdata) / jdt / 2**20, 1),
        "json_bytes": len(jdata),
    }


def bench_select_micro(
    sizes_mib=(1, 8, 64),
    selectivities=(0.001, 0.01, 0.1),
    reps: int = 3,
) -> dict:
    """TPU-pushdown select micro: size x selectivity, three engines.

    Each cell scans a synthetic CSV (``v,id,pad`` rows) with
    ``WHERE s.v > 99999``; selectivity is set by the DATA — a
    ``sel`` fraction of rows carry a 6-digit ``v`` among 3-digit
    ones, so the screen's ``deep`` (digit-count) atom flags exactly
    the matching rows.  This is the engine's designed fast shape:
    the screened column comes first (row-anchored screen), and the
    candidate set tracks the true match set, so D2H volume is
    result-proportional.  Shapes the screen cannot discriminate
    (``<`` on uniform data, predicates on later columns of
    mixed-type rows) fall back to the host path via the ratio guard
    and are covered by correctness tests, not this micro.
    Engines per cell:

      row             MINIO_TPU_SELECT=row    - the bisection oracle
      host            MINIO_TPU_SELECT=host   - numpy columnar scan
      device_stream   MINIO_TPU_SELECT=device - upload + screen + drain
      device_hot      device over a resident plane (the cache-tier
                      shape: built once outside the timed loop)

    Hard gates: every engine's decoded Records payload (frame
    boundaries differ per engine chunk size, so the event stream is
    unframed first) is byte-identical to the row oracle, and the
    device cells must finish with ZERO fallbacks — proving the screen ran and only candidate rows (plus
    the per-chunk anchor row) crossed D2H, so readback is
    result-proportional rather than plane-proportional.
    """
    import io
    import os

    from minio_tpu.s3select import device as seldev
    from minio_tpu.s3select.engine import S3Select, SelectRequest

    saved_mode = os.environ.get("MINIO_TPU_SELECT")

    def make_csv(size_mib, sel_frac):
        rng = np.random.default_rng(size_mib * 1000 + int(sel_frac * 1e4))
        target = size_mib << 20
        # ~64 B rows: v (3 or 6) + id (7) + fixed 46-byte pad
        nrows = target // 64
        hi = rng.random(nrows) < sel_frac
        v = np.where(
            hi,
            rng.integers(100_000, 1_000_000, nrows),
            rng.integers(100, 1_000, nrows),
        )
        pad = "x" * 46
        rows = [f"{v[i]},{i:07d},{pad}" for i in range(nrows)]
        return ("v,id,pad\n" + "\n".join(rows) + "\n").encode(), v

    def unframe(buf):
        # concatenate Records-event payloads; framing (flush points)
        # legitimately differs between engines, content must not
        out = bytearray()
        off = 0
        while off < len(buf):
            total = int.from_bytes(buf[off : off + 4], "big")
            hlen = int.from_bytes(buf[off + 4 : off + 8], "big")
            hdrs = buf[off + 12 : off + 12 + hlen]
            if b"Records" in hdrs:
                out += buf[off + 12 + hlen : off + total - 4]
            off += total
        return bytes(out)

    def run(expr, data, mode, source=None):
        os.environ["MINIO_TPU_SELECT"] = mode
        body = (
            "<SelectObjectContentRequest>"
            f"<Expression>{expr.replace('<', '&lt;')}</Expression>"
            "<ExpressionType>SQL</ExpressionType>"
            "<InputSerialization><CSV><FileHeaderInfo>USE"
            "</FileHeaderInfo></CSV></InputSerialization>"
            "<OutputSerialization><CSV/></OutputSerialization>"
            "</SelectObjectContentRequest>"
        ).encode()
        sel = S3Select(SelectRequest.from_xml(body))
        out = bytearray()
        t0 = time.perf_counter()
        if source is not None:
            sel.evaluate(None, len(data), out.extend, device_source=source)
        else:
            sel.evaluate(io.BytesIO(data), len(data), out.extend)
        return time.perf_counter() - t0, bytes(out)

    cells = []
    try:
        for size_mib in sizes_mib:
            for sel_frac in selectivities:
                data, _v = make_csv(size_mib, sel_frac)
                plane = seldev.as_device_plane(
                    [np.frombuffer(data, dtype=np.uint8)], len(data)
                )
                expr = "SELECT s.id FROM S3Object s WHERE s.v > 99999"
                cell = {
                    "size_mib": size_mib,
                    "selectivity": sel_frac,
                }
                oracle = None
                fb0 = sum(
                    seldev.STATS.snapshot()["fallbacks"].values()
                )
                for label, mode, source in (
                    ("row", "row", None),
                    ("host", "host", None),
                    ("device_stream", "device", None),
                    ("device_hot", "device", plane),
                ):
                    # the row oracle is timed once (it only anchors
                    # the identity + baseline; reps would dominate
                    # the wall clock at 64 MiB)
                    n = 1 if label == "row" else reps
                    run(expr, data, mode, source)  # warm (jit/caches)
                    best = None
                    for _ in range(n):
                        dt, payload = run(expr, data, mode, source)
                        best = dt if best is None else min(best, dt)
                    records = unframe(payload)
                    if oracle is None:
                        oracle = records
                        cell["result_bytes"] = len(records)
                    elif records != oracle:
                        raise AssertionError(
                            f"bit-identity gate: {label} diverged at "
                            f"{size_mib} MiB sel={sel_frac}"
                        )
                    cell[f"{label}_s"] = round(best, 4)
                    cell[f"{label}_mib_s"] = round(
                        size_mib / max(best, 1e-9), 1
                    )
                fb1 = sum(
                    seldev.STATS.snapshot()["fallbacks"].values()
                )
                cell["device_fallbacks"] = fb1 - fb0
                if fb1 != fb0:
                    raise AssertionError(
                        f"device screen fell back at {size_mib} MiB "
                        f"sel={sel_frac}: D2H not result-proportional"
                    )
                cell["speedup_hot_vs_host"] = round(
                    cell["host_s"] / max(cell["device_hot_s"], 1e-9), 2
                )
                cell["speedup_stream_vs_host"] = round(
                    cell["host_s"] / max(cell["device_stream_s"], 1e-9),
                    2,
                )
                cells.append(cell)
        gate_cells = [
            c
            for c in cells
            if c["size_mib"] >= 64 and c["selectivity"] <= 0.01
        ]
        return {
            "metric": (
                "select pushdown micro (device screen vs host vector "
                "vs row oracle; bit-identity + zero-fallback gated)"
            ),
            "reps_per_cell": reps,
            "cells": cells,
            "bit_identical_all_cells": True,
            "headline_hot_speedup": max(
                (c["speedup_hot_vs_host"] for c in gate_cells),
                default=None,
            ),
            "headline_gate_3x": bool(gate_cells)
            and all(
                c["speedup_hot_vs_host"] >= 3.0 for c in gate_cells
            ),
        }
    finally:
        if saved_mode is None:
            os.environ.pop("MINIO_TPU_SELECT", None)
        else:
            os.environ["MINIO_TPU_SELECT"] = saved_mode


def _kernel_stats_snapshot():
    from minio_tpu.codec.telemetry import KERNEL_STATS

    return KERNEL_STATS.snapshot()


def bench_concurrency_sweep(
    obj_mib: int = 1,
    levels=(1, 4, 8, 16, 32, 64),
    ops_per_level: int = 96,
) -> dict:
    """Request-plane sweep (--concurrency): GET and PUT latency under
    1..64 persistent keep-alive clients, async event-loop plane vs the
    threaded oracle, through the full HTTP stack (SigV4 auth, erasure
    object layer).  CPU codec backend so codec time does not drown
    the request-plane signal under test.

    Also runs a constrained shed probe (2 workers, 2-deep handler
    queue, 16 clients) so the 503 SlowDown admission path shows up in
    the numbers, not just the unit tests.
    """
    import concurrent.futures
    import datetime
    import hashlib
    import http.client
    import math
    import os
    import shutil
    import tempfile

    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.server import auth
    from minio_tpu.server.http import S3Server
    from minio_tpu.storage.xl import XLStorage

    size = obj_mib << 20
    payload = np.random.default_rng(13).integers(
        0, 256, size, dtype=np.uint8
    ).tobytes()
    phash_put = hashlib.sha256(payload).hexdigest()
    phash_empty = hashlib.sha256(b"").hexdigest()

    class _Client:
        """Persistent keep-alive connection issuing SigV4 requests."""

        def __init__(self, endpoint):
            host, port = endpoint.split("//")[1].rsplit(":", 1)
            self.host, self.port = host, int(port)
            self.conn = http.client.HTTPConnection(
                self.host, self.port, timeout=120
            )

        def request(self, method, path, body=b""):
            amz = datetime.datetime.now(datetime.timezone.utc).strftime(
                "%Y%m%dT%H%M%SZ"
            )
            phash = phash_put if body else phash_empty
            headers = {
                "host": f"{self.host}:{self.port}",
                "x-amz-date": amz,
                "x-amz-content-sha256": phash,
            }
            signed = sorted(headers)
            sig = auth.sign_v4(
                method, path, {}, headers, signed, phash,
                "minioadmin", "minioadmin", amz, "us-east-1",
            )
            scope = f"{amz[:8]}/us-east-1/s3/aws4_request"
            headers["authorization"] = (
                f"{auth.SIGN_V4_ALGORITHM} "
                f"Credential=minioadmin/{scope}, "
                f"SignedHeaders={';'.join(signed)}, Signature={sig}"
            )
            try:
                self.conn.request(
                    method, path, body=body or None, headers=headers
                )
                r = self.conn.getresponse()
                r.read()
                return r.status
            except (http.client.HTTPException, OSError):
                # server closed the connection (e.g. after a shed) -
                # reconnect like a real SDK would
                self.conn.close()
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=120
                )
                raise

        def close(self):
            self.conn.close()

    def _pct(lats, q):
        return sorted(lats)[max(0, math.ceil(len(lats) * q) - 1)]

    def _boot(mode, root, **env):
        saved = {
            k: os.environ.get(k) for k in ("MINIO_TPU_SERVER", *env)
        }
        os.environ["MINIO_TPU_SERVER"] = mode
        for k, v in env.items():
            os.environ[k] = str(v)
        disks = [XLStorage(f"{root}/d{i}") for i in range(8)]
        ol = ErasureObjects(disks, parity_blocks=4, block_size=BLOCK)
        srv = S3Server(ol, address="127.0.0.1:0").start()
        return srv, saved

    def _restore(saved):
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def _fanout(endpoint, clients, op, n_ops, keys):
        """n_ops requests spread over `clients` persistent
        connections; returns (latencies, shed_503_count)."""
        per = max(1, n_ops // clients)
        sheds = [0]

        def worker(cid):
            c = _Client(endpoint)
            lats = []
            try:
                for i in range(per):
                    key = keys[(cid * per + i) % len(keys)]
                    t0 = time.perf_counter()
                    if op == "GET":
                        st = c.request("GET", f"/bench/{key}")
                    else:
                        st = c.request(
                            "PUT", f"/bench/w{cid}-{i}", payload
                        )
                    dt = time.perf_counter() - t0
                    if st == 503:
                        sheds[0] += 1  # GIL-atomic int bump
                    else:
                        lats.append(dt)
            finally:
                c.close()
            return lats

        lats = []
        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            for f in [ex.submit(worker, i) for i in range(clients)]:
                lats.extend(f.result())
        return lats, sheds[0]

    saved_backend = os.environ.get("MINIO_ERASURE_BACKEND")
    os.environ["MINIO_ERASURE_BACKEND"] = "cpu"
    backend_mod.reset_backend()
    results = {"object_mib": obj_mib, "levels": [], "shed_probe": None}
    try:
        for mode in ("threaded", "async"):
            root = tempfile.mkdtemp(prefix=f"minio-tpu-csweep-{mode}-")
            # single loop pinned: these rows are the threaded-vs-async
            # oracle comparison; the loops axis lives in the storm tier
            srv, saved = _boot(mode, root, MINIO_TPU_SERVER_LOOPS=1)
            try:
                boot = _Client(srv.endpoint)
                assert boot.request("PUT", "/bench") == 200
                keys = [f"o{i}" for i in range(16)]
                for k in keys:
                    assert boot.request(
                        "PUT", f"/bench/{k}", payload
                    ) == 200
                boot.close()
                _fanout(srv.endpoint, 4, "GET", 16, keys)  # warm
                for clients in levels:
                    row = {"mode": mode, "clients": clients}
                    for op in ("GET", "PUT"):
                        s0 = srv.plane_stats.snapshot()["shed"]
                        lats, shed = _fanout(
                            srv.endpoint, clients, op,
                            ops_per_level, keys,
                        )
                        s1 = srv.plane_stats.snapshot()["shed"]
                        key = op.lower()
                        row[f"{key}_ops"] = len(lats)
                        row[f"{key}_p50_ms"] = round(
                            _pct(lats, 0.5) * 1e3, 1
                        )
                        row[f"{key}_p99_ms"] = round(
                            _pct(lats, 0.99) * 1e3, 1
                        )
                        row[f"{key}_shed_503"] = shed
                        row[f"{key}_plane_shed"] = {
                            r: s1[r] - s0[r] for r in s1 if s1[r] - s0[r]
                        }
                    results["levels"].append(row)
            finally:
                srv.shutdown(drain_s=5.0)
                _restore(saved)
                shutil.rmtree(root, ignore_errors=True)

        # shed probe: constrain the async handler stage so admission
        # actually refuses work, and report how many 503s land
        root = tempfile.mkdtemp(prefix="minio-tpu-csweep-shed-")
        srv, saved = _boot(
            "async", root,
            MINIO_TPU_SERVER_LOOPS=1,  # exact single-queue semantics
            MINIO_TPU_SERVER_WORKERS=2, MINIO_TPU_SERVER_BACKLOG=2,
        )
        try:
            boot = _Client(srv.endpoint)
            assert boot.request("PUT", "/bench") == 200
            keys = ["p0", "p1"]
            for k in keys:
                assert boot.request("PUT", f"/bench/{k}", payload) == 200
            boot.close()
            s0 = srv.plane_stats.snapshot()["shed"]
            lats, shed = _fanout(srv.endpoint, 16, "GET", 64, keys)
            s1 = srv.plane_stats.snapshot()["shed"]
            results["shed_probe"] = {
                "workers": 2, "backlog": 2, "clients": 16,
                "completed": len(lats), "shed_503": shed,
                "plane_shed": {
                    r: s1[r] - s0[r] for r in s1 if s1[r] - s0[r]
                },
            }
        finally:
            srv.shutdown(drain_s=5.0)
            _restore(saved)
            shutil.rmtree(root, ignore_errors=True)
    finally:
        if saved_backend is None:
            os.environ.pop("MINIO_ERASURE_BACKEND", None)
        else:
            os.environ["MINIO_ERASURE_BACKEND"] = saved_backend
        backend_mod.reset_backend()

    by = {
        (r["mode"], r["clients"]): r for r in results["levels"]
    }
    ratios = {}
    for op in ("get", "put"):
        t = by.get(("threaded", 32))
        a = by.get(("async", 32))
        if t and a and a[f"{op}_p99_ms"]:
            ratios[f"{op}_p99_ratio_32"] = round(
                t[f"{op}_p99_ms"] / a[f"{op}_p99_ms"], 2
            )
    results["acceptance"] = ratios
    results["storm"] = bench_connection_storm()
    return results


def bench_connection_storm(
    duration_s: float = 6.0,
    active_clients: int = 256,
    loris_conns: int = 256,
    pipeline_depth: int = 64,
) -> dict:
    """Connection-storm tier of --concurrency: the multi-loop front
    plane under 10k-class keep-alive connection counts, driven by a
    lightweight in-process asyncio client (one OS thread holds every
    client connection, so the storm measures the SERVER, not a client
    thread pool).

    Cells, per loop count (async@1 oracle vs async@N):

    - correctness gate BEFORE any timing: pathological pipelining
      (``pipeline_depth`` GETs burst-written in one segment, responses
      must come back in order, bodies bit-exact) and a SHA-256 running
      digest over every response body that must match across loop
      counts (bit-identity between 1 and N loops is a hard gate);
    - connection hold: open ~10k keep-alive connections in waves
      (MINIO_TPU_BENCH_STORM_CONNS overrides; clamped to the fd
      rlimit), each proves liveness with one small GET;
    - timed GET storm over ``active_clients`` of the held
      connections -> throughput + p99 while thousands of idle
      connections stay parked;
    - slow-loris flood: ``loris_conns`` connections trickle a request
      head forever; a concurrent GET flood on healthy connections must
      keep completing with correct bodies.

    A separate overload cell pins MINIO_TPU_TENANT_MAX_INFLIGHT and
    floods 64 one-shot clients: every response is 200 or an honest 503,
    and the healthinfo admission block's tenant high-water mark must
    show the GLOBAL cap was never exceeded across loops.
    """
    import asyncio
    import datetime
    import hashlib
    import os
    import resource
    import shutil
    import tempfile

    from minio_tpu.codec import backend as backend_mod
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.server import auth
    from minio_tpu.server.http import S3Server
    from minio_tpu.storage.xl import XLStorage

    cores = os.cpu_count() or 1
    soft_nofile, _hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = int(os.environ.get("MINIO_TPU_BENCH_STORM_CONNS", "0")) or (
        10_000 if cores >= 2 else 2_000
    )
    # every client connection costs two fds here (server is in-process)
    n_conns = max(active_clients, min(want, (soft_nofile - 512) // 2))
    multi_loops = min(max(cores, 2), 4)

    obj = np.random.default_rng(19).integers(
        0, 256, 8 << 10, dtype=np.uint8
    ).tobytes()
    slow_obj = np.random.default_rng(20).integers(
        0, 256, 1 << 20, dtype=np.uint8
    ).tobytes()
    phash_empty = hashlib.sha256(b"").hexdigest()

    def _head(host, port, path):
        """One signed GET request head (SigV4, keep-alive), as bytes -
        signed once and reused for every request on the storm's hot
        path so the driver stays lighter than the server."""
        amz = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y%m%dT%H%M%SZ"
        )
        headers = {
            "host": f"{host}:{port}",
            "x-amz-date": amz,
            "x-amz-content-sha256": phash_empty,
        }
        signed = sorted(headers)
        sig = auth.sign_v4(
            "GET", path, {}, headers, signed, phash_empty,
            "minioadmin", "minioadmin", amz, "us-east-1",
        )
        scope = f"{amz[:8]}/us-east-1/s3/aws4_request"
        headers["authorization"] = (
            f"{auth.SIGN_V4_ALGORITHM} "
            f"Credential=minioadmin/{scope}, "
            f"SignedHeaders={';'.join(signed)}, Signature={sig}"
        )
        lines = [f"GET {path} HTTP/1.1"]
        lines += [f"{k}: {v}" for k, v in headers.items()]
        return ("\r\n".join(lines) + "\r\n\r\n").encode()

    def _put_seed(host, port, path, body):
        """One signed PUT over a throwaway connection (seeding)."""
        import http.client as _hc

        amz = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y%m%dT%H%M%SZ"
        )
        ph = hashlib.sha256(body).hexdigest()
        hdrs = {
            "host": f"{host}:{port}",
            "x-amz-date": amz,
            "x-amz-content-sha256": ph,
        }
        signed = sorted(hdrs)
        sig = auth.sign_v4(
            "PUT", path, {}, hdrs, signed, ph,
            "minioadmin", "minioadmin", amz, "us-east-1",
        )
        scope = f"{amz[:8]}/us-east-1/s3/aws4_request"
        hdrs["authorization"] = (
            f"{auth.SIGN_V4_ALGORITHM} "
            f"Credential=minioadmin/{scope}, "
            f"SignedHeaders={';'.join(signed)}, Signature={sig}"
        )
        hc = _hc.HTTPConnection(host, port, timeout=60)
        try:
            hc.request("PUT", path, body=body or None, headers=hdrs)
            resp = hc.getresponse()
            resp.read()
            if resp.status != 200:
                raise RuntimeError(
                    f"storm seed PUT {path}: {resp.status}"
                )
        finally:
            hc.close()

    async def _read_resp(r):
        """Minimal HTTP/1.1 response read: (status, body)."""
        status_line = await r.readline()
        if not status_line:
            return None, b""
        status = int(status_line.split()[1])
        clen = 0
        while True:
            line = await r.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                clen = int(v)
        body = await r.readexactly(clen) if clen else b""
        return status, body

    def _boot(loops, **env):
        env = {
            "MINIO_TPU_SERVER": "async",
            "MINIO_TPU_SERVER_LOOPS": str(loops),
            **{k: str(v) for k, v in env.items()},
        }
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        root = tempfile.mkdtemp(prefix="minio-tpu-storm-")
        disks = [XLStorage(f"{root}/d{i}") for i in range(8)]
        ol = ErasureObjects(disks, parity_blocks=4, block_size=BLOCK)
        srv = S3Server(ol, address="127.0.0.1:0").start()
        host, port = srv.endpoint.split("//")[1].rsplit(":", 1)
        return srv, saved, root, host, int(port)

    def _restore(saved, root):
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(root, ignore_errors=True)

    async def _storm_cell(host, port, head, digest):
        """One loop count's full storm; returns the cell row.  Raises
        RuntimeError on ANY correctness violation (hard gate)."""
        cell = {}

        # -- correctness gate: pathological pipelining, before timing
        r, w = await asyncio.open_connection(host, port)
        try:
            for _round in range(2):
                w.write(head * pipeline_depth)  # one burst segment
                await w.drain()
                for i in range(pipeline_depth):
                    st, body = await _read_resp(r)
                    if st != 200 or body != obj:
                        raise RuntimeError(
                            f"pipelining: resp {i} status={st} "
                            f"len={len(body)}"
                        )
                    digest.update(body)
        finally:
            w.close()
        cell["pipelining"] = {
            "depth": pipeline_depth, "rounds": 2, "ordered": True
        }

        # -- connection hold: waves of keep-alive conns, one GET each.
        # A 503 SlowDown is an HONEST answer under a connect flood
        # (bounded handler queue) - the client retries on the same
        # connection like a real SDK; anything else is a hard failure.
        conns, connect_errors, hold_sheds = [], 0, [0]
        sem = asyncio.Semaphore(64)  # connect-wave width

        async def _checked_get(r, w):
            """One GET on an open conn; retries honest sheds.
            Returns the number of 503s absorbed."""
            sheds = 0
            while True:
                w.write(head)
                await w.drain()
                st, body = await _read_resp(r)
                if st == 200 and body == obj:
                    return sheds
                if st == 503:
                    sheds += 1
                    await asyncio.sleep(0.01 * min(sheds, 20))
                    continue
                raise RuntimeError(
                    f"GET status={st} len={len(body)}"
                )

        async def _hold():
            nonlocal connect_errors
            async with sem:
                try:
                    r, w = await asyncio.open_connection(host, port)
                    hold_sheds[0] += await _checked_get(r, w)
                    conns.append((r, w))
                except OSError:
                    connect_errors += 1

        await asyncio.gather(*[_hold() for _ in range(n_conns)])
        if connect_errors:
            raise RuntimeError(
                f"{connect_errors}/{n_conns} storm connects failed"
            )
        cell["held_conns"] = len(conns)
        cell["hold_sheds_retried"] = hold_sheds[0]

        # -- timed GET storm on a slice of the held connections while
        #    the rest stay parked (sheds counted, not timed)
        lats, storm_sheds = [], [0]
        stop_at = time.perf_counter() + duration_s

        async def _active(pair):
            r, w = pair
            n = 0
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                storm_sheds[0] += await _checked_get(r, w)
                lats.append(time.perf_counter() - t0)
                n += 1
            return n

        done = await asyncio.gather(
            *[_active(p) for p in conns[:active_clients]]
        )
        total = sum(done)
        lats.sort()
        cell["get"] = {
            "active_clients": active_clients,
            "idle_parked": len(conns) - active_clients,
            "ops": total,
            "sheds_retried": storm_sheds[0],
            "rps": round(total / duration_s, 1),
            "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
            "p99_ms": round(
                lats[max(0, int(len(lats) * 0.99) - 1)] * 1e3, 2
            ),
        }

        # -- slow-loris flood: trickling heads must not stall healthy
        #    connections (server read timeout reaps them eventually)
        loris = []
        for _ in range(loris_conns):
            r, w = await asyncio.open_connection(host, port)
            w.write(b"GET /bench/storm HTTP/1.1\r\n")
            await w.drain()
            loris.append((r, w))

        async def _trickle(pair):
            _r, w = pair
            try:
                for ch in "x-trickle: slow\r\n":
                    w.write(ch.encode())
                    await w.drain()
                    await asyncio.sleep(0.25)
            except (ConnectionError, OSError):
                pass  # server reaped the loris - that is a fine answer

        trickles = [
            asyncio.ensure_future(_trickle(p)) for p in loris
        ]
        flood_done = [0]
        flood_stop = time.perf_counter() + 3.0

        async def _flood(pair):
            r, w = pair
            while time.perf_counter() < flood_stop:
                await _checked_get(r, w)
                flood_done[0] += 1

        await asyncio.gather(*[_flood(p) for p in conns[:64]])
        for t in trickles:
            t.cancel()
        for _r, w in loris:
            w.close()
        if not flood_done[0]:
            raise RuntimeError("no GET completed under slow-loris")
        cell["loris"] = {
            "conns": loris_conns,
            "flood_clients": 64,
            "flood_window_s": 3.0,
            "flood_completed": flood_done[0],
        }

        for _r, w in conns:
            w.close()
        return cell

    saved_backend = os.environ.get("MINIO_ERASURE_BACKEND")
    os.environ["MINIO_ERASURE_BACKEND"] = "cpu"
    backend_mod.reset_backend()
    results = {
        "conns": n_conns,
        "cores": cores,
        "cells": {},
        "tenant_cap": None,
    }
    digests = {}
    try:
        for loops in (1, multi_loops):
            srv, saved, root, host, port = _boot(
                loops,
                # a deep handler queue keeps honest sheds rare so the
                # timed section measures service, not retry backoff
                MINIO_TPU_SERVER_WORKERS=16,
                MINIO_TPU_SERVER_BACKLOG=4096,
            )
            try:
                # seed through the same wire the storm uses
                _put_seed(host, port, "/bench", b"")
                _put_seed(host, port, "/bench/storm", obj)
                head = _head(host, port, "/bench/storm")
                digest = hashlib.sha256()
                cell = asyncio.run(
                    _storm_cell(host, port, head, digest)
                )
                cell["loops"] = loops
                digests[loops] = digest.hexdigest()
                results["cells"][str(loops)] = cell
            finally:
                srv.shutdown(drain_s=5.0)
                _restore(saved, root)

        # hard gate: both loop counts returned bit-identical bodies
        results["body_digest_by_loops"] = {
            str(k): v for k, v in digests.items()
        }
        results["bit_identical"] = (
            len(set(digests.values())) == 1
        )
        if not results["bit_identical"]:
            raise RuntimeError(
                f"loop counts disagree on response bytes: {digests}"
            )

        # -- overload cell: global tenant cap must hold EXACTLY across
        #    loops, sheds must be honest 503s
        cap = 8
        srv, saved, root, host, port = _boot(
            multi_loops,
            MINIO_TPU_SERVER_WORKERS=24,
            MINIO_TPU_SERVER_BACKLOG=64,
            MINIO_TPU_TENANT_MAX_INFLIGHT=cap,
        )
        try:
            _put_seed(host, port, "/bench", b"")
            _put_seed(host, port, "/bench/slow", slow_obj)
            slow_head = _head(host, port, "/bench/slow")
            statuses = []

            async def _one_shot():
                try:
                    r, w = await asyncio.open_connection(host, port)
                except OSError:
                    statuses.append(-1)
                    return
                try:
                    w.write(slow_head)
                    await w.drain()
                    st, body = await _read_resp(r)
                    if st == 200 and body != slow_obj:
                        raise RuntimeError("cap GET body mismatch")
                    statuses.append(st if st is not None else -1)
                finally:
                    w.close()

            async def _cap_flood():
                await asyncio.gather(
                    *[_one_shot() for _ in range(64)]
                )

            asyncio.run(_cap_flood())
            counts = {
                str(s): statuses.count(s) for s in sorted(set(statuses))
            }
            dishonest = [
                s for s in statuses if s not in (200, 503)
            ]
            if dishonest:
                raise RuntimeError(
                    f"non-200/503 answers under overload: {counts}"
                )
            hwm = srv.admission.budget.tenant_hwm().get("minioadmin", 0)
            results["tenant_cap"] = {
                "loops": multi_loops,
                "cap": cap,
                "clients": 64,
                "statuses": counts,
                "tenant_hwm": hwm,
                "held": hwm <= cap,
            }
            if hwm > cap:
                raise RuntimeError(
                    f"GLOBAL tenant cap exceeded: hwm={hwm} cap={cap}"
                )
        finally:
            srv.shutdown(drain_s=5.0)
            _restore(saved, root)
    finally:
        if saved_backend is None:
            os.environ.pop("MINIO_ERASURE_BACKEND", None)
        else:
            os.environ["MINIO_ERASURE_BACKEND"] = saved_backend
        backend_mod.reset_backend()

    # scaling acceptance: only a multi-core host can honestly show
    # multi-loop throughput wins (loops time-slice one core otherwise)
    one = results["cells"]["1"]["get"]
    many = results["cells"][str(multi_loops)]["get"]
    speedup = round(many["rps"] / one["rps"], 2) if one["rps"] else 0.0
    p99_ratio = (
        round(many["p99_ms"] / one["p99_ms"], 2)
        if one["p99_ms"]
        else 0.0
    )
    results["acceptance"] = {
        "loops_compared": [1, multi_loops],
        "get_rps_speedup": speedup,
        "get_p99_ratio": p99_ratio,
        "gate_applies": cores >= 2,
    }
    if cores >= 2 and multi_loops >= 2:
        if speedup < 1.6:
            raise RuntimeError(
                f"multi-loop GET speedup {speedup} < 1.6x"
            )
        if p99_ratio > 1.5:
            raise RuntimeError(
                f"multi-loop p99 regressed {p99_ratio}x > 1.5x"
            )
    return results


def bench_multichip(
    chip_counts=(1, 2, 4),
    policies=("span", "route", "auto"),
    small_batches=(1, 2, 4),
    large_batches=(16, 32),
    clients: int = 4,
    k: int = 8,
    m: int = 4,
    length: int = 4096,
) -> dict:
    """Placement sweep (--multichip): chips x batch x policy through the
    production seam (BatchingBackend over TpuBackend pinned to a device
    slice).  Each client encodes its own object-size class (distinct
    lengths -> independent merged groups), which is exactly the workload
    the router exists for: at small batch, ``span`` lowers every group
    to a collective shard_map across all chips and serializes groups on
    the dispatcher thread, while ``route`` runs them concurrently on
    single-chip submeshes through the fused jit path.  Bit-identity vs
    the CPU reference codec is a hard gate on every cell.

    Forces the virtual-CPU platform (same contract as
    __graft_entry__.dryrun_multichip: must run before jax initializes).
    """
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import threading

    import jax

    from minio_tpu.codec.backend import CpuBackend, TpuBackend
    from minio_tpu.codec.batcher import BatchingBackend
    from minio_tpu.codec.telemetry import KERNEL_STATS

    ref = CpuBackend()
    # one object-size class per client, word-aligned, close enough that
    # blocks/s stays comparable across clients
    lengths = [length + 64 * i for i in range(clients)]
    batches = tuple(small_batches) + tuple(large_batches)

    def _run_round(backend, batch, n_ops, check=False):
        """All clients concurrently; returns wall seconds."""
        errs = []
        start = threading.Barrier(clients + 1)

        def client(idx):
            rng = np.random.default_rng(1000 * idx + batch)
            data = rng.integers(
                0, 256, (batch, k, lengths[idx]), dtype=np.uint8
            )
            start.wait()
            for _ in range(n_ops):
                parity, digests = backend.encode(data, m)
            if check:
                ep, ed = ref.encode(data, m)
                if not (
                    np.array_equal(np.asarray(parity), ep)
                    and np.array_equal(np.asarray(digests), ed)
                ):
                    errs.append(idx)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise AssertionError(
                f"bit-identity mismatch vs CPU codec, clients {errs}"
            )
        return wall

    sweep = []
    for chips in chip_counts:
        devices = tuple(jax.devices()[:chips])
        for policy in policies:
            os.environ["MINIO_TPU_PLACEMENT"] = policy
            os.environ["MINIO_TPU_SUBMESH_DEVICES"] = "1"
            backend = BatchingBackend(
                TpuBackend(devices=devices), deadline_s=0.002
            )
            try:
                for batch in batches:
                    n_ops = max(3, 24 // batch)
                    # warmup compiles every client geometry + checks
                    # bit-identity, then the timed round
                    _run_round(backend, batch, 1, check=True)
                    KERNEL_STATS.reset()
                    wall = _run_round(backend, batch, n_ops)
                    snap = KERNEL_STATS.snapshot()
                    blocks = batch * n_ops * clients
                    sweep.append(
                        {
                            "chips": chips,
                            "policy": policy,
                            "batch": batch,
                            "blocks_per_s": round(blocks / wall, 1),
                            "wall_s": round(wall, 4),
                            "placement": snap["placement"],
                            "submesh_depth_hwm": {
                                s["submesh"]: s["depth_hwm"]
                                for s in snap["submeshes"]
                            },
                            "bit_identical": True,
                        }
                    )
            finally:
                backend.shutdown()
    os.environ.pop("MINIO_TPU_PLACEMENT", None)
    os.environ.pop("MINIO_TPU_SUBMESH_DEVICES", None)

    def _cell(chips, policy, batch):
        for row in sweep:
            if (row["chips"], row["policy"], row["batch"]) == (
                chips, policy, batch,
            ):
                return row
        return None

    top = max(chip_counts)
    small, large = small_batches[0], large_batches[-1]
    acceptance = {}
    for pol in ("route", "auto"):
        a, s = _cell(top, pol, small), _cell(top, "span", small)
        if a and s:
            acceptance[f"small_batch_{pol}_vs_span_{top}chip"] = round(
                a["blocks_per_s"] / s["blocks_per_s"], 2
            )
    a, s = _cell(top, "auto", large), _cell(top, "span", large)
    if a and s:
        acceptance[f"large_batch_auto_vs_span_{top}chip"] = round(
            a["blocks_per_s"] / s["blocks_per_s"], 2
        )
    return {
        "metric": (
            f"multi-chip placement sweep (EC {k}+{m}, "
            f"{clients} clients, distinct object-size classes)"
        ),
        "geometry": {"k": k, "m": m, "lengths": lengths},
        "chip_counts": list(chip_counts),
        "policies": list(policies),
        "sweep": sweep,
        "acceptance": acceptance,
        "bit_identical_all_cells": True,
    }


def main() -> None:
    import argparse
    import os

    from minio_tpu.utils import jaxenv

    jaxenv.setup_compile_cache()  # before the first JAX use

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--codec-micro",
        action="store_true",
        help="run ONLY the fused-vs-split CPU encode+digest microbench "
        "(EC 8+4, 64 MiB batch) and print its JSON - the kernel win "
        "isolated from e2e noise",
    )
    ap.add_argument(
        "--get-degraded",
        action="store_true",
        help="run ONLY the degraded-path GET micro (one disk at ~20x "
        "median read latency; hedged reads + breaker preference hold "
        "the p99) and print its JSON",
    )
    ap.add_argument(
        "--put-readback",
        action="store_true",
        help="run ONLY the device-resident parity plane micro (PUT-ack "
        "D2H byte accounting, legacy vs digest-only + quorum-early "
        "drain, on-disk shard bit-identity) and print its JSON",
    )
    ap.add_argument(
        "--cache-micro",
        action="store_true",
        help="run ONLY the tiered read cache micro (cold=off oracle vs "
        "hot=host tier, size sweep + Zipf skew, bit-identity gated) "
        "and print its JSON (BENCH_r12 schema)",
    )
    ap.add_argument(
        "--select-micro",
        action="store_true",
        help="run ONLY the select pushdown micro (size x selectivity, "
        "device screen vs host vector vs row oracle, bit-identity + "
        "zero-fallback gated) and print its JSON (BENCH_r13 schema)",
    )
    ap.add_argument(
        "--concurrency",
        action="store_true",
        help="run ONLY the request-plane concurrency sweep (1..64 "
        "keep-alive clients, GET+PUT p50/p99 + shed counts, async "
        "event-loop plane vs threaded oracle) plus the connection-"
        "storm tier (10k-class keep-alive conns via an asyncio "
        "driver, slow-loris flood, pathological pipelining, tenant-"
        "cap overload - all correctness-gated before timing, async@1 "
        "vs async@N bit-identity) and print its JSON",
    )
    ap.add_argument(
        "--multichip",
        action="store_true",
        help="run ONLY the multi-chip placement sweep (1/2/4 chips x "
        "batch x span/route/auto through the batcher's submesh router, "
        "bit-identity gated) and print its JSON (MULTICHIP_r06 schema)",
    )
    args = ap.parse_args()
    if args.multichip:
        print(json.dumps(bench_multichip(), indent=1))
        return
    if args.concurrency:
        print(json.dumps(bench_concurrency_sweep(), indent=1))
        return
    if args.codec_micro:
        print(json.dumps(bench_codec_micro(), indent=1))
        return
    if args.get_degraded:
        print(json.dumps(bench_get_degraded(), indent=1))
        return
    if args.cache_micro:
        print(json.dumps(bench_cache_micro(), indent=1))
        return
    if args.select_micro:
        print(json.dumps(bench_select_micro(), indent=1))
        return
    if args.put_readback:
        print(json.dumps(bench_put_readback(), indent=1))
        return
    cpu = bench_cpu_baseline()
    # e2e config #2 (BASELINE.md): through the object layer.  Two codec
    # variants: the native CPU codec isolates the control-plane + disk
    # path; the device codec is the production shape.  Both reported;
    # see BENCH_NOTES.md.
    e2e_cpu = bench_e2e(codec_backend="cpu")
    small = os.environ.get("MINIO_BENCH_E2E_DEVICE", "small")
    if small == "off":
        e2e_dev = None
    elif small == "full":
        e2e_dev = bench_e2e(codec_backend="tpu")
    else:
        e2e_dev = bench_e2e(
            obj_mib=4, singles=3, threads=4, per_thread=1,
            codec_backend="tpu",
        )
    select_scan = bench_select_scan()
    grid = []
    headline = None
    for k, m in GRID:
        cfg = _bench_config(k, m, trials=5 if (k, m) == (EC_K, EC_M) else 3)
        grid.append(cfg)
        if (k, m) == (EC_K, EC_M):
            headline = cfg
    value = headline["combined_gibps"]
    baseline = cpu["combined_gibps"]
    spreads = [
        s
        for s in (
            headline["stats"]["encode"]["rel_spread"],
            headline["stats"]["reconstruct"]["rel_spread"],
        )
        if s is not None
    ]
    print(
        json.dumps(
            {
                "metric": (
                    "erasure encode+reconstruct GiB/s per chip "
                    f"(EC {EC_K}+{EC_M}, 1 MiB blocks)"
                ),
                "value": round(value, 2),
                "unit": "GiB/s",
                "vs_baseline": round(value / baseline, 2),
                "rel_spread": max(spreads) if spreads else None,
                "detail": {
                    "tpu": {
                        k2: round(v, 2)
                        for k2, v in headline.items()
                        if isinstance(v, float)
                    },
                    "cpu_avx2_baseline": {
                        k2: (round(v, 2) if isinstance(v, float) else v)
                        for k2, v in cpu.items()
                    },
                    "grid": [
                        {
                            k2: (round(v, 2) if isinstance(v, float) else v)
                            for k2, v in cfg.items()
                            if k2 != "stats"
                        }
                        for cfg in grid
                    ],
                    "timing_stats": headline["stats"],
                    "batch_blocks": BATCH,
                    "e2e_cpu_codec": {
                        k2: (round(v, 3) if isinstance(v, float) else v)
                        for k2, v in e2e_cpu.items()
                    },
                    "e2e_device_codec": (
                        {
                            k2: (
                                round(v, 3) if isinstance(v, float) else v
                            )
                            for k2, v in e2e_dev.items()
                        }
                        if e2e_dev
                        else None
                    ),
                    "select": select_scan,
                    # kernel-level call/byte/seconds telemetry
                    # accumulated across the e2e runs above, so the
                    # bench trajectory records what the codec seam
                    # actually executed (codec/telemetry.py)
                    "kernel_stats": _kernel_stats_snapshot(),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
