"""Elastic multi-chip codec: partition-rule shardings, the compile
seam's geometry-keyed cache, batcher placement routing, and policy
bit-identity (parallel/rules.py + codec/batcher.py).

Runs on the virtual 8-device CPU mesh the conftest forces via
--xla_force_host_platform_device_count.
"""

import threading

import numpy as np
import pytest

from minio_tpu.codec.backend import CpuBackend, TpuBackend
from minio_tpu.codec.batcher import BatchingBackend
from minio_tpu.codec.telemetry import KERNEL_STATS
from minio_tpu.parallel import mesh as pm
from minio_tpu.parallel import rules


# -- partition-rule table -----------------------------------------------


def test_spec_for_covers_every_plane_family():
    P = rules.PartitionSpec
    expect = {
        "stripe_words": P("stripe", "shard", None),
        "stripe_bytes": P("stripe", "shard", None),
        "data_batch": P("stripe", "shard", None),
        "survivor_words": P("stripe", "shard", None),
        "data_digests": P("stripe", "shard", None),
        "parity_words": P("stripe", None, None),
        "parity_bytes": P("stripe", None, None),
        "parity_digests": P("stripe", None, None),
        "recon_words": P("stripe", None, None),
        "digest_rows": P(("stripe", "shard"), None),
        "digest_out": P(("stripe", "shard"), None),
        "seq_bytes": P(None, ("stripe", "shard")),
        "seq_parity": P(None, ("stripe", "shard")),
    }
    for name, spec in expect.items():
        assert tuple(rules.spec_for(name)) == tuple(spec), name


def test_spec_for_unknown_plane_raises():
    with pytest.raises(KeyError):
        rules.spec_for("mystery_plane")


def test_match_partition_rules_resolves_trees():
    specs = rules.match_partition_rules(
        ("stripe_words", ("parity_words", "data_digests"))
    )
    assert tuple(specs[0]) == ("stripe", "shard", None)
    assert tuple(specs[1][0]) == ("stripe", None, None)
    assert tuple(specs[1][1]) == ("stripe", "shard", None)


def test_rules_fingerprint_stable_and_content_keyed():
    fp = rules.rules_fingerprint()
    assert fp == rules.rules_fingerprint()
    # content hash, not table identity: a copied table fingerprints the same
    assert fp == rules.rules_fingerprint(tuple(rules.PARTITION_RULES))
    other = ((r"^x$", rules.PartitionSpec(None)),)
    assert rules.rules_fingerprint(other) != fp


# -- compile seam -------------------------------------------------------


def _raw_mesh(stripe, shard):
    """A fresh Mesh object each call (bypasses make_mesh's caching) so
    the seam's cache key, not object identity, is what's under test."""
    import jax
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[: stripe * shard]).reshape(
        stripe, shard
    )
    return Mesh(devs, ("stripe", "shard"))


def test_compile_cache_survives_mesh_rebuild():
    # (jax may intern equal Mesh objects; the seam must not rely on it —
    # its key is device ids + axis shape + names, never Mesh identity)
    m1 = _raw_mesh(4, 2)
    m2 = _raw_mesh(4, 2)
    fn1 = rules.compile_kernel("sharded_encode", m1, k=8, m=4)
    before = rules.cache_info()
    fn2 = rules.compile_kernel("sharded_encode", m2, k=8, m=4)
    after = rules.cache_info()
    assert fn1 is fn2
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 1


def test_compile_cache_misses_on_geometry_change():
    # the cache is process-global and other tests compile these
    # geometries too: start cold so the miss accounting is this test's
    rules.clear_compile_cache()
    rules.compile_kernel("sharded_encode", _raw_mesh(4, 2), k=8, m=4)
    before = rules.cache_info()
    rules.compile_kernel("sharded_encode", _raw_mesh(2, 4), k=8, m=4)
    assert rules.cache_info()["misses"] == before["misses"] + 1


def test_kernel_mode_tracks_geometry():
    # stripe-only, pure XLA: no cross-device collective, the seam picks
    # the global lowering under jit + NamedSharding
    assert rules.kernel_mode("sharded_encode", _raw_mesh(8, 1)) == "jit"
    # per-device bodies that may be Pallas calls stay under shard_map on
    # every geometry (XLA cannot partition a Mosaic kernel)
    for kind in (
        "mesh_encode_hash", "mesh_reconstruct", "mesh_verify_reconstruct"
    ):
        assert rules.kernel_mode(kind, _raw_mesh(8, 1)) == "shard_map"
    # sharded k: the per-shard partial-parity path needs the all-reduce
    assert (
        rules.kernel_mode("sharded_encode", _raw_mesh(4, 2)) == "shard_map"
    )
    assert (
        rules.kernel_mode("mesh_reconstruct", _raw_mesh(2, 4))
        == "shard_map"
    )
    # global-only kernels lower via jit on every geometry
    assert (
        rules.kernel_mode("sharded_encode_seq", _raw_mesh(4, 2)) == "jit"
    )
    assert rules.kernel_mode("mesh_digest", _raw_mesh(2, 4)) == "jit"


def test_registered_kernels_expose_rule_resolved_specs():
    for kind in rules.registered_kernels():
        kd = rules.kernel_def(kind)
        assert kd.in_specs() is not None
        assert kd.out_specs() is not None


# -- batch padding ------------------------------------------------------


def test_pad_batch_identity_when_already_sized():
    a = np.arange(24, dtype=np.uint32).reshape(2, 3, 4)
    assert pm._pad_batch(a, 2) is a


def test_pad_batch_zero_fills_the_tail():
    a = np.arange(24, dtype=np.uint32).reshape(2, 3, 4)
    padded = pm._pad_batch(a, 5)
    assert padded.shape == (5, 3, 4)
    assert padded.dtype == a.dtype
    np.testing.assert_array_equal(padded[:2], a)
    assert not padded[2:].any()


# -- placement routing --------------------------------------------------


def _devices(n):
    import jax

    return tuple(jax.devices()[:n])


def test_router_carves_contiguous_submeshes_with_remainder():
    r = rules.PlacementRouter(
        _devices(5), policy="route", submesh_devices=2
    )
    widths = [len(s.devices) for s in r.submeshes]
    assert widths == [2, 3]  # remainder folds into the last submesh
    flat = tuple(d for s in r.submeshes for d in s.devices)
    assert flat == _devices(5)


def test_router_least_loaded_and_release():
    r = rules.PlacementRouter(
        _devices(4), policy="route", submesh_devices=2
    )
    a = r.route(1)
    b = r.route(1)
    assert a is not None and b is not None and a is not b
    assert r.depths() == {"sub0": 1, "sub1": 1}
    r.release(a)
    assert r.route(1) is a  # freed submesh is least-loaded again
    r.release(a)
    r.release(b)
    assert set(r.depths().values()) == {0}


def test_router_span_policy_and_auto_threshold():
    span = rules.PlacementRouter(
        _devices(4), policy="span", submesh_devices=2
    )
    assert span.route(1) is None
    auto = rules.PlacementRouter(
        _devices(4), policy="auto", submesh_devices=2
    )
    # enough stripes to occupy every device: span the mesh
    assert auto.route(4) is None
    # small batch: route to a submesh
    assert auto.route(1) is not None
    # a single submesh can't route anywhere
    solo = rules.PlacementRouter(
        _devices(2), policy="route", submesh_devices=2
    )
    assert solo.route(1) is None


def test_placed_scopes_devices_to_the_thread():
    assert rules.current_placement() is None
    seen = {}
    with rules.placed(_devices(2)):
        assert rules.current_placement() == _devices(2)

        def probe():
            seen["other"] = rules.current_placement()

        t = threading.Thread(target=probe)
        t.start()
        t.join()
    assert seen["other"] is None  # thread-local, not process-global
    assert rules.current_placement() is None


# -- policy bit-identity ------------------------------------------------


def _data(batch, k=4, length=64, seed=0):
    return np.random.default_rng(seed + batch).integers(
        0, 256, (batch, k, length), dtype=np.uint8
    )


@pytest.mark.parametrize("policy", ["span", "route", "auto"])
@pytest.mark.parametrize("batch", [1, 3, 5, 16])
def test_policy_bit_identity(monkeypatch, policy, batch):
    """encode/digest/reconstruct are bit-identical whether a batch
    spans the mesh, routes to a submesh, or runs single-device."""
    monkeypatch.setenv("MINIO_TPU_PLACEMENT", policy)
    monkeypatch.setenv("MINIO_TPU_SUBMESH_DEVICES", "2")
    ref = CpuBackend()
    b = BatchingBackend(TpuBackend(), deadline_s=0.01)
    try:
        data = _data(batch)
        p1, d1 = b.encode(data, 2)
        p2, d2 = ref.encode(data, 2)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        shards = np.concatenate([data, np.asarray(p1)], axis=1)
        present = (False, True, True, True, True, False)
        r1 = b.reconstruct(shards, present, 4, 2)
        r2 = ref.reconstruct(shards, present, 4, 2)
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        np.testing.assert_array_equal(
            np.asarray(b.digest(shards)), np.asarray(ref.digest(shards))
        )
    finally:
        b.shutdown()


def test_single_device_backend_matches_cpu(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_PLACEMENT", "auto")
    tpu = TpuBackend(devices=_devices(1))
    assert tpu.placement_router() is None  # nothing to carve
    data = _data(3)
    p1, d1 = tpu.encode(data, 2)
    p2, d2 = CpuBackend().encode(data, 2)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


# -- disjoint-submesh overlap -------------------------------------------


class _BlockingBackend(CpuBackend):
    """Encode blocks until released so the test can observe two merged
    batches in flight on disjoint submeshes at the same time."""

    def __init__(self, router):
        self._router = router
        self.started = threading.Semaphore(0)
        self.unblock = threading.Event()

    def placement_router(self):
        return self._router

    def encode(self, data, m, lengths=None):
        self.started.release()
        assert self.unblock.wait(10), "test never released the encode"
        return super().encode(data, m, lengths)


def test_two_batches_overlap_on_disjoint_submeshes():
    KERNEL_STATS.reset()
    router = rules.PlacementRouter(
        _devices(4), policy="route", submesh_devices=2
    )
    inner = _BlockingBackend(router)
    b = BatchingBackend(inner, deadline_s=0.01)
    results = {}
    try:
        # different lengths -> different merge keys -> two groups, each
        # routed to its own submesh worker
        def client(tag, length):
            data = _data(2, length=length, seed=hash(tag) % 97)
            results[tag] = (data, b.encode(data, 2))

        t1 = threading.Thread(target=client, args=("a", 64))
        t2 = threading.Thread(target=client, args=("b", 128))
        t1.start()
        t2.start()
        assert inner.started.acquire(timeout=10)
        assert inner.started.acquire(timeout=10)
        # both encodes are running right now: both submeshes occupied
        depths = router.depths()
        assert depths["sub0"] >= 1 and depths["sub1"] >= 1
        inner.unblock.set()
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert not t1.is_alive() and not t2.is_alive()
    finally:
        inner.unblock.set()
        b.shutdown()
    for tag, (data, (parity, digests)) in results.items():
        p, d = CpuBackend().encode(data, 2)
        np.testing.assert_array_equal(np.asarray(parity), p)
        np.testing.assert_array_equal(np.asarray(digests), d)
    snap = KERNEL_STATS.snapshot()
    assert snap["placement"]["route"] >= 2
    hwm = {s["submesh"]: s["depth_hwm"] for s in snap["submeshes"]}
    assert hwm.get("sub0", 0) > 0 and hwm.get("sub1", 0) > 0


def test_auto_policy_routes_only_throughput_ops():
    """Under "auto", reconstruct/digest (the degraded-read and verify
    plane) stay on the span path; encode routes.  An explicit "route"
    policy routes everything."""

    class _RouterBackend(CpuBackend):
        def __init__(self, router):
            self._router = router

        def placement_router(self):
            return self._router

    KERNEL_STATS.reset()
    router = rules.PlacementRouter(
        _devices(4), policy="auto", submesh_devices=2
    )
    b = BatchingBackend(_RouterBackend(router), deadline_s=0.01)
    try:
        data = _data(2)
        parity, _ = b.encode(data, 2)
        shards = np.concatenate([data, np.asarray(parity)], axis=1)
        snap_mid = KERNEL_STATS.snapshot()["placement"]
        assert snap_mid["route"] >= 1  # small-batch encode routed
        b.digest(shards)
        b.reconstruct(
            shards, (False, True, True, True, True, False), 4, 2
        )
        snap = KERNEL_STATS.snapshot()["placement"]
        assert snap["route"] == snap_mid["route"]  # neither op routed
        assert snap["span"] >= snap_mid["span"] + 2
    finally:
        b.shutdown()


def test_placement_families_render_in_prometheus_text():
    from minio_tpu.server.metrics import Metrics

    KERNEL_STATS.reset()
    KERNEL_STATS.record_placement("route")
    KERNEL_STATS.record_submesh_depths({"sub0": 1, "sub1": 0})
    text = Metrics().render().decode()
    assert 'miniotpu_codec_placement_total{policy="route"} 1' in text
    assert 'miniotpu_codec_placement_total{policy="span"} 0' in text
    assert (
        'miniotpu_codec_submesh_queue_depth{submesh="sub0"} 1' in text
    )
    assert (
        'miniotpu_codec_submesh_queue_depth_peak{submesh="sub0"} 1'
        in text
    )


def test_instrumented_backend_delegates_placement_router():
    from minio_tpu.codec.telemetry import instrument

    router = rules.PlacementRouter(
        _devices(4), policy="route", submesh_devices=2
    )
    inner = _BlockingBackend(router)
    wrapped = instrument(inner)
    assert wrapped.placement_router() is router
    assert instrument(CpuBackend()).placement_router() is None
