"""The plain reference agrees with itself and with a second witness, the
program's CPU codec."""
import numpy as np
import pytest
import reference as R


def test_matrix_is_systematic():
    m = R.encode_matrix(8, 4)
    assert [list(r) for r in m[:8]] == [[int(i == j) for j in range(8)] for i in range(8)]
    assert all(any(r) for r in m[8:])


def test_any_k_of_n_decode():
    k, m = 8, 4
    rng = np.random.default_rng(1)
    data = [rng.integers(0, 256, 4096, dtype=np.uint8) for _ in range(k)]
    parity = R._apply([list(r) for r in R.encode_matrix(k, m)[k:]], data)
    shards = dict(enumerate(data + parity))
    for use in ([0, 1, 2, 3, 4, 5, 6, 7], [4, 5, 6, 7, 8, 9, 10, 11], [0, 2, 3, 5, 8, 9, 10, 11]):
        got = R.decode_shards(k, m, {i: shards[i] for i in use})
        assert all(np.array_equal(g, d) for g, d in zip(got, data))
    shards[9] = shards[9] ^ 1  # a parity shard that is wrong decodes to wrong data
    got = R.decode_shards(k, m, {i: shards[i] for i in [0, 1, 2, 3, 4, 5, 6, 9]})
    assert not np.array_equal(got[7], data[7])


def test_parity_agrees_with_the_programs_cpu_codec():
    backend = pytest.importorskip("minio_tpu.codec.backend")
    k, m = 8, 4
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (1, k, 2048), dtype=np.uint8)
    parity, _ = backend.CpuBackend().encode(data, m)
    want = R._apply([list(r) for r in R.encode_matrix(k, m)[k:]], list(data[0]))
    assert np.array_equal(parity[0], np.stack(want))


def test_store_model():
    s = R.StoreModel()
    assert s.live("a") is None
    assert s.put("a", 10) == 1 and s.live("a") == (1, 10)
    s.delete("a")
    assert s.live("a") is None
    assert s.put("a", 20) == 2 and s.live("a") == (2, 20)  # versions never repeat
    assert len(R.trailer("obj-00001", 12)) == R.TRAILER
