"""Cross-request codec batching (codec/batcher.py): identical results,
actual coalescing under concurrency, error propagation."""

import threading

import numpy as np
import pytest

from minio_tpu.codec.backend import CpuBackend
from minio_tpu.codec.batcher import BatchingBackend


class _CountingBackend(CpuBackend):
    """Counts inner calls so tests can assert coalescing happened."""

    def __init__(self):
        self.encode_calls = 0
        self.digest_calls = 0
        self.reconstruct_calls = 0

    def encode(self, data, m, lengths=None):
        self.encode_calls += 1
        return super().encode(data, m, lengths)

    def digest(self, shards, lengths=None):
        self.digest_calls += 1
        return super().digest(shards, lengths)

    def reconstruct(self, shards, present, k, m):
        self.reconstruct_calls += 1
        return super().reconstruct(shards, present, k, m)


@pytest.fixture
def inner():
    return _CountingBackend()


@pytest.fixture
def batched(inner):
    b = BatchingBackend(inner, deadline_s=0.05)
    yield b
    b.shutdown()


def _data(batch=3, k=4, length=64, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (batch, k, length), dtype=np.uint8
    )


def test_results_identical(batched):
    ref = CpuBackend()
    data = _data()
    p1, d1 = batched.encode(data, 2)
    p2, d2 = ref.encode(data, 2)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(d1, d2)
    shards = np.concatenate([data, p1], axis=1)
    present = (False, True, True, True, True, False)
    r1 = batched.reconstruct(shards, present, 4, 2)
    r2 = ref.reconstruct(shards, present, 4, 2)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(
        batched.digest(shards), ref.digest(shards)
    )
    np.testing.assert_array_equal(
        batched.verify(shards, d1), ref.verify(shards, d1)
    )


def test_concurrent_encodes_coalesce(inner, batched):
    """8 same-geometry encodes from 8 threads -> far fewer inner calls,
    every result correct."""
    ref = CpuBackend()
    datas = [_data(seed=i) for i in range(8)]
    expected = [ref.encode(d, 2) for d in datas]
    results = [None] * 8
    barrier = threading.Barrier(8)

    def work(i):
        barrier.wait()
        results[i] = batched.encode(datas[i], 2)

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(8):
        np.testing.assert_array_equal(results[i][0], expected[i][0])
        np.testing.assert_array_equal(results[i][1], expected[i][1])
    # with an 8-thread barrier release and a 50 ms deadline, the
    # dispatcher must have merged most submissions
    assert inner.encode_calls < 8


def test_single_stream_no_deadline_wait(inner):
    """A lone client flushes immediately (active == queued)."""
    import time

    b = BatchingBackend(inner, deadline_s=5.0)  # painful if waited
    try:
        t0 = time.monotonic()
        b.encode(_data(), 2)
        assert time.monotonic() - t0 < 1.0
    finally:
        b.shutdown()


def test_mixed_geometry_not_merged(inner, batched):
    """Different shard lengths stay separate calls but both succeed."""
    ref = CpuBackend()
    a, bdat = _data(length=64), _data(length=128)
    ra = batched.encode(a, 2)
    rb = batched.encode(bdat, 2)
    np.testing.assert_array_equal(ra[0], ref.encode(a, 2)[0])
    np.testing.assert_array_equal(rb[0], ref.encode(bdat, 2)[0])


def test_pipelined_clients_flush_without_deadline(inner):
    """Double-buffering clients hold an un-ended handle while they
    submit the next batch.  Counting those held handles as 'still
    coming' used to stall every flush to the full deadline; counting
    DISTINCT submitting clients instead fires the fast path as soon as
    each pipelined client has one job queued."""
    import time

    b = BatchingBackend(inner, deadline_s=2.0)  # painful if waited
    n_clients = 3
    barrier = threading.Barrier(n_clients)
    elapsed = [None] * n_clients
    results = [None] * n_clients
    datas = [_data(seed=10 + i) for i in range(n_clients)]

    def work(i):
        # batch 1 held open across batch 2's submission, like the
        # erasure encoder's double buffer
        h1 = b.encode_begin(datas[i], 2)
        barrier.wait()
        t0 = time.monotonic()
        h2 = b.encode_begin(_data(seed=20 + i), 2)
        b.encode_end(h2)
        elapsed[i] = time.monotonic() - t0
        results[i] = b.encode_end(h1)

    threads = [
        threading.Thread(target=work, args=(i,))
        for i in range(n_clients)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        ref = CpuBackend()
        for i in range(n_clients):
            assert elapsed[i] is not None and elapsed[i] < 1.0, (
                f"client {i} stalled {elapsed[i]}s waiting for a "
                "deadline flush"
            )
            np.testing.assert_array_equal(
                results[i][0], ref.encode(datas[i], 2)[0]
            )
    finally:
        b.shutdown()


def test_error_propagates(batched):
    with pytest.raises(Exception):
        # reconstruct with too few survivors must raise in the caller
        shards = _data(batch=1, k=6, length=64)
        batched.reconstruct(
            shards, (False, False, False, True, True, True), 4, 2
        )
