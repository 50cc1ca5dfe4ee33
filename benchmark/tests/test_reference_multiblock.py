"""The plain reference on an object of many blocks, against a second witness,
the program's CPU codec.  (A file of its own: a PR that adds a cell may add
files to the benchmark and edit none.)"""
import numpy as np
import pytest
import reference as R


def test_a_multi_block_object_with_a_ragged_tail_decodes_from_any_8_of_12(tmp_path):
    """Six full blocks and a 0.4-block tail in one shard file a drive, as the
    program's CPU codec and its framing lay them out (32 digest bytes before
    every shard block, a block's shard padded to 32 bytes): ``decode_object``
    walks all seven blocks, from 8 shards with every parity shard among them."""
    backend = pytest.importorskip("minio_tpu.codec.backend")
    k, m, block = 8, 4, 65536
    size = 6 * block + 26214
    rng = np.random.default_rng(7)
    body = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    files = [open(tmp_path / f"part.{i}", "wb") for i in range(k + m)]
    for off in range(0, size, block):
        piece = np.frombuffer(body[off:off + block], dtype=np.uint8)
        ss = -(-len(piece) // k)
        padded = (ss + R.ALIGN - 1) // R.ALIGN * R.ALIGN
        data = np.zeros((1, k, padded), dtype=np.uint8)
        flat = np.zeros(k * ss, dtype=np.uint8)
        flat[:len(piece)] = piece
        data[0, :, :ss] = flat.reshape(k, ss)
        parity, _ = backend.CpuBackend().encode(data, m)
        for i, f in enumerate(files):
            f.write(b"\0" * R.FRAME_DIGEST)  # the digest's value is the program's own
            f.write((data[0, i] if i < k else parity[0, i - k]).tobytes())
    for f in files:
        f.close()
    parts = {i: str(tmp_path / f"part.{i}") for i in range(k + m)}
    assert len({len(b) for b in R._shard_blocks(parts[0], size, k, block)}) == 2  # 8192, 3296
    for use in ([0, 2, 5, 7, 8, 9, 10, 11], list(range(8)), [1, 2, 3, 4, 5, 6, 9, 11]):
        assert R.decode_object(parts, use, size, k, m, block) == body
    with open(parts[9], "r+b") as f:  # one byte of the tail block's parity
        f.seek(6 * (R.FRAME_DIGEST + 8192) + R.FRAME_DIGEST + 5)
        f.write(b"\xff")
    got = R.decode_object(parts, [0, 2, 5, 7, 8, 9, 10, 11], size, k, m, block)
    assert got[:6 * block] == body[:6 * block] and got != body
