"""MTPU503 fixture: device values captured by closures that cross a
worker-pool thread boundary — the eventual D2H becomes a hidden sync
on an arbitrary worker thread, outside every drain seam."""

from minio_tpu.ops import codec_step


def put_async(pool, words, parity_shards, shard_len):
    parity, digests = codec_step.encode_words_fused1(
        words, parity_shards, shard_len
    )

    def _work():
        return parity.sum()

    pool.submit("stripe-0", _work)  # VIOLATION: MTPU503


def put_async_lambda(pool, words, parity_shards, shard_len):
    parity, digests = codec_step.encode_words_fused1(
        words, parity_shards, shard_len
    )
    pool.submit("stripe-1", lambda: digests.sum())  # VIOLATION: MTPU503
