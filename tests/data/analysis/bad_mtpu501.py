"""MTPU501 fixture: a buffer read after being passed at a donated
position of a registered donating entry point (the PR 14 bug class)."""

import jax.numpy as jnp

from minio_tpu.ops import codec_step


def put_object(data, parity_shards, shard_len):
    words = jnp.asarray(data)
    parity, digests = codec_step.encode_words_fused1(
        words, parity_shards, shard_len
    )
    checksum = words.sum()  # VIOLATION: MTPU501
    return parity, digests, checksum
