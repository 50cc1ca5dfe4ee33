"""MTPU502 fixture: a device-provenance value escapes D2H through a
helper — invisible to the per-file MTPU107/111 checks, caught by the
interprocedural pass (parameter taint flows through the call edge)."""

import numpy as np

from minio_tpu.ops import codec_step


def _to_host(arr):
    return np.asarray(arr)  # VIOLATION: MTPU502


def read_parity(words, parity_shards, shard_len):
    parity, digests = codec_step.encode_words_fused1(
        words, parity_shards, shard_len
    )
    return _to_host(parity)
