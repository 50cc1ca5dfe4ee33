"""MTPU502 twin: the device value materializes through a REGISTERED
drain seam (s3select drain_plane), whose return is a host fact — the
downstream bytes() is no longer a device escape."""

from minio_tpu.ops import codec_step
from minio_tpu.s3select import device as sdevice


def read_rows(words, parity_shards, shard_len, nbytes):
    parity, digests = codec_step.encode_words_fused1(
        words, parity_shards, shard_len
    )
    payload = sdevice.drain_plane(parity, nbytes)
    return bytes(payload)
