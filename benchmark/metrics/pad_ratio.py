"""Codec seam: bytes staged to the device per byte of shard the callers brought (kernel-stats.ragged.staged_bytes / .true_bytes, window delta); 1.0 where every row lies on a rung of the width ladder."""
import ragged_readers


def read(run):
    return ragged_readers.pad_ratio(run)
