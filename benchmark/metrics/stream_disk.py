"""Erasure stream: seconds in drive I/O per stream, ms (kernel-stats.stages, window delta)."""
import readers


def read(run):
    return readers.stream_disk(run)
