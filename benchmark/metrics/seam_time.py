"""Codec seam: host-observed ms per call (kernel-stats.ops, window delta)."""
import readers


def read(run):
    return readers.seam_time(run)
