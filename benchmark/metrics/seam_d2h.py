"""Codec seam: ms per copy of a ready result to the host (kernel-stats.spans seam_d2h, window delta)."""
import span_readers


def read(run):
    return span_readers.seam_d2h(run)
