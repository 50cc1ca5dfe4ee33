"""Codec seam: ms per block_until_ready on a result (kernel-stats.spans seam_kernel_wait, window delta)."""
import span_readers


def read(run):
    return span_readers.seam_kernel_wait(run)
