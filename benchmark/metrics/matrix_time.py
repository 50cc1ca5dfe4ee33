"""Codec seam: ms per look-up of a loss pattern's survivors and matrix (kernel-stats.spans seam_matrix, window delta)."""
import defaults_readers


def read(run):
    return defaults_readers.matrix_time(run)
