"""Codec seam: distinct staged widths launched inside the window (kernel-stats.ragged.staged_rows, the widths whose count moved)."""
import ragged_readers


def read(run):
    return ragged_readers.staged_widths(run)
