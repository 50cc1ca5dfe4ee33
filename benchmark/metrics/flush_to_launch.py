"""Batcher: ms from a flush leaving the queue to the seam's first jitted call (kernel-stats.spans flush_to_launch, window delta)."""
import span_readers


def read(run):
    return span_readers.flush_to_launch(run)
