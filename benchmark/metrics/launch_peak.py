"""Codec seam: the largest input of one launch of the served entry points inside the window, MiB (kernel-stats.launch.sizes, the largest key whose count moved)."""
import stream_readers


def read(run):
    return stream_readers.launch_peak(run)
