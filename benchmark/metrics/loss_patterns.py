"""Codec seam: survivor sets first decoded from inside the window (kernel-stats.reconstruct.patterns_seen, window delta)."""
import defaults_readers


def read(run):
    return defaults_readers.loss_patterns(run)
