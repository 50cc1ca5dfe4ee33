"""Batcher: queue wait per job, ms (kernel-stats.batch, window delta)."""
import readers


def read(run):
    return readers.batch_wait(run)
