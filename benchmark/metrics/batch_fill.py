"""Batcher: jobs per flush (kernel-stats.batch, window delta)."""
import readers


def read(run):
    return readers.batch_fill(run)
