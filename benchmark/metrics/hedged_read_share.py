"""Erasure stream: hedges launched per shard read launched in the window (kernel-stats.hedge), percent."""
import defaults_readers


def read(run):
    return defaults_readers.hedged_read_share(run)
