"""Erasure stream: GETs decoded by reconstruct with every drive of the set online, per GET stream in the window (kernel-stats.reconstruct.healthy_calls), percent."""
import defaults_readers


def read(run):
    return defaults_readers.healthy_reconstruct_share(run)
