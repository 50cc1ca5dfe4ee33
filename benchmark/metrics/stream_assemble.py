"""Erasure stream: host seconds assembling blocks per stream, ms (kernel-stats.stages, window delta)."""
import readers


def read(run):
    return readers.stream_assemble(run)
