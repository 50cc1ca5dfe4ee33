"""Erasure stream: erasure blocks per stream, PUT and GET (kernel-stats.stream.<direction>.blocks / .streams, window delta); 1.0 where every object is one block, 7.0 for 64 MiB objects."""
import stream_readers


def read(run):
    return stream_readers.stream_blocks(run)
