"""Erasure stream: ms a GET's handler waits for the batch its read-ahead is decoding, per wait (wait stream_readahead_wait, kernel-stats.spans, window delta); a one-batch GET has none."""
import stream_readers


def read(run):
    return stream_readers.readahead_wait(run)
