"""Served request: ms from ol_get_object's start to the first body bytes handed to the response writer, per GET (span get_first_write, kernel-stats.spans, window delta)."""
import stream_readers


def read(run):
    return stream_readers.get_first_write(run)
