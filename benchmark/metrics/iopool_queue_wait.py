"""Drives: ms a job sat in an iopool queue before its worker took it (kernel-stats.spans iopool_queue_wait, window delta)."""
import span_readers


def read(run):
    return span_readers.iopool_queue_wait(run)
