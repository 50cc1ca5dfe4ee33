"""Request plane: ms a request sat in a loop's handler queue before a worker took it (kernel-stats.spans aio_queue_wait, window delta)."""
import span_readers


def read(run):
    return span_readers.handler_queue_wait(run)
