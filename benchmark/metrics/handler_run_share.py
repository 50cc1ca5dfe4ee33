"""Served request: CPU over wall inside s3_request on the handler threads, percent (kernel-stats.spans, window delta)."""
import span_readers


def read(run):
    return span_readers.handler_run_share(run)
