"""Served request: CPU of the handler's thread inside the root span s3_request, ms a request, every verb (kernel-stats.requests, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.request_cpu(run)
