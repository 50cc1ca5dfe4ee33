"""Served request: percent of the requests' wall on their own threads that is the self time of s3_request and the four ol_* spans - inside no named child (kernel-stats.requests, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.unspanned_share(run)
