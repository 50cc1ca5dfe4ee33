"""Drives: ms a PUT waits for its three drive fan-outs - flushes to quorum, writers' close, rename_data - each by its wall, not a sum over jobs (kernel-stats.fanout / requests[PutObject].count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.put_drive_wait(run)
