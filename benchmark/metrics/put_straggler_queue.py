"""Drives: ms a PUT that the job which ended each of its three drive waits had sat in its drive's queue (kernel-stats.fanout.last_queue_seconds / requests[PutObject].count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.put_straggler_queue(run)
