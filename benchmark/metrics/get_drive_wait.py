"""Erasure stream: ms a GET waits for the shard reads of its block groups (kernel-stats.fanout.get_reads.wall_seconds / requests[GetObject].count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.get_drive_wait(run)
