"""Drives: ms that the rename_data which ended a PUT's last drive wait ran once a worker had it - the drive's own system calls without the hand-off (kernel-stats.fanout.put_rename.last_run_seconds / .count, window delta)."""
import ledger_readers


def read(run):
    ran = ledger_readers._table_moved(run, "fanout", "put_rename", "last_run_seconds")
    return ledger_readers._per(ran, ledger_readers._table_moved(run, "fanout", "put_rename", "count"))
