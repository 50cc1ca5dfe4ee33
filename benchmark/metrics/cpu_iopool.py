"""Drives: ms of CPU a request on the iopool workers: the drive calls and the hand-off (kernel-stats.cpu.iopool / s3_request.count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.cpu_per_request(run, "iopool")
