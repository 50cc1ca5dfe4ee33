"""Batcher: ms of CPU a request on the codec dispatcher thread: staging, launches, read-back (kernel-stats.cpu.batcher / s3_request.count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.cpu_per_request(run, "batcher")
