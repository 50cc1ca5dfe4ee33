"""Request plane: ms of CPU a request on the handler threads, by the scheduler's account (kernel-stats.cpu.handler / s3_request.count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.cpu_per_request(run, "handler")
