"""Device, host side: ms of CPU the whole process burns a request, every thread of it, the runtime's too (kernel-stats.cpu.process_seconds / s3_request.count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.cpu_per_request(run, "process_seconds")
