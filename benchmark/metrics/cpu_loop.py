"""Request plane: ms of CPU a request on the event-loop threads: recv, the hand-overs, the response writer (kernel-stats.cpu.loop / s3_request.count, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.cpu_per_request(run, "loop")
