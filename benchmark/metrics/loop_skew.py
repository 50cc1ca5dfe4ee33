"""Request plane, admission: the busiest event loop's requests x loops / all requests - 1 where the connections hashed evenly (kernel-stats.loops, window delta)."""
import ledger_readers


def read(run):
    return ledger_readers.loop_skew(run)
