"""Drive I/O pool: busy seconds over window times queues, percent."""
import readers


def read(run):
    return readers.iopool_busy(run)
