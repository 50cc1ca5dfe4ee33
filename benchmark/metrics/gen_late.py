"""Open loop: p95 of how late a request left after it was due, ms."""
import readers


def read(run):
    return readers.gen_late(run)
