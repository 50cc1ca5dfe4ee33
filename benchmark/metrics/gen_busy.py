"""Cores used by the busiest generator process over the window."""
import readers


def read(run):
    return readers.gen_busy(run)
