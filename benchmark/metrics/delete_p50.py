"""Median DELETE latency in the window, ms."""
import readers


def read(run):
    return readers.median_ms(run, "DELETE")
