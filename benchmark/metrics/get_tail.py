"""Closed loop: p95 of GET latency on the generator's clock, ms (a reading, not a judge)."""
import readers


def read(run):
    return readers.closed_tail(run, "GET", 95)
