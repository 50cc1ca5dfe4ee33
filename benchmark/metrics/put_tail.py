"""Closed loop: p95 of PUT latency on the generator's clock, ms (a reading, not a judge)."""
import readers


def read(run):
    return readers.closed_tail(run, "PUT", 95)
