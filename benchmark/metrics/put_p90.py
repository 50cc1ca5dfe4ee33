"""Open loop: 90th percentile of PUT latency from the time the request was due, ms."""
import readers


def read(run):
    return readers.open_tail(run, "PUT", 90)
