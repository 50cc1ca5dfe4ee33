"""Open loop: 95th percentile of GET latency from the time the request was due, ms."""
import readers


def read(run):
    return readers.open_tail(run, "GET", 95)
