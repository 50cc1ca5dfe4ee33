"""Interpreter: mean lateness of the probe thread's 20 ms sleep, ms (kernel-stats.probe, window delta)."""
import span_readers


def read(run):
    return span_readers.gil_late(run)
