"""Idle share of the least idle device (mesh cells), percent."""
import readers


def read(run):
    return readers.device_idle_worst(run)
