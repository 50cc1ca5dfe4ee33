"""Device idle share of the traced slice in the paced cell, percent."""
import readers


def read(run):
    return readers.device_idle(run)
