"""Codec seam: bytes over the host-device bus per payload byte completed."""
import readers


def read(run):
    return readers.bus_ratio(run)
