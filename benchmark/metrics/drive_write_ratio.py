"""Bytes the sampled objects hold on the drives per byte of user data."""
import readers


def read(run):
    return readers.drive_write_ratio(run)
