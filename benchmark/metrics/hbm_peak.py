"""Peak device memory on the fullest chip, MiB."""
import readers


def read(run):
    return readers.hbm_peak(run)
