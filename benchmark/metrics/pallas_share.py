"""Kernels: share of the window's launches of the entry points with a Pallas form that ran it (kernel-stats.pallas_passes / .device_passes of PALLAS_KERNELS, window delta), percent."""
import ragged_readers


def read(run):
    return ragged_readers.pallas_share(run)
