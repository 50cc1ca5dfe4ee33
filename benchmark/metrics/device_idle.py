"""Share of the traced slice in which no operation ran on the device, percent."""
import readers


def read(run):
    return readers.device_idle(run)
