"""Object bytes PUT and GET completed in the window over the window's length, MiB/s."""
import readers


def read(run):
    return readers.payload_rate(run)
