"""S3 requests completed in the window over the window's length."""
import readers


def read(run):
    return readers.op_rate(run)
