"""Mesh placement: batches spanned over devices among spanned and routed, percent."""
import readers


def read(run):
    return readers.spanned_share(run)
