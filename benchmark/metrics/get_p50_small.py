"""Served request: client median of the window's GETs of the three smallest object sizes, ms (generator's clock; Record.nbytes)."""
import ragged_readers


def read(run):
    return ragged_readers.get_p50_small(run)
