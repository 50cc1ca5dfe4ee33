"""Programs compiled or loaded from the cache inside the window (should be 0)."""
import readers


def read(run):
    return readers.window_compiles(run)
