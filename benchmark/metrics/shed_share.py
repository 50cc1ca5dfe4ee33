"""503s over requests answered in the window, percent."""
import readers


def read(run):
    return readers.shed_share(run)
