"""Open loop: requests due and unanswered when the window closed."""
import readers


def read(run):
    return readers.backlog_end(run)
