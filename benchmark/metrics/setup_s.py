"""Process start to window open, seconds."""
import readers


def read(run):
    return run.t0 - run.t_process_start
