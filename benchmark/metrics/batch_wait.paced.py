"""Batcher: queue wait per job in the paced cell, ms."""
import readers


def read(run):
    return readers.batch_wait(run)
