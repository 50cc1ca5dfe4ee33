"""Reconstruct kernel under hedged reads (jit_reconstruct_words_batch): share of its roofline in the traced slice for k rows read and the rebuilt rows written, percent."""
import defaults_readers


def read(run):
    return defaults_readers.hedge_reconstruct_roofline(run)
