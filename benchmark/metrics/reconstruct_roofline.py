"""Degraded-read kernel (jit_reconstruct_words_batch): share of its roofline in the traced slice, percent."""
import readers
import roofline


def read(run):
    return readers.kernel_roofline(run, "jit_reconstruct_words_batch", "reconstruct", roofline.reconstruct_cost)
