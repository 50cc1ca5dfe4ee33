"""Healthy-read digest (jit_digest_words): share of its memory roofline in the traced slice, percent."""
import readers
import roofline


def read(run):
    return readers.kernel_roofline(run, "jit_digest_words", "digest", roofline.digest_cost)
