"""PUT kernel (jit_encode_words_fused1): share of its memory roofline in the traced slice, percent."""
import readers
import roofline


def read(run):
    return readers.kernel_roofline(run, "jit_encode_words_fused1", "encode_digest", roofline.encode_cost)
