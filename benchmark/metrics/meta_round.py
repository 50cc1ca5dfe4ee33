"""Metadata reads: ms of wall per round of meta_read_all, the read of xl.meta from every drive of the set (kernel-stats.spans, window delta)."""
import span_readers


def read(run):
    return span_readers.ms_per_count(run, "meta_read_all")
