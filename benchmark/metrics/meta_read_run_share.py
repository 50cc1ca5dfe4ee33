"""Metadata reads: CPU over wall inside meta_read_all (the loop of xl_read_version over the drives), percent (kernel-stats.spans, window delta)."""
import span_readers


def read(run):
    return span_readers.meta_read_run_share(run)
