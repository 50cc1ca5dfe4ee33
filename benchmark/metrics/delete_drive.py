"""Drives: ms of wall per xl_delete_file, one drive's removal of an object's files or of a replaced data dir, whichever way the drive goes about it (kernel-stats.spans, window delta)."""
import span_readers


def read(run):
    return span_readers.ms_per_count(run, "xl_delete_file")
