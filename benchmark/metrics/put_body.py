"""Served request: ms of wall inside hashreader_read (the handler's reads of the body: the wait for the loop to hand it over, MD5 and SHA-256) per PUT (ol_put_object) (kernel-stats.spans, window delta)."""
import span_readers


def read(run):
    wall = span_readers.delta(run, "hashreader_read", "wall_seconds")
    puts = span_readers.delta(run, "ol_put_object", "count")
    return 1e3 * wall / puts if wall and puts else None
