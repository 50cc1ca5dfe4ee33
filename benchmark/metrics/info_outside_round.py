"""Object layer, metadata: ms of ol_get_object_info that are not its round - the mean wall of a STAT's call into the set less the mean wall of a round of meta_read_all: the bucket question, the snapshots of live drives, the quorum pick (kernel-stats.spans, window delta)."""
import span_readers


def read(run):
    info = span_readers.ms_per_count(run, "ol_get_object_info")
    meta_round = span_readers.ms_per_count(run, "meta_read_all")
    return None if info is None or meta_round is None else info - meta_round
